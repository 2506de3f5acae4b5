#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Each run is one process on the machine that holds the chip.  It looks up
the cell in ``BENCHMARK.json``, reads ``benchmark/workloads/<cell>.json``
(the configuration's name, the traffic module and its parameters) and
``benchmark/configs/<config>.json`` (data, index and service), then:

1. set-up: makes the corpus and the query set on the device from the seed
   (``datagen.py``), computes the exact top-k of the evaluation queries
   (``exact.py``, not counted in ``setup_s``), builds the index through
   ``IndexSpec.build``, frees the raw corpus, builds ``AnnService`` and
   warms up the shapes the traffic will send (``traffic/<name>.py``);
2. the window: the traffic module offers its load for ``--seconds``; with
   ``--trace 1`` under the JAX profiler;
3. the check, once the window has closed and the service is shut down: a
   sample of the window's answers against the plain IVF-PQ reference, and
   the stored cluster and code of a sample of corpus rows against those
   the reference derives from the built centroids and codebooks
   (``reference.py``); recall@k against the exact top-k is a metric.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number beside its
limit).  The cell's end-to-end metrics are reported with ``--trace 0``
and its per-layer metrics, read by ``metrics/<name>.py`` from the run's
record, with ``--trace 1``.  Nothing here names a cell, a configuration
or a metric: all of them are found by name in those files.

The run exits non-zero, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str):
    """(end-to-end, per-layer) metric entries of ``BENCHMARK.json`` that
    the cell reports."""
    def applies(m):
        return "workloads" not in m or cell in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (cell in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return e2e, per


def compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``<checkout>/.jax_cache``."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class Compiles:
    """Counts XLA compilations (a persistent-cache hit counts too)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration_s: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Stream:
    """The pool's queries in the order the run sends them (wrapping)."""

    def __init__(self, pool: np.ndarray, order: np.ndarray):
        self.pool, self.order, self.pos = pool, order, 0
        self.dim = pool.shape[1]

    def take(self, n: int):
        idx = self.order[(self.pos + np.arange(n)) % len(self.order)]
        self.pos += n
        return idx, self.pool[idx]


def check_device(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def build_index(cfg: dict, seed: int, rec: dict, layout: bool = True):
    """Data on the device, the exact top-k of the evaluation set, the
    build, and a host copy of the corpus rows whose stored cluster and
    codes the check re-derives; frees the raw corpus.  Returns the index
    spec, the built handle, the query set on the host, the exact top-k
    and the seconds the exact top-k took.  ``layout=False`` leaves out the
    padded layout the service scans (the build's control needs no
    service)."""
    import jax

    import datagen
    import exact

    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    from repro.service import IndexSpec

    data, gen = cfg["data"], cfg["generator"]
    st = datagen.structure(data, gen)
    rot = datagen.rotation(gen, int(data["dim"]))
    corpus = datagen.corpus(data, gen, st, rot)
    pool_dev = datagen.query_pool(data, gen, st, rot)
    pool = np.asarray(pool_dev, np.float32)
    n_eval = int(data["eval_queries"])

    t = time.perf_counter()
    _, gt = exact.exact_topk(corpus, pool_dev[:n_eval],
                             k=int(cfg["service"]["k"]))
    gt = np.asarray(gt)
    gt_s = time.perf_counter() - t
    del pool_dev, rot

    t = time.perf_counter()
    ispec = IndexSpec(**cfg["index"])
    handle = ispec.build(corpus)
    jax.block_until_ready(handle.clusters if layout else handle.ivf)
    rec["build_s"] = time.perf_counter() - t
    rows = build_sample(seed, int(data["n"]),
                        int(cfg["correct"]["build_sample"]))
    rec["build_rows"] = (rows, np.asarray(corpus[rows]))
    del corpus
    gc.collect()
    return ispec, handle, pool, gt, gt_s


def setup(cfg: dict, wl: dict, seed: int, rec: dict):
    """Data, exact top-k, build, service, warm-up.  Returns the service,
    the query stream, the exact top-k of the evaluation set and the
    seconds the exact top-k took."""
    import datagen

    ispec, handle, pool, gt, gt_s = build_index(cfg, seed, rec)
    from repro.service import AnnService, ServiceSpec

    sspec = ServiceSpec(index=ispec, **cfg["service"])
    svc = AnnService.build(sspec, index=handle)
    ivf = handle.ivf
    rec["index"] = {
        "sizes": np.asarray(ivf.sizes), "centroids": np.asarray(
            ivf.centroids), "m": int(ivf.codebook.m),
        "cb": int(ivf.codebook.cb), "nprobe": int(sspec.nprobe),
        "table_bytes": int(ivf.centroids.nbytes
                           + ivf.codebook.codebooks.nbytes),
        "n": int(np.asarray(ivf.sizes).sum())}
    data = cfg["data"]
    stream = Stream(pool, datagen.stream_order(
        seed, int(data["queries"]), int(data["eval_queries"])))
    traffic = load_module("traffic", wl["traffic"])
    traffic.warmup(svc, stream, wl["params"])
    return svc, stream, gt, gt_s, traffic


def index_arrays(ivf) -> tuple:
    """Host copies of a built index's public arrays."""
    if ivf.rotation is not None:
        raise ValueError("the reference does not implement OPQ rotations")
    return (np.asarray(ivf.centroids), np.asarray(ivf.codebook.codebooks),
            np.asarray(ivf.codes), np.asarray(ivf.ids),
            np.asarray(ivf.offsets))


def sample(seed: int, answers: list, n: int) -> np.ndarray:
    """Which of the window's answers are checked: ``n`` drawn by the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xC4]))
    n = min(int(n), len(answers))
    return np.sort(rng.choice(len(answers), size=n, replace=False))


def build_sample(seed: int, n_rows: int, n: int) -> np.ndarray:
    """Which corpus rows the build check re-derives: ``n`` drawn by the
    seed."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xB1]))
    return np.sort(rng.choice(n_rows, size=min(int(n), n_rows),
                              replace=False))


def recall(cfg: dict, answers: list, gt: np.ndarray) -> float:
    """Mean recall@k of the evaluation set's answers against the exact
    top-k."""
    import exact

    n_eval = int(cfg["data"]["eval_queries"])
    evals = [(i, ids) for i, ids in answers if i < n_eval]
    if not evals:
        return 0.0
    return exact.recall_at_k(np.stack([ids for _, ids in evals]),
                             gt[[i for i, _ in evals]])


def check(cfg: dict, seed: int, ref, stream: Stream, answers: list,
          build_rows: tuple) -> dict:
    """The compared numbers, each beside its limit."""
    import reference

    lim, svc_cfg = cfg["correct"], cfg["service"]
    pick = sample(seed, answers, lim["sample"])
    gap = reference.max_gap(
        ref, stream.pool[[answers[j][0] for j in pick]],
        [answers[j][1] for j in pick], int(svc_cfg["nprobe"]),
        int(svc_cfg["k"]), svc_cfg["lut_dtype"])
    rows, vectors = build_rows
    return {
        "adc_gap": {"value": gap, "limit": float(lim["adc_gap_max"]),
                    "rule": "<="},
        "build_mismatch": {
            "value": reference.build_mismatch(ref, vectors, rows),
            "limit": float(lim["build_mismatch_max"]), "rule": "<="},
        "checked": {"value": len(pick), "limit": 1, "rule": ">="},
    }


def holds(c: dict) -> bool:
    v = c["value"]
    return (math.isfinite(v) and
            (v <= c["limit"] if c["rule"] == "<=" else v >= c["limit"]))


def execute(cell: str, seed: int, seconds: float, trace: bool, *,
            bench: dict, cfg: dict, wl: dict, require_tpu: bool = True,
            t_start: float | None = None, keep: dict | None = None) -> dict:
    """One run of a cell; returns the result object (``checks`` last).
    ``keep``, where given, receives the run's record, stream and
    reference index."""
    import reference

    t_start = time.perf_counter() if t_start is None else t_start
    chips = next(w["chips"] for w in bench["workloads"] if w["name"] == cell)
    if require_tpu:
        check_device(chips)
    import jax

    devs = jax.devices()
    peak_table = peaks(devs[0].device_kind) if trace else None
    if require_tpu:
        compile_cache()
    compiles = Compiles()
    e2e_defs, per_defs = cell_metrics(bench, cell)
    rec = {"cell": cell, "seed": seed}

    svc, stream, gt, gt_s, traffic = setup(cfg, wl, seed, rec)
    rec["pool"] = stream.pool
    gc.collect()
    in_use = devs[0].memory_stats() or {}
    hbm = in_use.get("bytes_in_use")

    log_dir = None
    if trace:
        log_dir = os.path.join(HERE, "out", "trace", cell)
        shutil.rmtree(log_dir, ignore_errors=True)
        jax.profiler.start_trace(log_dir)

    def span(name):
        return (jax.profiler.TraceAnnotation(name) if trace
                else contextlib.nullcontext())

    setup_s = time.perf_counter() - t_start - gt_s
    n_compiles = compiles.n
    with span("bench.window"):
        out = traffic.run(svc, stream, float(seconds), wl["params"], rec,
                         span)
    window_compiles = compiles.n - n_compiles
    if trace:
        jax.profiler.stop_trace()
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
    arrays = index_arrays(svc.index.ivf)
    svc.shutdown()
    del svc
    gc.collect()

    ref = reference.ref_index(*arrays)
    checks = check(cfg, seed, ref, stream, rec["answers"],
                   rec.pop("build_rows"))
    if keep is not None:
        keep.update(rec=rec, stream=stream, ref=ref)
    checks["failed"] = {"value": out["failed"], "limit": 0, "rule": "<="}
    values = dict(out["metrics"])
    values["recall_at_10"] = recall(cfg, rec["answers"], gt)
    values["setup_s"] = setup_s
    if hbm is not None:
        values["hbm_bytes_per_vector"] = hbm / rec["index"]["n"]
    result = {"correct": all(holds(c) for c in checks.values()),
              "attempted": out["attempted"], "failed": out["failed"]}
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    notes = dict(out.get("notes", {}), window_compiles=window_compiles,
                 exact_topk_s=gt_s, build_s=rec["build_s"])
    if trace:
        import devtrace

        red = devtrace.reduce(devtrace.find_xplane(log_dir))
        rec["trace"] = red
        rec["peaks"] = peak_table
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
        result["metrics"] = {}
        for m in per_defs:
            v = load_module("metrics", m["name"]).read(rec)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        result["metrics"] = {}
        for m in e2e_defs:
            if m["name"] not in values and not require_tpu:
                continue             # the CPU reports no device memory
            v = values[m["name"]]
            result["metrics"][m["name"]] = {
                "value": v if math.isfinite(v) else 1e300, "unit": m["unit"]}
            if not math.isfinite(v):
                result["correct"] = False
    result["device"] = dev
    result["notes"] = notes
    result["checks"] = checks
    return result


def peaks(kind: str) -> dict:
    table = load_json(HERE, "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(CHECKOUT, "BENCHMARK.json")
    wl = load_json(HERE, "workloads", args.workload + ".json")
    cfg = load_json(HERE, "configs", wl["config"] + ".json")
    try:
        result = execute(args.workload, args.seed, args.seconds,
                         bool(args.trace), bench=bench, cfg=cfg, wl=wl,
                         t_start=T_PROCESS)
    except NoChip as err:
        print(f"benchmark: {err}", file=sys.stderr)
        return 2
    for name, value in result.pop("notes").items():
        print(f"note {name}: {value!r}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['rule']} "
              f"{c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
