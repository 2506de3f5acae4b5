#!/usr/bin/env python3
"""Readings that set the benchmark's limits and rates, on the chip.

    python3 benchmark/calibrate.py limits --workload <cell> --seeds 1,2,3 --seconds 10
    python3 benchmark/calibrate.py build --workload <cell> --seeds 1,2,3 [--control 1]
    python3 benchmark/calibrate.py capacity --workload <cell> --seeds 1,2 --rates 6,8,10 --seconds 20

``limits`` makes whole runs of a cell, one seed after another in this one
process, and prints for each seed the numbers ``correct`` compares
(``adc_gap``, ``build_mismatch``) and ``recall_at_10`` beside the
control's reading of the same sample: the reference put in the program's
place one precision below the configuration's LUTs (``bfloat16`` for
``f32``, ``int4`` for ``uint8``).
The lower reading of a limit is the largest ``adc_gap`` of the sound
runs, the upper one the smallest ``control_gap``.

``build`` makes the cell's data and index only, one seed after another,
and prints the build's numbers: ``build_mismatch`` and the recall@10 of
the reference's own search over the built index.  With ``--control 1``
the build is the control: every distance it ranks (k-means, the coarse
assignment, PQ encoding) from one bfloat16 pass, as a TPU's default
matmul precision gives, in place of the program's ``HIGHEST``.  Run it in
a process of its own, since the program's compiled build is replaced.

``capacity`` sets the cell up once and offers its open-loop traffic at each
rate in turn, printing how much of it was served in time: the highest rate
the service sustains, from which an open-loop cell's rate is fixed.

None of them is part of a benchmark run.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import numpy as np

import run

CONTROL = {"f32": "bf16", "uint8": "int4"}


def limits(args, bench, cfg, wl, require_tpu: bool = True) -> None:
    import reference

    fields = cfg["service"]
    lut = fields["lut_dtype"]
    nprobe, k = int(fields["nprobe"]), int(fields["k"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        keep: dict = {}
        t0 = time.perf_counter()
        res = run.execute(args.workload, seed, args.seconds, False,
                          bench=bench, cfg=cfg, wl=wl, keep=keep,
                          require_tpu=require_tpu)
        answers, ref, pool = (keep["rec"]["answers"], keep["ref"],
                              keep["stream"].pool)
        t1 = time.perf_counter()
        pick = run.sample(seed, answers, cfg["correct"]["sample"])
        qs = pool[[answers[j][0] for j in pick]].astype(np.float64)
        ctrl = [reference.search(ref, q, nprobe, k, CONTROL[lut]) for q in qs]
        cgap = reference.max_gap(ref, qs, ctrl, nprobe, k, lut)
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "adc_gap": res["checks"]["adc_gap"]["value"],
            "build_mismatch": res["checks"]["build_mismatch"]["value"],
            "control": CONTROL[lut], "control_gap": cgap,
            "recall_at_10": res["metrics"]["recall_at_10"]["value"],
            "checked": len(pick), "metrics": {
                n: m["value"] for n, m in res["metrics"].items()},
            "run_s": t1 - t0, "control_s": time.perf_counter() - t1}),
            flush=True)


def plant_bf16_distances(setattr=setattr) -> None:
    """The build's control, planted in the program: squared distances from
    a matmul of bfloat16 inputs (f32 accumulation) in every module of the
    build.  ``setattr`` may be a test's ``monkeypatch.setattr``."""
    import importlib
    import os

    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(run.CHECKOUT, "src"))

    def l2_sq_bf16(x, y):
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
        xx = jnp.sum(x * x, axis=-1, keepdims=True)
        yy = jnp.sum(y * y, axis=-1, keepdims=True).T
        xy = jnp.matmul(x.astype(jnp.bfloat16), y.T.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        return jnp.maximum(xx + yy - 2.0 * xy, 0.0)

    for name in ("kmeans", "ivf", "pq"):
        setattr(importlib.import_module(f"repro.core.{name}"), "l2_sq",
                l2_sq_bf16)
    jax.clear_caches()


def build(args, bench, cfg, wl, require_tpu: bool = True) -> None:
    import exact
    import reference

    if require_tpu:
        run.check_device(1)
        run.compile_cache()
    if args.control:
        plant_bf16_distances()
    nprobe, k = int(cfg["service"]["nprobe"]), int(cfg["service"]["k"])
    lut = cfg["service"]["lut_dtype"]
    n_eval = int(cfg["data"]["eval_queries"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        rec: dict = {}
        t0 = time.perf_counter()
        _, handle, pool, gt, _ = run.build_index(cfg, seed, rec,
                                                 layout=False)
        ref = reference.ref_index(*run.index_arrays(handle.ivf))
        del handle
        t1 = time.perf_counter()
        rows, vectors = rec["build_rows"]
        mismatch = reference.build_mismatch(ref, vectors, rows)
        served = [reference.search(ref, q, nprobe, k, lut)
                  for q in pool[:n_eval].astype(np.float64)]
        sizes = np.diff(ref.offsets)
        print(json.dumps({
            "seed": seed, "control": bool(args.control),
            "build_mismatch": mismatch,
            "reference_recall_at_10": exact.recall_at_k(np.stack(served),
                                                        gt),
            "checked_rows": len(rows), "largest_cluster": int(sizes.max()),
            "build_s": rec["build_s"], "run_s": t1 - t0,
            "reference_s": time.perf_counter() - t1}), flush=True)


def served(out: dict, r: dict, seconds: float, part: float) -> dict:
    """How much of an open-loop window was served, and how fast."""
    t0, t1 = r["window"]
    lat = r["latency_s"]
    third = max(1, len(lat) // 3)
    res = {"sent": out["attempted"], "failed": out["failed"],
           "served_per_s": (out["attempted"] - out["failed"]) / (t1 - t0),
           "last_done_after_close_s": t1 - t0 - seconds,
           "p95_ms": out["metrics"]["p95_ms"], **out["notes"],
           "first_third_mean_ms": 1e3 * float(np.mean(lat[:third])),
           "last_third_mean_ms": 1e3 * float(np.mean(lat[-third:]))}
    if part:
        res[f"p95_ms_first_{part:g}s"] = 1e3 * float(
            np.percentile(lat[r["due_s"] < part], 95))
    return res


def capacity(args, bench, cfg, wl, require_tpu: bool = True) -> None:
    if require_tpu:
        run.check_device(1)
        run.compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    rec = {"cell": args.workload, "seed": seeds[0]}
    svc, stream, _, _, traffic = run.setup(cfg, wl, seeds[0], rec)
    try:
        for seed in seeds:
            for rate in [float(r) for r in args.rates.split(",")]:
                params = dict(wl["params"], rate_qps=rate, drain_s=30.0)
                r = {"seed": seed}
                out = traffic.run(svc, stream, args.seconds, params, r,
                                  lambda name: contextlib.nullcontext())
                print(json.dumps({"seed": seed, "rate_qps": rate,
                                  **served(out, r, args.seconds, args.part)}),
                      flush=True)
    finally:
        svc.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("limits", "build", "capacity"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--part", type=float, default=0.0,
                    help="capacity: also the p95 of the requests due in "
                    "the window's first PART seconds")
    args = ap.parse_args(argv)
    bench = run.load_json(run.CHECKOUT, "BENCHMARK.json")
    wl = run.load_json(run.HERE, "workloads", args.workload + ".json")
    cfg = run.load_json(run.HERE, "configs", wl["config"] + ".json")
    {"limits": limits, "build": build, "capacity": capacity}[args.what](
        args, bench, cfg, wl)
    return 0


if __name__ == "__main__":
    sys.exit(main())
