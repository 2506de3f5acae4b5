"""Where the work happens, on the profiler's clock: the reader of per-phase
device time (``benchmark/phases.py``) on a recorded TPU trace and on a
synthetic one, and the service's ``ann.*`` host spans in a CPU trace.

The synthetic trace is written here with a protobuf wire-format writer in
the XPlane schema, with intervals chosen so that every number is worked
out by hand (ms):

    host    python/1: bench.window [0, 100], ann.result [51, 95]
            python/2: ann.batch#batch_id=1# [52, 90]
    device  %while [0, 50] (the loop: no phase), enclosing
                %fusion.1 [0, 10] CL, %fusion.2 [12, 40] DC,
                %fusion.3 [40, 45] DC with TS nested inside DC;
            %copy.4 [50, 55] (no phase), %fusion.5 [80, 90] TS,
            %fusion.6 [95, 105] CL (past the window's end)

Busy is [0, 55] + [80, 90] + [95, 100] = 70 ms: CL 10 + 5, DC 28 + 5,
TS 10, and 12 in no phase (the loop's own 2 + 5 between its body's
operations, and the copy's 5).  The idle gaps are [55, 80] (midpoint
under ann.result and ann.batch) and [90, 95] (under ann.result alone).
"""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import devtrace  # noqa: E402
import phases  # noqa: E402

RECORDED = os.path.join(ROOT, "benchmark", "tests", "fixtures",
                        "tpu_v5e_probe.xplane.pb")
MS = 10 ** 9                                   # picoseconds per ms


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, lines, metadata, stat_names=()):
    """An XPlane: ``metadata`` maps id -> (name, tf_op stat or None);
    a tf_op given as an int refers to a stat metadata entry's name."""
    stats = [(1, "tf_op")] + list(stat_names)
    fields = [(2, name)] + [(3, ln) for ln in lines]
    for mid, (ename, tf_op) in metadata.items():
        body = [(1, mid), (2, ename)]
        if tf_op is not None:
            val = (7, tf_op) if isinstance(tf_op, int) else (5, tf_op)
            body.append((5, _msg((1, 1), val)))
        fields.append((4, _msg((1, mid), (2, _msg(*body)))))
    for sid, sname in stats:
        fields.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    return _msg(*fields)


def _line(line_id, name, events):
    """An XLine at timestamp 0 of (metadata id, start ms, end ms)."""
    return _msg((1, line_id), (2, name), (3, 0), *[
        (4, _msg((1, mid), (2, s * MS), (3, (e - s) * MS)))
        for mid, s, e in events])


def synthetic_xspace() -> bytes:
    """The synthetic trace of the module text, as ``XSpace`` bytes."""
    body = "jit(search)/while/body/closed_call"
    device = _plane(
        "/device:TPU:0",
        [_line(1, "XLA Modules", [(9, 0, 90)]),
         _line(2, "XLA Ops", [(1, 0, 50), (2, 0, 10), (3, 12, 40),
                              (4, 40, 45), (5, 50, 55), (6, 80, 90),
                              (7, 95, 105)])],
        {1: ("%while", "jit(search)/while:"),
         2: ("%fusion.1 = f32[8] fusion()", f"{body}/CL/dot_general:"),
         3: ("%fusion.2", 30),
         4: ("%fusion.3", f"{body}/DC/jit(merge)/TS/top_k:"),
         5: ("%copy.4", "jit(search)/copy:"),
         6: ("%fusion.5", "jit(merge)/TS/top_k:"),
         7: ("%fusion.6", "jit(search)/CL/dot_general:"),
         9: ("jit_search", None)},
        stat_names=[(30, f"{body}/DC/gather:")])
    host = _plane(
        "/host:CPU",
        [_line(1, "python", [(1, 0, 100), (2, 51, 95), (4, 20, 30)]),
         _line(2, "python", [(3, 52, 90)])],
        {1: ("bench.window", None), 2: ("ann.result", None),
         3: ("ann.batch#batch_id=1#", None), 4: ("svc.search", None)})
    return _msg((1, device), (1, host))


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "synthetic.xplane.pb"
    path.write_bytes(synthetic_xspace())
    return phases.reduce(str(path))


def test_decoder_reads_op_names_of_a_recorded_tpu_trace():
    names = phases.op_names(RECORDED)
    assert names["%fusion"] == "jit(<lambda>)/dot_general"
    assert names["%dynamic-slice_multiply_fusion.2"] == (
        "jit(<lambda>)/while/body/closed_call/mul")


def test_busy_time_agrees_with_devtrace_on_a_recorded_tpu_trace():
    red = phases.reduce(RECORDED)
    assert red["busy_s"] == pytest.approx(
        devtrace.reduce(RECORDED)["busy_s"], rel=1e-6)
    assert not red["scoped"]                 # the probe names no phase
    assert red["unscoped_s"] == pytest.approx(red["busy_s"])


def test_phase_is_the_outermost_scope():
    assert phases.phase_of("jit(f)/while/body/DC/jit(g)/TS/top_k") == "DC"
    assert phases.phase_of("jit(f)/while/body/closed_call/mul") is None
    assert phases.phase_of("jit(CL)/dot") is None


def test_only_leaf_operations_count():
    evs = [(0, 50, "loop"), (0, 10, "a"), (12, 40, "b"), (50, 55, "c")]
    assert phases.leaves(evs) == [(0, 10, "a"), (12, 40, "b"),
                                  (50, 55, "c")]


def test_phase_seconds_of_a_synthetic_trace(synthetic):
    assert synthetic["scoped"]
    assert synthetic["window_s"] == pytest.approx(0.100)
    assert synthetic["busy_s"] == pytest.approx(0.070)
    assert synthetic["phase_s"] == {
        "CL": pytest.approx(0.015), "RC": 0.0, "LC": 0.0,
        "DC": pytest.approx(0.033), "TS": pytest.approx(0.010)}
    assert synthetic["unscoped_s"] == pytest.approx(0.012)
    assert sum(synthetic["phase_s"].values()) + synthetic[
        "unscoped_s"] == pytest.approx(synthetic["busy_s"])


def test_idle_gaps_carry_the_open_program_spans(synthetic):
    gaps = synthetic["gaps"]
    assert [g["s"] for g in gaps] == [pytest.approx(0.025),
                                      pytest.approx(0.005)]
    assert gaps[0]["at_s"] == pytest.approx(0.055)
    assert gaps[0]["spans"] == {"python/1": ["ann.result"],
                                "python/2": ["ann.batch"]}
    assert gaps[1]["spans"] == {"python/1": ["ann.result"]}


def test_a_run_without_a_trace_reads_nothing():
    assert phases.read({"cell": "x"}) is None
    assert phases.ms_per_query({"cell": "x", "calls": []}, "DC") is None


# ---------------------------------------------------------------------------
# The service's host spans, in a CPU profiler trace
# ---------------------------------------------------------------------------

def _inside(inner, outer) -> bool:
    return outer[2] <= inner[2] and inner[3] <= outer[3]


def test_service_spans_nest_inside_the_caller(small_index, small_corpus,
                                              tmp_path):
    from repro.service import AnnService, ServiceSpec

    svc = AnnService.build(ServiceSpec(
        engine="local", nprobe=8, k=10, buckets=(1, 2, 4), max_wait_s=1e-3,
        replicas=2, router="cache_aware"), index=small_index)
    svc.warmup()
    queries = np.asarray(small_corpus.queries[:4], np.float32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            svc.search(queries)
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            svc.submit_async(queries[0]).result(timeout=60.0)
    finally:
        jax.profiler.stop_trace()
        svc.shutdown()
    _, spans = phases.events(devtrace.find_xplane(str(tmp_path)))

    def named(n):
        return sorted((s for s in spans if s[1] == n), key=lambda s: s[2])
    bulk, online = named(devtrace.WINDOW_SPAN)
    caller = bulk[0]
    assert online[0] == caller               # one thread calls both

    (search,) = named("ann.search")
    assert search[0] == caller and _inside(search, bulk)
    engines = named("ann.engine")
    assert len(engines) == 2                 # the bulk call and the batch
    assert _inside(engines[0], search)
    fetches = named("ann.fetch")
    for eng in engines:                      # fused path: ids and dists
        assert sum(_inside(f, eng) and f[0] == eng[0]
                   for f in fetches) == 2
    assert len(fetches) == 4

    (submit,) = named("ann.submit")
    (route,) = named("ann.route")
    (result,) = named("ann.result")
    assert submit[0] == result[0] == caller
    assert _inside(submit, online) and _inside(route, submit)
    assert _inside(result, online)
    (batch,) = named("ann.batch")
    assert batch[0] != caller                # the replica's worker
    assert _inside(batch, online) and _inside(engines[1], batch)
    assert any(w[0] == batch[0] and w[3] <= batch[2]
               for w in named("ann.wait"))
