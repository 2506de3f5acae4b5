"""The trace reduction, on small ``.xplane.pb`` fixtures.

``synthetic_tpu.xplane.pb`` is written with the XPlane schema in the
layout of a TPU trace (a ``/device:TPU:0`` plane whose ``XLA Ops`` line
holds the operations, a ``/host:CPU`` plane with the benchmark's spans),
with intervals chosen so that every number is worked out by hand:

    host    bench.window [0, 100] ms; svc.search [0, 25], future.result
            [26, 62], svc.search [63, 98]; an unrelated span [1, 3]
    device  XLA Ops fusion.1 [0, 10], fusion.2 [5, 12], fusion.1 [30, 40],
            copy.3 [60, 61], fusion.2 [99, 104] (past the window's end);
            XLA Modules jit_search [0, 12] (not an operation)

Busy is [0, 12] + [30, 40] + [60, 61] + [99, 100] = 24 ms of 100.  The
idle gaps [12, 30] and [61, 99] have their midpoints in svc.search
(18 + 38 ms), and [40, 60] in future.result (20 ms).
"""

import os

import pytest

import devtrace

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


@pytest.fixture(scope="module")
def red():
    return devtrace.reduce(os.path.join(FIXTURES, "synthetic_tpu.xplane.pb"))


def test_union_merges_overlaps():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                                 (5, 8)]


def test_busy_is_the_union_of_ops_inside_the_window(red):
    assert red["busy_s"] == pytest.approx(0.024)
    assert red["window_s"] == pytest.approx(0.100)
    assert red["devices"] == 1


def test_ops_are_summed_by_name_inside_the_window(red):
    assert red["device_ops"] == [["fusion.1", pytest.approx(0.020)],
                                 ["fusion.2", pytest.approx(0.008)],
                                 ["copy.3", pytest.approx(0.001)]]


def test_gaps_are_put_down_to_the_innermost_call(red):
    assert red["idle_gaps"] == [
        ["svc.search: 2 gaps, longest 0.038000 s", pytest.approx(0.056)],
        ["future.result: 1 gaps, longest 0.020000 s", pytest.approx(0.020)]]


def test_a_trace_without_device_ops_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(3).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no device operations"):
        devtrace.reduce(devtrace.find_xplane(str(tmp_path)))


def test_a_recorded_tpu_trace():
    """``tpu_v5e_probe.xplane.pb`` was recorded on a TPU v5 lite: inside
    ``bench.window``, three ``svc.search`` spans around a (1024, 1024)
    matmul, each followed by a 20 ms host sleep and a ``future.result``
    span around a 1,024-step ``lax.map``.  The reduction must agree with
    an independent count of busy microseconds."""
    import numpy as np

    path = os.path.join(FIXTURES, "tpu_v5e_probe.xplane.pb")
    red = devtrace.reduce(path)
    ops, spans = devtrace._events(path)
    (lo, hi), = [(s, e) for n, s, e in spans if n == devtrace.WINDOW_SPAN]
    (evs,) = ops.values()
    t = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, e in evs:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            t[int((a - lo) // 1000):int(-(-(b - lo) // 1000))] = True
    assert red["busy_s"] == pytest.approx(t.sum() * 1e-6, abs=1e-4)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert 0 < red["busy_s"] < red["window_s"]
    labels = {n.split(":")[0] for n, _ in red["idle_gaps"]}
    assert labels <= {"svc.search", "future.result", devtrace.WINDOW_SPAN}
    assert devtrace.WINDOW_SPAN in labels      # the sleeps
    assert sum(s for _, s in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    assert all(n.startswith("%") and " " not in n
               for n, _ in red["device_ops"])
