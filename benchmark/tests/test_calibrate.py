"""The controls, kept as tests: the reference put in the program's place
one precision below the configuration's LUTs reads above the limit of
``adc_gap``, and the build with its distances one precision lower reads
above the limit of ``build_mismatch``, where the program reads below
both.  Also drives the capacity sweep once."""

import json
import types

import pytest

import calibrate
from test_run import BENCH, large, small


@pytest.mark.parametrize("cell", ["bigann10m.bulk", "deep10m.bulk"])
def test_control_fails_the_limit(cell, capsys):
    cfg, wl = large(cell)
    args = types.SimpleNamespace(workload=cell, seeds="5,2147483653",
                                 seconds=0.0)       # one block a seed
    calibrate.limits(args, BENCH, cfg, wl, require_tpu=False)
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    limit = cfg["correct"]["adc_gap_max"]
    assert len(rows) == 2
    for r in rows:
        assert r["correct"] and r["adc_gap"] <= limit
        assert r["control_gap"] > limit


def test_capacity_sweep_runs(capsys):
    cfg, wl = small("bigann10m.online")
    args = types.SimpleNamespace(workload="bigann10m.online", seeds="3,4",
                                 rates="20,40", seconds=1.0, part=0.5)
    calibrate.capacity(args, BENCH, cfg, wl, require_tpu=False)
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [(r["seed"], r["rate_qps"]) for r in rows] == [
        (3, 20.0), (3, 40.0), (4, 20.0), (4, 40.0)]
    assert all(r["failed"] == 0 and r["p95_ms_first_0.5s"] > 0
               for r in rows)


@pytest.mark.parametrize("cell", ["bigann10m.bulk", "deep10m.bulk"])
@pytest.mark.parametrize("control", [0, 1])
def test_build_control_fails_the_limit(cell, control, capsys, monkeypatch):
    import jax

    cfg, wl = small(cell)
    plant = calibrate.plant_bf16_distances
    monkeypatch.setattr(calibrate, "plant_bf16_distances",
                        lambda: plant(monkeypatch.setattr))
    args = types.SimpleNamespace(workload=cell, seeds="6", control=control)
    jax.clear_caches()
    try:
        calibrate.build(args, BENCH, cfg, wl, require_tpu=False)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    (r,) = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    limit = cfg["correct"]["build_mismatch_max"]
    assert r["control"] == bool(control)
    if control:
        assert r["build_mismatch"] > limit
    else:
        assert r["build_mismatch"] <= limit
