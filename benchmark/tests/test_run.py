"""A whole run of each cell, on the CPU at a small size, with the chip
check skipped: ``correct`` holds for the program as it is, and comes out
false with the timed path broken underneath it.

The faults a served ANN cell can have: an answer altered where it is
produced, half of a batch left out (its rows given other rows' answers),
and the controls planted in the program: the LUTs one precision lower
than the configuration states (bfloat16 for f32, 4 bits for uint8), and
the build's distances from one bfloat16 pass.
There is no step state and no exchange between chips in these cells.
"""

import copy
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import run

BENCH = json.load(open(os.path.join(run.CHECKOUT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


def small(cell, rate=40.0):
    """The cell's configuration and workload at a size the CPU holds."""
    wl = run.load_json(run.HERE, "workloads", cell + ".json")
    cfg = copy.deepcopy(run.load_json(run.HERE, "configs",
                                      wl["config"] + ".json"))
    cfg["data"].update(n=40000, queries=1024, eval_queries=64)
    cfg["generator"]["chunk_rows"] = 20000
    cfg["index"].update(nlist=64, train_sample=None)
    cfg["correct"]["sample"] = 64
    wl = copy.deepcopy(wl)
    wl["params"] = dict(wl["params"], drain_s=30.0)
    if "block" in wl["params"]:
        wl["params"]["block"] = 64
    if "rate_qps" in wl["params"]:
        wl["params"]["rate_qps"] = rate
    return cfg, wl


def large(cell):
    """A size at which a query sees ~78,000 candidates, as at the cell's
    own (nprobe 32 of clusters of ~2,441 rows), and 256 answers are
    checked: the control's errors need near-ties at the k-th distance."""
    cfg, wl = small(cell, rate=300.0)
    cfg["data"].update(n=200000, queries=2048)
    cfg["index"].update(nlist=82)
    cfg["correct"]["sample"] = 256
    if "block" in wl["params"]:
        wl["params"]["block"] = 256
    return cfg, wl


def go(cell, seconds=1.0, seed=12345678901, rate=40.0, size=None):
    cfg, wl = size(cell) if size else small(cell, rate)
    return run.execute(cell, seed, seconds, False, bench=BENCH, cfg=cfg,
                       wl=wl, require_tpu=False)


@pytest.fixture
def engine(monkeypatch):
    from repro.runtime import serving
    jax.clear_caches()
    yield serving.LocalEngine, monkeypatch
    jax.clear_caches()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = go(cell)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    e2e, _ = run.cell_metrics(BENCH, cell)
    assert set(r["metrics"]) | {"hbm_bytes_per_vector"} == {
        m["name"] for m in e2e}


def _patch_answers(engine, alter):
    cls, mp = engine
    orig = cls.search_batch

    def broken(self, queries, *a, **kw):
        d, i = orig(self, queries, *a, **kw)
        return d, alter(np.array(i))
    mp.setattr(cls, "search_batch", broken)


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answer_is_caught(cell, engine):
    def alter(ids):
        ids[:, -1] = (ids[:, -1] * 7919 + 1) % 40000
        return ids
    _patch_answers(engine, alter)
    r = go(cell)
    assert not r["correct"]
    assert r["checks"]["adc_gap"]["value"] > r["checks"]["adc_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_half_batch_left_out_is_caught(cell, engine):
    def alter(ids):
        h = (len(ids) + 1) // 2
        ids[h:] = ids[:len(ids) - h]
        return ids
    _patch_answers(engine, alter)
    r = go(cell, rate=1000.0)               # so that batches form
    assert not r["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_luts_are_caught(cell, engine):
    """The control, planted in the program: LUTs one precision lower."""
    import jax.numpy as jnp
    from repro.core import adc, search

    _, mp = engine
    orig_build, orig_q = search.build_lut_batch, adc.quantize_lut

    def bf16_lut(codebook, residuals):
        return orig_build(codebook, residuals).astype(jnp.bfloat16).astype(
            jnp.float32)

    def int4_lut(lut):
        q = orig_q(lut)
        lo, hi = jnp.min(lut, -1), jnp.max(lut, -1)
        step = jnp.where(hi > lo, (hi - lo) / 15.0, 1.0)
        q4 = jnp.clip(jnp.round((lut - lo[..., None]) / step[..., None]),
                      0, 15)
        # back on the uint8 grid the scan expects: 17 u8 steps per level
        return q._replace(lut_q=(q4 * 17).astype(jnp.uint8),
                          scale=step / 17.0)
    mp.setattr(search, "build_lut_batch", bf16_lut)
    mp.setattr(search, "quantize_lut", int4_lut)
    r = go(cell, seconds=0.0, size=large)   # one block: the same answers
    assert not r["correct"]                 # on every run, however fast
    assert r["checks"]["adc_gap"]["value"] > r["checks"]["adc_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_build_is_caught(cell, engine):
    """The build's control, planted in the program: every distance the
    build ranks from one bfloat16 pass."""
    import calibrate

    _, mp = engine
    calibrate.plant_bf16_distances(mp.setattr)
    r = go(cell)
    assert not r["correct"]
    c = r["checks"]["build_mismatch"]
    assert c["value"] > c["limit"]


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(run.HERE, "run.py"),
                        "--workload", CELLS[0], "--seed", "1", "--seconds",
                        "1", "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=run.CHECKOUT, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr

