"""Open loop of independent users: single queries through
``AnnService.submit_async`` at due times fixed before the window opens,
whether or not earlier ones have finished.

Parameters:

- ``rate_qps``: the mean offered rate;
- ``drain_s``: how long past the window's close open requests are waited
  for (60 by default).

The gaps are the quantiles of the exponential distribution at the mean
rate, ``n = rate * seconds`` of them, in one fixed order: every run offers
the same arrivals, and the seed decides only which query each carries (the
stream's order).  The order of the gaps is not the seed's because it sets
where arrivals bunch up, which moved ``p95_ms`` between seeds more than
between two runs of one seed.  A request is timed
from when it was due to when its future resolved, so a stalled generator
or a growing queue shows; a failed request, or one still open at the end
of the drain, counts as +inf.  End to end this gives ``p95_ms``.
"""

from __future__ import annotations

import math
import time

import numpy as np


#: fixes the order of the gaps, the same in every run
ARRIVALS_SEED = 0xA7


def due_times(seconds: float, params: dict) -> np.ndarray:
    """Offsets (s) from the window's start at which requests are due."""
    n = max(1, int(round(float(params["rate_qps"]) * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    gaps = np.random.default_rng(ARRIVALS_SEED).permutation(gaps)
    t = np.cumsum(gaps) * (seconds / gaps.sum())    # mean gap 1 / rate
    return t - t[0]


def percentile(x: np.ndarray, q: float) -> float:
    """numpy's linear percentile, +inf where it would interpolate with an
    infinite value (numpy gives nan there)."""
    s = np.sort(np.asarray(x, np.float64))
    pos = q / 100.0 * (len(s) - 1)
    if not np.isfinite(s[int(math.ceil(pos))]):
        return math.inf
    return float(np.percentile(s, q))


def warmup(svc, stream, params: dict) -> None:
    """Compile every bucket, start the executors, serve one request."""
    svc.warmup()
    svc.submit_async(np.zeros(stream.dim, np.float32)).result(timeout=600)


def run(svc, stream, seconds: float, params: dict, rec: dict, span) -> dict:
    due = due_times(seconds, params)
    n = len(due)
    idx, qs = stream.take(n)
    submit_t = np.zeros(n)
    done_t = np.full(n, math.nan)
    futs = []

    def stamp(i):
        def cb(_fut):
            done_t[i] = time.perf_counter()
        return cb

    t0 = time.perf_counter()
    for i in range(n):
        delay = t0 + due[i] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        submit_t[i] = time.perf_counter()
        with span("svc.submit_async"):
            fut = svc.submit_async(qs[i])
        fut.add_done_callback(stamp(i))
        futs.append(fut)
    horizon = max(t0 + seconds, time.perf_counter()) + float(
        params.get("drain_s", 60.0))
    ok = np.zeros(n, bool)
    answers, timings = [], []
    for i, fut in enumerate(futs):
        try:
            with span("future.result"):
                _, ids = fut.result(
                    timeout=max(0.0, horizon - time.perf_counter()))
        except Exception as err:            # noqa: BLE001 — counted
            rec.setdefault("errors", []).append(repr(err))
            continue
        ok[i] = True
        answers.append((int(idx[i]), np.asarray(ids)))
        timings.append(fut.timing())
    lat = np.where(ok, done_t - (t0 + due), np.inf)
    finished = done_t[ok]
    rec["window"] = (t0, float(finished.max()) if ok.any() else horizon)
    rec["answers"] = answers
    rec["requests"] = timings
    rec["due_s"], rec["latency_s"] = due, lat
    lag = submit_t - (t0 + due)
    return {"attempted": n, "failed": int(n - ok.sum()),
            "metrics": {"p95_ms": percentile(lat, 95) * 1e3},
            "notes": {"requests": n,
                      "p50_ms": percentile(lat, 50) * 1e3,
                      "generator_lag_p95_ms": float(np.percentile(lag, 95))
                      * 1e3,
                      "generator_lag_max_ms": float(lag.max()) * 1e3}}
