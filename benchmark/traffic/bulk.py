"""Closed loop of one caller: blocks of fresh queries through
``AnnService.search``, the next block sent when the last one returned.

Parameters: ``block`` (queries per call).

End to end it gives ``qps``: every query completed in the window over the
time from the window's start to the last completion, the block still
running when ``--seconds`` ran out included.
"""

from __future__ import annotations

import time

import numpy as np


def warmup(svc, stream, params: dict) -> None:
    """Compile and run the one shape the window sends."""
    svc.search(np.zeros((int(params["block"]), stream.dim), np.float32))


def run(svc, stream, seconds: float, params: dict, rec: dict, span) -> dict:
    block = int(params["block"])
    calls, failed = [], 0
    t0 = time.perf_counter()
    while not calls or time.perf_counter() - t0 < seconds:
        idx, q = stream.take(block)
        ts = time.perf_counter()
        try:
            with span("svc.search"):
                _, ids = svc.search(q)
        except Exception as err:            # noqa: BLE001 — counted
            rec.setdefault("errors", []).append(repr(err))
            failed += block
            break
        calls.append({"t0": ts, "t1": time.perf_counter(), "idx": idx,
                      "ids": np.asarray(ids)})
    t_end = calls[-1]["t1"] if calls else time.perf_counter()
    rec["calls"] = calls
    rec["window"] = (t0, t_end)
    rec["answers"] = [(int(i), row) for c in calls
                      for i, row in zip(c["idx"], c["ids"])]
    done = sum(len(c["idx"]) for c in calls)
    return {"attempted": done + failed, "failed": failed,
            "metrics": {"qps": done / (t_end - t0)},
            "notes": {"calls": len(calls), "queries": done}}
