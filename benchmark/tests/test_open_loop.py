"""The open-loop traffic times each request from when it was due, so an
engine that stalls raises ``p95_ms`` even for requests that arrive later
and are served fast."""

import contextlib
import queue
import threading
import time

import numpy as np

from run import Stream, load_module

open_loop = load_module("traffic", "open_loop")


class Future:
    def __init__(self):
        self.ev, self.cbs, self.ids = threading.Event(), [], None

    def add_done_callback(self, fn):
        self.cbs.append(fn)

    def result(self, timeout=None):
        if not self.ev.wait(timeout):
            raise TimeoutError
        return np.zeros(3), self.ids

    def timing(self):
        return {"queue_s": 0.0, "batch_s": 0.0, "engine_s": 0.001}


class OneServer:
    """A single server thread; serving takes ``service_s``, and the
    request numbered ``stall_at`` holds it for ``stall_s`` more."""

    def __init__(self, service_s, stall_at=-1, stall_s=0.0):
        self.q, self.n = queue.Queue(), 0
        self.service_s, self.stall_at, self.stall_s = (
            service_s, stall_at, stall_s)
        self.t = threading.Thread(target=self.loop, daemon=True)
        self.t.start()

    def submit_async(self, query):
        f = Future()
        self.q.put((self.n, f))
        self.n += 1
        return f

    def loop(self):
        while True:
            i, f = self.q.get()
            if f is None:
                return
            time.sleep(self.service_s + (self.stall_s if i == self.stall_at
                                         else 0.0))
            f.ids = np.arange(3)
            f.ev.set()
            for cb in f.cbs:
                cb(f)


def drive(svc, seconds=1.0, rate=40.0):
    pool = np.zeros((500, 4), np.float32)
    rec = {"seed": 1}
    out = open_loop.run(svc, Stream(pool, np.arange(500)), seconds,
                        {"rate_qps": rate, "drain_s": 5.0}, rec,
                        lambda name: contextlib.nullcontext())
    svc.q.put((0, None))
    return out, rec


def test_due_times_are_fixed_and_keep_the_rate():
    a = open_loop.due_times(10.0, {"rate_qps": 20.0})
    b = open_loop.due_times(10.0, {"rate_qps": 20.0})
    assert np.array_equal(a, b) and len(a) == 200
    gaps = np.diff(a)
    assert (gaps > 0).all() and a[-1] <= 10.0
    # exponential gaps in no sorted order
    assert 0.3 < np.mean(gaps > np.median(gaps)) < 0.7
    assert np.corrcoef(gaps[:-1], gaps[1:])[0, 1] < 0.3


def test_a_stalled_engine_raises_p95():
    fast, _ = drive(OneServer(0.002))
    slow, rec = drive(OneServer(0.002, stall_at=5, stall_s=0.6))
    assert fast["failed"] == slow["failed"] == 0
    assert fast["metrics"]["p95_ms"] < 100
    # requests due during the stall wait for it: over 5% of them
    assert slow["metrics"]["p95_ms"] > 300
    assert len(rec["answers"]) == slow["attempted"]


def test_an_unanswered_request_counts_as_infinite():
    class Never(OneServer):
        def submit_async(self, query):
            f = super().submit_async(query)
            if self.n % 10 == 0:
                return Future()             # never resolved
            return f
    out, _ = drive(Never(0.001), seconds=0.5)
    assert out["failed"] > 0
    assert out["metrics"]["p95_ms"] == float("inf")
