"""Engine: mean time from the engine's start on a request's batch to its
completion (``engine_s`` of ``SearchFuture.timing()``), in ms, over the
window's answered requests."""


def read(rec):
    reqs = rec.get("requests")
    if not reqs:
        return None
    return 1e3 * sum(r["engine_s"] for r in reqs) / len(reqs)
