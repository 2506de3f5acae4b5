"""Front door and micro-batching: mean wait of a request before the
engine starts its batch (``queue_s + batch_s`` of ``SearchFuture.timing()``),
in ms, over the window's answered requests."""


def read(rec):
    reqs = rec.get("requests")
    if not reqs:
        return None
    return 1e3 * sum(r["queue_s"] + r["batch_s"] for r in reqs) / len(reqs)
