"""Search step: device busy time in the traced window per query the
window completed, in ms."""


def read(rec):
    tr, calls = rec.get("trace"), rec.get("calls")
    if not tr or not calls:
        return None
    return 1e3 * tr["busy_s"] / sum(len(c["idx"]) for c in calls)
