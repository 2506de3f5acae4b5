"""Device: the share of the traced window in which no operation ran on
the device, ``1 - busy / window`` (``devtrace.reduce``)."""


def read(rec):
    tr = rec.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 1.0 - tr["busy_s"] / tr["window_s"]
