"""Search step and kernels: the least time the algorithm's work allows
for the window's ``svc.search`` calls (``roofline.py``: the code bytes of
the union of clusters each call probes, at the index's own sizes, and the
tables once, against the chip's peaks), as a share of the device's busy
time in the traced window, in %."""

import roofline


def read(rec):
    tr, calls, peak = rec.get("trace"), rec.get("calls"), rec.get("peaks")
    if not tr or not calls or not peak or tr["busy_s"] <= 0:
        return None
    ix = rec["index"]
    total = 0.0
    for c in calls:
        probed = roofline.probes(ix["centroids"], rec["pool"][c["idx"]],
                                 ix["nprobe"])
        total += roofline.least_seconds(
            roofline.call_bytes(ix["sizes"], probed, ix["m"],
                                ix["table_bytes"]),
            roofline.call_ops(ix["sizes"], probed, len(ix["sizes"]),
                              ix["centroids"].shape[1], ix["m"], ix["cb"]),
            peak)
    return 100.0 * total / tr["busy_s"]
