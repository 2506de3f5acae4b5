"""Search step, DC: the least time the code scan of the window's
``svc.search`` calls allows (``roofline.py``: the code bytes of the union
of clusters each call probes, at the index's own sizes, against
2 x M operations per probed row, with no tables) as a share of the
device time of the ``DC`` scope (``phases.py``), in %."""

import numpy as np

import phases
import roofline


def read(rec):
    red, calls, peak = phases.read(rec), rec.get("calls"), rec.get("peaks")
    if not red or not red["scoped"] or not calls or not peak:
        return None
    dc_s = red["phase_s"]["DC"]
    if dc_s <= 0:
        return None
    ix = rec["index"]
    sizes = np.asarray(ix["sizes"], np.int64)
    total = 0.0
    for c in calls:
        probed = roofline.probes(ix["centroids"], rec["pool"][c["idx"]],
                                 ix["nprobe"])
        total += roofline.least_seconds(
            roofline.call_bytes(sizes, probed, ix["m"], 0),
            2 * ix["m"] * int(sizes[probed].sum()), peak)
    return 100.0 * total / dc_s
