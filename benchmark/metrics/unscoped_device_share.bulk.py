"""Search step: the share of the device's busy time in the traced window
spent in operations that name none of the five phases (``phases.py``),
a fraction: how much of the work the phase scopes leave uncovered."""

import phases


def read(rec):
    red = phases.read(rec)
    if not red or not red["scoped"] or red["busy_s"] <= 0:
        return None
    return red["unscoped_s"] / red["busy_s"]
