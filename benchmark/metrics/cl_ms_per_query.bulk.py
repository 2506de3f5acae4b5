"""Search step, CL: device time of the leaf operations named by the
program's ``CL`` scope in the traced window (``phases.py``) per query
the window completed, in ms."""

import phases


def read(rec):
    return phases.ms_per_query(rec, "CL")
