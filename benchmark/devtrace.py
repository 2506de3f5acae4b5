"""Reduction of a JAX profiler trace (``.xplane.pb``) to device busy time.

- Device operations are the events of the ``XLA Ops`` lines of the
  ``/device:`` planes, named by their HLO instruction (``%fusion.3``).  A
  loop's operation encloses those of its body, so a device's busy time is
  the length of the union of its operations' intervals inside the window;
  ``busy_s`` is its mean over the devices that ran anything.  The
  breakdown's per-operation seconds count an enclosing loop and its body
  each.
- The window is the benchmark's own host span ``bench.window`` (a
  ``jax.profiler.TraceAnnotation``), or the whole trace where it is absent.
- Idle gaps are the parts of the window in which no operation ran on the
  device; each is put down to the innermost benchmark span around its
  midpoint (``svc.search``, ``svc.submit_async``, ``future.result``), or to
  the window itself when the benchmark was in none of them.
"""

from __future__ import annotations

import collections
import glob
import os

WINDOW_SPAN = "bench.window"
CALL_SPANS = ("svc.search", "svc.submit_async", "future.result")
TOP = 10


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _events(path: str):
    """(device ops per device plane, host spans) in nanoseconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans = {}, []
    names = set(CALL_SPANS) | {WINDOW_SPAN}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = [(e.name.split(" = ")[0], e.start_ns,
                    e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            if evs:
                ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name in names)
    return ops, spans


def reduce(path: str) -> dict:
    """``busy_s``, ``window_s``, ``device_ops`` and ``idle_gaps`` (each a
    list of at most 10 ``[name, seconds]``) of one trace."""
    ops, spans = _events(path)
    if not ops:
        raise ValueError(f"{path}: no device operations in the trace")
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for evs in ops.values() for _, s, _ in evs)
        hi = max(e for evs in ops.values() for _, _, e in evs)
    calls = [(n, s, e) for n, s, e in spans if n in CALL_SPANS]

    busy, per_op = [], collections.Counter()
    gaps = collections.defaultdict(list)
    for evs in ops.values():
        clipped = [(max(s, lo), min(e, hi), n) for n, s, e in evs
                   if e > lo and s < hi]
        for s, e, n in clipped:
            per_op[n] += (e - s) * 1e-9
        merged = union((s, e) for s, e, _ in clipped)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps[_label(calls, (s + e) / 2)].append((e - s) * 1e-9)
    n_dev = len(ops)
    idle = sorted(((f"{name}: {len(g)} gaps, longest {max(g):.6f} s",
                    sum(g) / n_dev) for name, g in gaps.items()),
                  key=lambda x: -x[1])
    return {
        "busy_s": sum(busy) / n_dev,
        "window_s": (hi - lo) * 1e-9,
        "devices": n_dev,
        "device_ops": [[n, s / n_dev] for n, s in per_op.most_common(TOP)],
        "idle_gaps": [[n, s] for n, s in idle[:TOP]],
    }


def _label(calls, t: float) -> str:
    """The innermost benchmark call span around time ``t``."""
    inside = [(e - s, n) for n, s, e in calls if s <= t <= e]
    return min(inside)[1] if inside else WINDOW_SPAN
