"""Per-phase device time from a JAX profiler trace (``.xplane.pb``).

The program names its five search phases with ``jax.named_scope`` (``CL``,
``RC``, ``LC``, ``DC``, ``TS``), and XLA keeps the name in each HLO
instruction's ``op_name``.  The profiler writes that path as the ``tf_op``
stat of each device operation's *event metadata*, which
``jax.profiler.ProfileData`` does not expose; this module reads the
``XSpace`` protobuf with a small wire-format reader instead.

- An operation's phase is the outermost of the five names in its ``tf_op``
  path; an operation with none is unscoped.
- Only leaf operations count: a loop's operation encloses those of its
  body, so an operation that encloses another on its line is not a leaf.
- Per device and phase the seconds are the length of the union of that
  phase's leaf intervals inside the window (``bench.window``, as
  ``devtrace`` takes it); ``unscoped_s`` is the busy time (the union of
  every operation, as ``devtrace.reduce`` counts it) in no phase.  Each is
  the mean over the devices that ran anything.
- ``gaps`` lists the window's idle gaps on the first device, longest
  first, each with the program's host spans (``ann.*``, named up to their
  ``#`` arguments) open on each host thread at the gap's midpoint.

``read(rec)`` finds the traced run's file where ``run.py`` writes it
(``out/trace/<cell>``) and caches the reduction per path; it returns
``None`` for an untraced run, and ``scoped`` is False where no operation
names a phase (a program without the scopes).
"""

from __future__ import annotations

import os

import devtrace

PHASES = ("CL", "RC", "LC", "DC", "TS")
SPAN_PREFIX = "ann."
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                         "trace")

_CACHE: dict = {}


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: an int for varint and fixed
    fields, a ``memoryview`` for length-delimited ones."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            val, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _plane(buf):
    """(name, lines, event metadata {id: raw ``XEventMetadata``}, stat
    names {id: name}) of one ``XPlane``."""
    name, lines, meta, stat_names = "", [], {}, {}
    for num, v in fields(buf):
        if num == 2:
            name = _text(v)
        elif num == 3:
            lines.append(v)
        elif num in (4, 5):                 # map<int64, X...Metadata>
            entry = dict(fields(v))
            body = dict((n, x) for n, x in fields(entry.get(2, b""))
                        if n in (1, 2))
            if num == 5:
                stat_names[body.get(1, entry.get(1, 0))] = _text(
                    body.get(2, b""))
            else:
                meta[entry.get(1, 0)] = entry.get(2, b"")
    return name, lines, meta, stat_names


def _meta(meta_buf, stat_names) -> tuple:
    """(name, HLO ``op_name``) of one ``XEventMetadata``: the op_name is
    its ``tf_op`` stat (``<op_name>:<op type>``) less the type, or ``""``."""
    name, op = "", ""
    for num, v in fields(meta_buf):
        if num == 2:
            name = _text(v)
        elif num == 5:
            stat = dict(fields(v))
            if stat_names.get(stat.get(1)) != "tf_op":
                continue
            if 5 in stat:
                op = _text(stat[5])
            elif 7 in stat:
                op = stat_names.get(stat[7], "")
            op = op.rpartition(":")[0] if ":" in op else op
    return name, op


def op_names(path: str) -> dict:
    """{HLO instruction (``%fusion.4``): op_name} of the device
    operations' event metadata in one trace."""
    out = {}
    for num, pbuf in fields(_load(path)):
        if num != 1:
            continue
        name, _, meta, stat_names = _plane(pbuf)
        if name.startswith("/device:"):
            for buf in meta.values():
                hlo, op = _meta(buf, stat_names)
                out[hlo.split(" = ")[0]] = op
    return out


def _load(path: str) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def _line(buf):
    """(name, timestamp ns, events as (metadata id, offset ps, duration ps))
    of one ``XLine``; a host line is one thread, named ``<name>/<id>``."""
    name, line_id, ts, events = "", 0, 0, []
    for num, v in fields(buf):
        if num == 1:
            line_id = v
        elif num == 2:
            name = _text(v)
        elif num == 3:
            ts = v
        elif num == 4:
            ev = {1: 0, 2: 0, 3: 0}
            for n, x in fields(v):
                if n in ev:
                    ev[n] = x
            events.append((ev[1], ev[2], ev[3]))
    return name, line_id, ts, events


def events(path: str):
    """(device ops per device plane as (start ps, end ps, op_name), host
    spans as (thread, name, start ps, end ps)) of one trace.  Host spans
    are the window and the program's ``ann.*`` spans, named up to ``#``."""
    ops, spans = {}, []
    for num, pbuf in fields(_load(path)):
        if num != 1:
            continue
        name, lines, meta, stat_names = _plane(pbuf)
        if name.startswith("/device:"):
            paths = {mid: _meta(b, stat_names)[1] for mid, b in meta.items()}
            evs = []
            for lbuf in lines:
                lname, _, ts, line_evs = _line(lbuf)
                if lname == "XLA Ops":
                    evs.extend((ts * 1000 + off, ts * 1000 + off + dur,
                                paths.get(mid, ""))
                               for mid, off, dur in line_evs)
            if evs:
                ops[name] = evs
        elif name.startswith("/host:"):
            names = {mid: _meta(b, stat_names)[0].split("#")[0]
                     for mid, b in meta.items()}
            for lbuf in lines:
                lname, line_id, ts, line_evs = _line(lbuf)
                for mid, off, dur in line_evs:
                    n = names.get(mid, "")
                    if n.startswith(SPAN_PREFIX) or n == devtrace.WINDOW_SPAN:
                        s = ts * 1000 + off
                        spans.append((f"{lname}/{line_id}", n, s, s + dur))
    return ops, spans


def phase_of(op_name: str):
    """The outermost phase named in an ``op_name`` path, or None."""
    for part in op_name.split("/"):
        if part in PHASES:
            return part
    return None


def leaves(evs):
    """The operations that enclose no other operation of their line."""
    evs = sorted(evs, key=lambda e: (e[0], -e[1]))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[0] >= e[1]]


def _length(intervals) -> int:
    return sum(e - s for s, e in devtrace.union(intervals))


def reduce(path: str) -> dict:
    """``phase_s`` (seconds per phase), ``unscoped_s``, ``busy_s``,
    ``scoped`` and ``gaps`` of one trace (see the module text)."""
    ops, spans = events(path)
    if not ops:
        raise ValueError(f"{path}: no device operations in the trace")
    windows = [(s, e) for _, n, s, e in spans if n == devtrace.WINDOW_SPAN]
    if windows:
        lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    else:
        lo = min(s for evs in ops.values() for s, _, _ in evs)
        hi = max(e for evs in ops.values() for _, e, _ in evs)
    calls = sorted(((t, n, s, e) for t, n, s, e in spans
                    if n != devtrace.WINDOW_SPAN), key=lambda x: (x[2], -x[3]))
    phase_s = dict.fromkeys(PHASES, 0.0)
    unscoped = busy = 0.0
    scoped = False
    gaps = []
    for d, evs in enumerate(ops.values()):
        clipped = [(max(s, lo), min(e, hi), p) for s, e, p in evs
                   if e > lo and s < hi]
        merged = devtrace.union((s, e) for s, e, _ in clipped)
        all_busy = sum(e - s for s, e in merged)
        by_phase = {p: [] for p in PHASES}
        for s, e, p in leaves(clipped):
            ph = phase_of(p)
            if ph is not None:
                by_phase[ph].append((s, e))
        for ph, ivs in by_phase.items():
            n = _length(ivs)
            phase_s[ph] += n * 1e-12
            scoped = scoped or bool(ivs)
        busy += all_busy * 1e-12
        unscoped += (all_busy - _length(
            iv for ivs in by_phase.values() for iv in ivs)) * 1e-12
        if d == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps = [(e - s, s, e) for s, e in zip(edges[::2], edges[1::2])
                    if e > s]
    n_dev = len(ops)
    gaps.sort(reverse=True)
    return {
        "phase_s": {p: v / n_dev for p, v in phase_s.items()},
        "unscoped_s": unscoped / n_dev,
        "busy_s": busy / n_dev,
        "window_s": (hi - lo) * 1e-12,
        "scoped": scoped,
        "gaps": [{"s": g * 1e-12, "at_s": (s - lo) * 1e-12,
                  "spans": open_spans(calls, (s + e) / 2)}
                 for g, s, e in gaps],
    }


def open_spans(spans, t: float) -> dict:
    """{host thread: [span names, outermost first]} of the spans open at
    time ``t``; a thread in none is left out.  ``spans`` are sorted by
    start, the longer first where two start together."""
    out: dict = {}
    for thread, n, s, e in spans:
        if s > t:
            break
        if t <= e:
            out.setdefault(thread, []).append(n)
    return out


def read(rec: dict):
    """The reduction of the run's trace (cached per path), or None where
    the run was not traced."""
    if not rec.get("trace"):
        return None
    path = devtrace.find_xplane(os.path.join(TRACE_DIR, rec["cell"]))
    if path not in _CACHE:
        _CACHE[path] = reduce(path)
    return _CACHE[path]


def queries(rec: dict) -> int:
    """Queries the window completed (as ``device_ms_per_query.bulk``
    counts them)."""
    return sum(len(c["idx"]) for c in rec.get("calls") or [])


def ms_per_query(rec: dict, phase: str):
    """Device ms of one phase per query completed, or None where the run
    has no trace, no calls or no phase scopes."""
    red, n = read(rec), queries(rec)
    if not red or not red["scoped"] or not n:
        return None
    return 1e3 * red["phase_s"][phase] / n
