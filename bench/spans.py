"""Reduce a profiler trace to the program's own host spans, its named
device scopes, and the device's idle time put down to host spans.

    python3 -m bench.spans <file.xplane.pb>     # print the reduction

It reads what ``bench.trace`` leaves out, and leaves that module's
numbers as they are:

* ``spans``: for each host span of the program (names that start with
  ``engine.`` or ``router.``; ``repro.common.tracing.SPANS`` lists
  them): ``count``, ``durations`` (seconds, in trace order),
  ``total_s``, ``self_s`` (each span's duration less that of its
  direct child spans on the same host line, the benchmark's ``bench.*``
  spans counting as children), ``stats`` (the sum of each numeric stat)
  and ``parents`` (how often each span name directly encloses it).
* ``scopes``: for each named scope on an operation's scope path (the
  stat ``SCOPE_STAT`` of a device operation), the seconds of the union
  of those operations' intervals. A control-flow operation
  (``%while``, ``%conditional``) and the operations of its body overlap
  in the trace, so a sum would count them twice.
* ``idle_by_span``: each idle gap of the device put down to the
  innermost span of either family (``bench.*`` or the program's) that
  covers its midpoint, ``host:other`` where none does.
* ``idle_outside_program_s``: the device's idle time during which no
  program span was open on any host line.

Device numbers are averaged over the chips. The window is the
benchmark's ``bench.window`` span where the trace has one, else the
trace's first to last span or operation; spans are kept if they lie
inside it.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict

from bench.trace import (DEVICE_PREFIX, OPS_LINE, SPAN_PREFIX, WINDOW_SPAN,
                         _clip, _hosts_at, _merge)

PROGRAM_PREFIXES = ("engine.", "router.")
# the device operation's stat that holds its op_name metadata, the
# ``jax.named_scope`` path (jit(_srv_train)/router.rebuild/cond/...)
SCOPE_STAT = "tf_op"
_SCOPE = re.compile(r"\b(?:engine|router)\.[\w.]+")


def _is_program(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def _is_device(plane: str) -> bool:
    return plane.startswith(DEVICE_PREFIX) and \
        plane[len(DEVICE_PREFIX):].isdigit()


def read(path):
    """(host lines, each a list of (name, start, end, stats) spans of
    either family; {device plane: [(start, end, scope path)]}), times
    in ns, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                found = [(ev.name, ev.start_ns, ev.end_ns, ev.stats)
                         for ev in line.events
                         if ev.name.startswith(SPAN_PREFIX)
                         or _is_program(ev.name)]
                if found:
                    lines.append(found)
    with open(path, "rb") as f:
        return lines, device_ops(memoryview(f.read()))


# ``jax.profiler.ProfileData`` gives an event's own stats but not those
# of its metadata, where a device operation's op_name path is kept, so
# the device planes are read from the protobuf wire format (XSpace,
# tsl/profiler/protobuf/xplane.proto) directly.
def _varint(buf, pos):
    out = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, pos
        shift += 7


def _fields(buf, span):
    """(field number, value) of each field of the message in
    ``buf[span[0]:span[1]]``: an int, or a (start, end) span for a
    length-delimited field."""
    pos, end = span
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            val, pos = (pos, pos + n), pos + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            val, pos = int.from_bytes(buf[pos:pos + n], "little"), pos + n
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {pos}")
        yield key >> 3, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _scope_of(buf, stat_spans, stat_names) -> str:
    """The ``SCOPE_STAT`` string among XStat messages, if any: a string
    value, or a reference to a stat metadata entry holding it."""
    for span in stat_spans:
        sid, value = None, None
        for num, val in _fields(buf, span):
            if num == 1:
                sid = val
            elif num == 5:
                value = _text(buf, val)
            elif num == 7:
                value = stat_names.get(val)
        if stat_names.get(sid) == SCOPE_STAT and value is not None:
            return value
    return ""


def device_ops(buf) -> dict:
    """{device plane: [(start ns, end ns, scope path)]} of the
    operations on each ``/device:TPU:<n>`` plane's ``XLA Ops`` line."""
    out = {}
    for num, plane in _fields(buf, (0, len(buf))):
        if num != 1:
            continue
        name, lines, ev_meta, stat_names = "", [], {}, {}
        for num, val in _fields(buf, plane):
            if num == 2:
                name = _text(buf, val)
                if not _is_device(name):
                    break
            elif num == 3:
                lines.append(val)
            elif num in (4, 5):      # map<int64, XEventMetadata / XStat..>
                key, meta = None, None
                for n, v in _fields(buf, val):
                    key, meta = (v, meta) if n == 1 else (key, v)
                if meta is None:
                    continue
                got = dict(_fields(buf, meta))
                if num == 5:
                    stat_names[key] = _text(buf, got.get(2, (0, 0)))
                else:
                    ev_meta[key] = [v for n, v in _fields(buf, meta)
                                    if n == 5]
        if _is_device(name):
            scopes = {k: _scope_of(buf, v, stat_names)
                      for k, v in ev_meta.items()}
            out[name] = [op for line in lines
                         for op in _ops(buf, line, scopes, stat_names)]
    return out


def _ops(buf, line, scopes, stat_names):
    name, t0, events = "", 0, []
    for num, val in _fields(buf, line):
        if num == 2:
            name = _text(buf, val)
        elif num == 3:
            t0 = val
        elif num == 4:
            events.append(val)
    if name != OPS_LINE:
        return
    for ev in events:
        mid = offset = dur = 0
        stats = []
        for num, val in _fields(buf, ev):
            if num == 1:
                mid = val
            elif num == 2:
                offset = val
            elif num == 3:
                dur = val
            elif num == 4:
                stats.append(val)
        scope = (_scope_of(buf, stats, stat_names) if stats else "") or \
            scopes.get(mid, "")
        start = t0 + offset / 1000
        yield start, start + dur / 1000, scope


def _nest(line):
    """(direct parent index or None, ns of direct children) for
    each span of one host line, whose spans nest properly."""
    n = len(line)
    parent, child_ns = [None] * n, [0.0] * n
    stack = []
    for i in sorted(range(n), key=lambda i: (line[i][1], -line[i][2])):
        s, e = line[i][1], line[i][2]
        while stack and (line[stack[-1]][2] <= s or line[stack[-1]][2] < e):
            stack.pop()
        if stack:
            parent[i] = stack[-1]
            child_ns[stack[-1]] += e - s
        stack.append(i)
    return parent, child_ns


def _measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _intersect(a, b):
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _window(lines, devices):
    for line in lines:
        for name, s, e, _ in line:
            if name == WINDOW_SPAN:
                return s, e
    ends = [(s, e) for line in lines for _, s, e, _ in line]
    ends += [(s, e) for ops in devices.values() for s, e, _ in ops]
    if not ends:
        raise ValueError("the trace holds no span and no device operation")
    return min(s for s, _ in ends), max(e for _, e in ends)


def _spans(lines, lo, hi) -> dict:
    out = {}
    for line in lines:
        parent, child_ns = _nest(line)
        for i, (name, s, e, stats) in enumerate(line):
            if not _is_program(name) or s < lo or e > hi:
                continue
            rec = out.setdefault(name, {
                "count": 0, "durations": [], "total_s": 0.0, "self_s": 0.0,
                "stats": defaultdict(float), "parents": defaultdict(int)})
            rec["count"] += 1
            rec["durations"].append((e - s) * 1e-9)
            rec["total_s"] += (e - s) * 1e-9
            rec["self_s"] += (e - s - child_ns[i]) * 1e-9
            for k, v in stats:
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    rec["stats"][str(k)] += v
            up = parent[i]
            rec["parents"][line[up][0] if up is not None else ""] += 1
    for rec in out.values():
        rec["stats"] = dict(rec["stats"])
        rec["parents"] = dict(rec["parents"])
    return out


def reduce(path) -> dict:
    """The reduction described in the module docstring, times in
    seconds, with ``window_s`` and ``chips`` (0 for a trace with no
    device plane, whose device keys are then empty)."""
    lines, devices = read(path)
    lo, hi = _window(lines, devices)
    inner = [(n, s, e) for line in lines for n, s, e, _ in line
             if n != WINDOW_SPAN]
    program = _merge(list(_clip([(s, e) for n, s, e in inner
                                 if _is_program(n)], lo, hi)))
    scopes, idle, outside = defaultdict(float), defaultdict(float), 0.0
    for ops in devices.values():
        by_scope = defaultdict(list)
        for s, e, path_ in ops:
            for name in set(_SCOPE.findall(path_)):
                by_scope[name].append((s, e))
        for name, iv in by_scope.items():
            scopes[name] += _measure(_merge(list(_clip(iv, lo, hi))))
        busy = _merge(list(_clip([(s, e) for s, e, _ in ops], lo, hi)))
        gaps, prev = [], lo
        for s, e in busy + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        for name, (s, e) in zip(_hosts_at(inner, [(s + e) / 2
                                                  for s, e in gaps]), gaps):
            idle[name] += e - s
        outside += _measure(gaps) - _measure(_intersect(gaps, program))
    chips = len(devices)
    per_chip = 1e-9 / max(chips, 1)
    return {
        "window_s": (hi - lo) * 1e-9, "chips": chips,
        "spans": _spans(lines, lo, hi),
        "scopes": {k: v * per_chip for k, v in scopes.items()},
        "idle_by_span": {k: v * per_chip for k, v in idle.items()},
        "idle_outside_program_s": outside * per_chip if chips else None,
    }


def main(argv=None) -> int:
    import json

    import numpy as np
    s = reduce((argv or sys.argv[1:])[0])
    for rec in s["spans"].values():
        rec["p50_s"] = float(np.median(rec.pop("durations")))
    s["spans"] = dict(sorted(s["spans"].items(),
                             key=lambda kv: -kv[1]["self_s"]))
    s["idle_by_span"] = dict(sorted(s["idle_by_span"].items(),
                                    key=lambda kv: -kv[1]))
    print(json.dumps(s, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
