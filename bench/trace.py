"""Reduce a profiler trace (``.xplane.pb``) to busy time, idle gaps and
time per device operation.

    python3 -m bench.trace <file.xplane.pb>     # print the reduction

* Device planes are those named ``/device:TPU:<n>``; on each, the line
  ``XLA Ops`` holds one event per operation that ran (``XLA Modules``
  one per program). Busy time is the union of the operation intervals
  inside the window, averaged over the chips.
* The window is the host span ``bench.window`` that the harness opens
  around the measured loop.
* An operation's event name is its HLO text; it is kept by the name
  before `` = `` (``%nucb_decide_padded.1``), with the text itself as
  the stat ``hlo``.
* Each idle gap inside the window is put down to the innermost host span
  named ``bench.*`` that covers its midpoint (``host:other`` where none
  does).
"""
from __future__ import annotations

import sys
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        if isinstance(v, (str, int, float)):
            out[str(k)] = v
    return out


def read_planes(path):
    """(host spans [(name, start, end)], {device: {"ops": [...],
    "modules": [...]}}) with times in ns, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in pd.planes:
        plane_name = plane.name
        if plane_name.startswith(DEVICE_PREFIX) and \
                plane_name[len(DEVICE_PREFIX):].isdigit():
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    name, st = ev.name, None
                    if key == "ops":
                        st = _stats(ev)
                        head, sep, _ = name.partition(" = ")
                        if sep:
                            st["hlo"] = name[:300]
                            name = head
                    dev[key].append((name, ev.start_ns, ev.end_ns, st))
            devices[plane_name] = dev
        elif plane_name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    return spans, devices


def reduce(path, top: int = 10) -> dict:
    """The trace's numbers, in seconds: ``window_s``, ``busy_s`` (mean
    over chips), ``ops`` {operation name: seconds, summed over chips},
    ``op_stats`` {operation name: its stats}, ``modules`` {program name:
    seconds}, ``idle_by_host`` {host span: idle seconds}, ``chips``, and
    the breakdown lists ``device_ops`` and ``idle_gaps`` (top ``top``)."""
    spans, devices = read_planes(path)
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not devices:
        raise ValueError(f"{path}: no {DEVICE_PREFIX}<n> plane")
    if win:
        lo, hi = win[0]
    else:   # no harness span: the device's own first to last operation
        evs = [(s, e) for d in devices.values() for _, s, e, _ in d["ops"]]
        lo, hi = min(s for s, _ in evs), max(e for _, e in evs)
    inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
    ops, op_stats, modules = defaultdict(float), {}, defaultdict(float)
    idle = defaultdict(float)
    busy_total = 0.0
    for dev in devices.values():
        for name, s, e, st in dev["ops"]:
            for cs, ce in _clip([(s, e)], lo, hi):
                ops[name] += (ce - cs) * 1e-9
                op_stats.setdefault(name, st)
        for name, s, e, _ in dev["modules"]:
            for cs, ce in _clip([(s, e)], lo, hi):
                modules[name] += (ce - cs) * 1e-9
        busy = _merge(list(_clip([(s, e) for _, s, e, _ in dev["ops"]],
                                 lo, hi)))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        prev, gaps = lo, []
        for s, e in busy + [[hi, hi]]:
            if s > prev:
                gaps.append(((prev + s) / 2, (s - prev) * 1e-9))
            prev = max(prev, e)
        for name, secs in zip(_hosts_at(inner, [m for m, _ in gaps]),
                              (d for _, d in gaps)):
            idle[name] += secs
    chips = len(devices)
    return {
        "window_s": (hi - lo) * 1e-9, "busy_s": busy_total / chips,
        "chips": chips, "ops": dict(ops), "op_stats": op_stats,
        "modules": dict(modules),
        "idle_by_host": {k: v / chips for k, v in idle.items()},
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / chips] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def _hosts_at(spans, times):
    """The innermost span covering each of the (ascending) ``times``."""
    spans = sorted(spans, key=lambda sp: sp[1])
    out, active, j = [], [], 0
    for t in times:
        while j < len(spans) and spans[j][1] <= t:
            active.append(spans[j])
            j += 1
        active = [sp for sp in active if sp[2] >= t]
        out.append(min(active, key=lambda sp: sp[2] - sp[1])[0]
                   if active else "host:other")
    return out


def seconds_matching(summary: dict, patterns, key: str = "ops") -> float:
    """Seconds of the operations (or programs) whose own name contains
    one of ``patterns``. An operation's name is the head of its HLO text
    (``%nucb_decide_padded.1``), so an operation that only consumes the
    kernel's output, and names it among its operands, does not count."""
    return sum(secs for name, secs in summary[key].items()
               if any(pat in name for pat in patterns))


def main(argv=None) -> int:
    import json
    path = (argv or sys.argv[1:])[0]
    s = reduce(path)
    s["ops"] = dict(sorted(s["ops"].items(), key=lambda kv: -kv[1])[:40])
    s["op_stats"] = {k: s["op_stats"].get(k) for k in s["ops"]}
    print(json.dumps(s, indent=1, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
