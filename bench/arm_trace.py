"""Reduce a profiler trace to what the real arms did in it, per arm.

``bench.spans`` keeps the program's ``engine.*`` and ``router.*`` names
only; this reads the arms' own: the host spans ``arm.generate`` (stats
``arm``, the arm's pool index; ``rows``; ``prompt_max``), with
``arm.prefill`` and ``arm.decode`` inside them (stat ``steps``), and the
device scopes ``arm.*`` (``repro.common.tracing``) of the decode
program's operations. For each arm, over its ``arm.generate`` spans that
lie inside the window (the benchmark's ``bench.window`` span):

* ``calls``: (rows, prompt_max) of each span;
* ``steps``: the decode steps dispatched (the summed ``steps`` stats);
* ``generate_s``: the spans' summed duration;
* ``device_s``: the union of the device operations inside the spans,
  less those of the router's programs (scope ``router.*``), which may
  still run when a span opens;
* ``scopes``: for each ``arm.*`` scope, the union of its operations
  inside the spans.

Device seconds are averaged over the chips.
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench.spans import device_ops
from bench.trace import WINDOW_SPAN, _merge

GENERATE = "arm.generate"
STEPS = ("arm.prefill", "arm.decode")
_ARM_SCOPE = re.compile(r"\barm\.[a-z_]+")


def _measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _within(intervals, spans):
    """The parts of ``intervals`` inside any of the disjoint, sorted
    ``spans``."""
    for s, e in intervals:
        for lo, hi in spans:
            if hi <= s:
                continue
            if lo >= e:
                break
            yield max(s, lo), min(e, hi)


def host_spans(path):
    """[(name, start ns, end ns, {stat: value})] of the window span and
    the arms' spans, from every host line."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN or ev.name.startswith("arm."):
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                {str(k): v for k, v in ev.stats}))
    return out


def reduce(path) -> dict:
    spans = host_spans(path)
    with open(path, "rb") as f:
        devices = device_ops(memoryview(f.read()))
    win = [(s, e) for n, s, e, _ in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    lo, hi = win[0]
    gens = sorted((s, e, st) for n, s, e, st in spans
                  if n == GENERATE and lo <= s and e <= hi)
    steps = [(s, e, st) for n, s, e, st in spans if n in STEPS]
    arms = {}
    for s, e, st in gens:
        a = arms.setdefault(int(st.get("arm", -1)), {
            "calls": [], "steps": 0, "generate_s": 0.0, "spans": []})
        a["calls"].append((int(st.get("rows", 0)),
                           int(st.get("prompt_max", 0))))
        a["steps"] += sum(int(t.get("steps", 0)) for ss, se, t in steps
                          if s <= ss and se <= e)
        a["generate_s"] += (e - s) * 1e-9
        a["spans"].append((s, e))
    chips = max(len(devices), 1)
    for a in arms.values():
        iv = a.pop("spans")
        busy, scopes = 0.0, defaultdict(float)
        for ops in devices.values():
            mine = [(s, e, p) for s, e, p in ops if "router." not in p]
            busy += _measure(_merge(list(_within(
                sorted((s, e) for s, e, _ in mine), iv))))
            by_scope = defaultdict(list)
            for s, e, p in mine:
                for name in set(_ARM_SCOPE.findall(p)):
                    by_scope[name].append((s, e))
            for name, got in by_scope.items():
                scopes[name] += _measure(_merge(list(_within(
                    sorted(got), iv))))
        a["device_s"] = busy * 1e-9 / chips
        a["scopes"] = {k: v * 1e-9 / chips for k, v in scopes.items()}
    return {"window_s": (hi - lo) * 1e-9, "chips": len(devices),
            "arms": arms}


def traced_arm(run: dict, key: str):
    """For a device metric's reader: (the real arm ``key``'s entry in
    the driver's layer data, its reduction above), or None where the
    trace has no device plane or saw no step of the arm."""
    lay = run["layer"]
    tr = lay.get("arm_trace")
    arm = next((a for a in lay.get("real_arms", {}).values()
                if a["key"] == key), None)
    if not tr or not tr["chips"] or arm is None:
        return None
    got = tr["arms"].get(arm["index"])
    if not got or not got["steps"]:
        return None
    return arm, got
