"""Writes ``spans.xplane.pb``, the hand-made profiler trace that
``test_bench_spans.py`` reduces (needs TensorFlow's xplane proto; run it
only to change the trace):

    python3 tests/bench/data/make_span_trace.py

Times in ns. Host line "python" (spans nest as one thread's do):

    engine.submit [100, 500] rows 3             (before the window)
    bench.window [1000, 21000]
      bench.frontend.pump [2000, 12000]
        engine.pump [2100, 11900]
          engine.decide [2200, 5200] gid 0, rows 4, wait_us 10.0
            bench.router.decide [2300, 5100]
              router.decide [2400, 5000] rows 4
                router.decide.prep [2400, 2600]
                router.decide.launch [2600, 3000]
                router.decide.fetch [3000, 4800]
          engine.dispatch [5200, 5600] gid 0, rows 4
          engine.serve [6000, 11000] arm 1, rows 4
            engine.finalize [7000, 10000] gid 0, rows 4
              bench.router.update [7500, 9500]
                router.update [7600, 9400] rows 4
      engine.end_slice [13000, 19000]
        router.end_slice [13100, 18900]
          router.train.launch [13100, 13500]
          router.train.wait [13500, 18900]

Host line "worker": engine.submit [3000, 3500] rows 8 and
[20000, 20500] rows 4 (overlapping engine.decide in time, but on another
line, so no child of it).

Device TPU:0 operations (on a line whose clock starts at 2000), with
their scope path as the stat tf_op, on the event (%fusion.1) or on its
metadata (the others; %fusion.8's as a reference to a stat name):
%fusion.1 [2700, 2900] router.decide; %fusion.8 [8000, 8600]
router.update; %while.3 [14000, 16000] router.train_sgd, holding its
body's %fusion.5 [14200, 14800] and %fusion.6 [15000, 15500];
%conditional.2 [16200, 18000] router.rebuild, holding its branch's
%fusion.7 [16500, 17500] (under a transpose(jvp(...)) path); %copy.1
[18000, 18200] with no scope.
"""
import os

from tensorflow.tsl.profiler.protobuf import xplane_pb2


def _plane(space, pid, name):
    p = space.planes.add()
    p.id, p.name = pid, name
    return p


def _stat_id(plane, name):
    sid = next((m.id for m in plane.stat_metadata.values()
                if m.name == name), len(plane.stat_metadata) + 1)
    plane.stat_metadata[sid].id = sid
    plane.stat_metadata[sid].name = name
    return sid


def _stats(plane, owner, stats):
    for key, value in stats.items():
        s = owner.stats.add()
        s.metadata_id = _stat_id(plane, key)
        if isinstance(value, Ref):      # the string kept as a stat name
            s.ref_value = _stat_id(plane, value.text)
        elif isinstance(value, str):
            s.str_value = value
        elif isinstance(value, float):
            s.double_value = value
        else:
            s.int64_value = value


class Ref:
    def __init__(self, text):
        self.text = text


def _line(plane, lid, name, events, t0=0):
    """Events (name, start, end[, event stats[, metadata stats]])."""
    line = plane.lines.add()
    line.id, line.name, line.timestamp_ns = lid, name, t0
    for ename, start, end, *st in events:
        mid = len(plane.event_metadata) + 1
        plane.event_metadata[mid].id = mid
        plane.event_metadata[mid].name = ename
        ev = line.events.add()
        ev.metadata_id = mid
        ev.offset_ps = (start - t0) * 1000
        ev.duration_ps = (end - start) * 1000
        _stats(plane, ev, st[0] if st else {})
        _stats(plane, plane.event_metadata[mid], st[1] if len(st) > 1
               else {})


def main():
    space = xplane_pb2.XSpace()
    host = _plane(space, 1, "/host:CPU")
    _line(host, 1, "python", [
        ("engine.submit", 100, 500, {"rows": 3}),
        ("bench.window", 1000, 21000),
        ("bench.frontend.pump", 2000, 12000),
        ("engine.pump", 2100, 11900),
        ("engine.decide", 2200, 5200,
         {"gid": 0, "rows": 4, "wait_us": 10.0}),
        ("bench.router.decide", 2300, 5100),
        ("router.decide", 2400, 5000, {"rows": 4}),
        ("router.decide.prep", 2400, 2600),
        ("router.decide.launch", 2600, 3000),
        ("router.decide.fetch", 3000, 4800),
        ("engine.dispatch", 5200, 5600, {"gid": 0, "rows": 4}),
        ("engine.serve", 6000, 11000, {"arm": 1, "rows": 4}),
        ("engine.finalize", 7000, 10000, {"gid": 0, "rows": 4}),
        ("bench.router.update", 7500, 9500),
        ("router.update", 7600, 9400, {"rows": 4}),
        ("engine.end_slice", 13000, 19000),
        ("router.end_slice", 13100, 18900),
        ("router.train.launch", 13100, 13500),
        ("router.train.wait", 13500, 18900)])
    _line(host, 2, "worker", [
        ("engine.submit", 3000, 3500, {"rows": 8}),
        ("engine.submit", 20000, 20500, {"rows": 4})])
    dev = _plane(space, 2, "/device:TPU:0")
    _line(dev, 1, "XLA Modules", [("jit__srv_decide(1)", 2700, 2900),
                                  ("jit__srv_update(2)", 8000, 8600),
                                  ("jit__srv_train(3)", 14000, 18200)])
    train = "jit(_srv_train)/router.train_sgd/while"
    rebuild = "jit(_srv_train)/router.rebuild/cond"
    none = {}
    _line(dev, 2, "XLA Ops", [
        # the path on the event itself
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.1)", 2700, 2900,
         {"tf_op": "jit(_srv_decide)/router.decide/dot_general"}),
        # the path on the event's metadata, as a string or a reference
        ("%fusion.8 = f32[8]{0} fusion(f32[8]{0} %p.2)", 8000, 8600, none,
         {"tf_op": Ref("jit(_srv_update)/router.update/add")}),
        ("%while.3 = (s32[]) while((s32[]) %t.1)", 14000, 16000, none,
         {"tf_op": train}),
        ("%fusion.5 = f32[8]{0} fusion(f32[8]{0} %p.3)", 14200, 14800,
         none, {"tf_op": train + "/body/dot_general"}),
        ("%fusion.6 = f32[8]{0} fusion(f32[8]{0} %p.4)", 15000, 15500,
         none, {"tf_op": train + "/body/add"}),
        ("%conditional.2 = (f32[8]{0}) conditional(s32[] %i.1)", 16200,
         18000, none, {"tf_op": rebuild}),
        ("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p.5)", 16500, 17500,
         none, {"tf_op": "transpose(jvp(jit(_srv_train)/router.rebuild))"
                         "/cond/branch_1_fun/dot_general"}),
        ("%copy.1 = f32[8]{0} copy(f32[8]{0} %p.6)", 18000, 18200)],
        t0=2000)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "spans.xplane.pb")
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    main()
