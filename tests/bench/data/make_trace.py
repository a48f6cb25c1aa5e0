"""Writes ``small.xplane.pb``, the hand-made profiler trace that
``test_bench_trace.py`` reduces (needs TensorFlow's xplane proto; run
it only to change the trace):

    python3 tests/bench/data/make_trace.py

Times in ns. Host spans: bench.window [1000, 11000], bench.router.decide
[2000, 4000], bench.frontend.pump [5000, 9000] holding
bench.router.update [6000, 7000]. Device TPU:0 operations, named by
their HLO text as a TPU trace names them: %fusion.3 [500, 1500] (starts
before the window), %fusion.1 [2500, 3000], the Pallas call
%nucb_decide_padded.1 [3000, 3500], %slice.7 [3500, 3700] (which takes
the Pallas call's output as its operand), %fusion.2 [6000, 6800];
programs jit__srv_decide [2500, 3500] and jit__srv_update [6000, 6800].
Device TPU:1: %fusion.2 [2000, 2600] (the same name as TPU:0's last
operation), in program jit__srv_update [2000, 2600].
"""
import os

from tensorflow.tsl.profiler.protobuf import xplane_pb2


def _plane(space, pid, name):
    p = space.planes.add()
    p.id, p.name = pid, name
    return p


def _line(plane, lid, name, events, stats=None):
    line = plane.lines.add()
    line.id, line.name, line.timestamp_ns = lid, name, 0
    for ename, start, end, *st in events:
        mid = len(plane.event_metadata) + 1
        plane.event_metadata[mid].id = mid
        plane.event_metadata[mid].name = ename
        ev = line.events.add()
        ev.metadata_id = mid
        ev.offset_ps = start * 1000
        ev.duration_ps = (end - start) * 1000
        for key, value in (st[0] if st else {}).items():
            sid = stats[key]
            s = ev.stats.add()
            s.metadata_id = sid
            s.str_value = value


def main():
    space = xplane_pb2.XSpace()
    host = _plane(space, 1, "/host:CPU")
    _line(host, 1, "python", [
        ("bench.window", 1000, 11000), ("bench.router.decide", 2000, 4000),
        ("bench.frontend.pump", 5000, 9000),
        ("bench.router.update", 6000, 7000)])
    dev = _plane(space, 2, "/device:TPU:0")
    dev.stat_metadata[1].id = 1
    dev.stat_metadata[1].name = "Time Scale Multiplier"
    _line(dev, 1, "XLA Modules", [("jit__srv_decide(1)", 2500, 3500),
                                  ("jit__srv_update(2)", 6000, 6800)])
    _line(dev, 2, "XLA Ops", [
        ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p.1)", 500, 1500),
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.2)", 2500, 3000),
        ("%nucb_decide_padded.1 = (s32[512,1]{1,0}) custom-call(f32[512,384]"
         "{1,0} %p.3), custom_call_target=\"tpu_custom_call\"", 3000, 3500,
         {"Time Scale Multiplier": "1.0"}),
        ("%slice.7 = s32[256,1]{1,0} slice(s32[512,1]{1,0} "
         "%nucb_decide_padded.1), slice={[0:256], [0:1]}", 3500, 3700),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p.4)", 6000, 6800)],
        stats={"Time Scale Multiplier": 1})
    dev1 = _plane(space, 3, "/device:TPU:1")
    _line(dev1, 1, "XLA Modules", [("jit__srv_update(2)", 2000, 2600)])
    _line(dev1, 2, "XLA Ops", [
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p.4)", 2000, 2600)])
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "small.xplane.pb")
    with open(path, "wb") as f:
        f.write(space.SerializeToString())


if __name__ == "__main__":
    main()
