"""The span reduction (``bench.spans``): on a small committed trace whose
numbers are known by construction (``data/make_span_trace.py`` says what
it holds), and on a trace of the served path itself, recorded here on
the CPU."""
import glob
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import spans, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SPANS = os.path.join(DATA, "spans.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return spans.reduce(SPANS)


def test_window_and_spans_kept(summary):
    assert summary["chips"] == 1
    assert summary["window_s"] == pytest.approx(20e-6)
    # the submit before bench.window is left out; the worker line's two
    # are kept
    assert summary["spans"]["engine.submit"]["count"] == 2
    assert summary["spans"]["engine.submit"]["durations"] == \
        pytest.approx([0.5e-6, 0.5e-6])
    assert not any(n.startswith("bench.") for n in summary["spans"])


@pytest.mark.parametrize("name, total_ns, self_ns", [
    ("engine.pump", 9800, 1400),      # less decide, dispatch, serve
    ("engine.decide", 3000, 200),     # less the benchmark's decide span
    ("router.decide", 2600, 200),     # less prep, launch, fetch
    ("router.decide.fetch", 1800, 1800),
    ("engine.serve", 5000, 2000),     # less the finalize inside it
    ("engine.finalize", 3000, 1000),  # less the update inside it
    ("router.update", 1800, 1800),
    ("engine.end_slice", 6000, 200),
    ("router.end_slice", 5800, 0),
    # on another line than engine.decide, so no child of it
    ("engine.submit", 1000, 1000),
])
def test_self_time_is_duration_less_direct_children(summary, name,
                                                    total_ns, self_ns):
    rec = summary["spans"][name]
    assert rec["total_s"] == pytest.approx(total_ns * 1e-9)
    assert rec["self_s"] == pytest.approx(self_ns * 1e-9, abs=1e-15)


def test_stats_are_summed_and_parents_counted(summary):
    sp = summary["spans"]
    assert sp["engine.decide"]["stats"] == pytest.approx(
        {"gid": 0, "rows": 4, "wait_us": 10.0})
    assert sp["engine.submit"]["stats"] == pytest.approx({"rows": 12})
    assert sp["engine.serve"]["stats"] == pytest.approx({"arm": 1,
                                                         "rows": 4})
    assert sp["router.decide.launch"]["stats"] == {}
    assert sp["router.decide"]["parents"] == {"bench.router.decide": 1}
    assert sp["router.decide.launch"]["parents"] == {"router.decide": 1}
    assert sp["engine.finalize"]["parents"] == {"engine.serve": 1}
    assert sp["engine.end_slice"]["parents"] == {"bench.window": 1}
    assert sp["engine.submit"]["parents"] == {"": 2}


def test_scopes_are_unions_of_their_operations(summary):
    # the %while and its body's operations overlap: a union, not a sum
    # (which would read 3100 and 2800 ns); the unscoped copy is in none
    assert summary["scopes"] == pytest.approx({
        "router.decide": 200e-9, "router.update": 600e-9,
        "router.train_sgd": 2000e-9, "router.rebuild": 1800e-9})


def test_idle_gaps_go_to_the_innermost_span_of_either_family(summary):
    # gaps [1000, 2700] and [18200, 21000] lie in no span but the
    # window; [2900, 8000] has its midpoint in the dispatch, [8600,
    # 14000] in the pump after the serve ended, [16000, 16200] in the
    # train's wait
    assert summary["idle_by_span"] == pytest.approx({
        "host:other": 4500e-9, "engine.dispatch": 5100e-9,
        "engine.pump": 5400e-9, "router.train.wait": 200e-9})
    busy = 4800e-9
    assert sum(summary["idle_by_span"].values()) + busy == \
        pytest.approx(summary["window_s"])
    # idle while no program span is open, on any line: [1000, 2100],
    # [11900, 13000], [19000, 20000], [20500, 21000]
    assert summary["idle_outside_program_s"] == pytest.approx(3700e-9)


@pytest.mark.parametrize("name", ["small.xplane.pb", "spans.xplane.pb"])
def test_device_planes_read_as_the_profiler_reads_them(name):
    # the wire-format reader gives the operations, and their times, that
    # jax.profiler.ProfileData gives
    from jax.profiler import ProfileData
    path = os.path.join(DATA, name)
    want = {plane.name: sorted((ev.start_ns, ev.end_ns)
                               for line in plane.lines
                               if line.name == trace.OPS_LINE
                               for ev in line.events)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith(trace.DEVICE_PREFIX)}
    with open(path, "rb") as f:
        got = spans.device_ops(memoryview(f.read()))
    assert {k: sorted((s, e) for s, e, _ in v) for k, v in got.items()} \
        == want


def test_reduction_leaves_the_trace_module_as_it_reads():
    # the same file through bench.trace: busy time and the device's ops
    s = trace.reduce(SPANS)
    assert s["busy_s"] == pytest.approx(4800e-9)
    assert s["window_s"] == pytest.approx(20e-6)


def test_main_prints_the_reduction(capsys):
    import json
    assert spans.main([SPANS]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["spans"]["router.decide.fetch"]["p50_s"] == \
        pytest.approx(1.8e-6)
    assert list(out["spans"])[0] == "router.train.wait"   # most self time


# ------------------------------------------------- the served path --
def _engine(max_train_lag, clock):
    from repro.core.utilitynet import UtilityNetConfig
    from repro.data.routerbench import RouterBenchSim
    from repro.serving import AsyncRouterEngine, DevicePolicyRouter
    from repro.sim import DeviceReplayEnv, make_policy
    from repro.sim.engine import _tables

    rng = np.random.default_rng(0)
    n, K = 64, 2
    plen = rng.integers(4, 9, size=n)
    data = {
        "domain": rng.integers(0, 3, size=n).astype(np.int32),
        "topic": rng.normal(size=(n, 32)).astype(np.float32),
        "difficulty": np.zeros(n, np.float32),
        "prompt_tokens": plen.astype(np.float32),
        "quality": rng.uniform(0.2, 0.95, size=(n, K)).astype(np.float32),
        "cost": (np.array([2e-4, 1e-5])[None]
                 * (plen[:, None] + 8)).astype(np.float32),
        "x_feat": rng.normal(size=(n, 4)).astype(np.float32),
        "model_names": np.array(["a", "b"]),
    }
    env = DeviceReplayEnv.from_host(
        RouterBenchSim(seed=0, n_slices=2, cost_lambda=1.0, data=data))
    cfg = UtilityNetConfig(emb_dim=env.x_emb.shape[1], num_actions=K)
    pol, hyp = make_policy("neuralucb", env, cfg)
    router = DevicePolicyRouter(pol, hyp, _tables(env), seed=0,
                                slice_width=512, capacity_slices=8,
                                batch_size=16, train_chunks=1,
                                max_train_lag=max_train_lag)
    return AsyncRouterEngine(router, K, reward_table=np.asarray(env.reward),
                             queue_capacity=2048, decide_batch=512,
                             serve_batch=512, clock=clock)


def _rounds(eng, now, rounds, per=2048):
    from repro.serving import Request
    tok = np.arange(1, 5, dtype=np.int32)
    for r in range(rounds):
        now[0] = float(r)
        eng.submit([Request(tokens=tok, sample_idx=i % 64)
                    for i in range(per)])
        now[0] = r + 0.25         # every row waits 0.25 s in the queue
        eng.pump()
        eng.drain()
        eng.end_slice()


@pytest.mark.parametrize("lag, train_children", [
    (0, {"router.train.launch", "router.train.wait"}),
    (1, {"router.train.launch", "router.train.commit"}),
])
def test_served_path_spans_under_the_profiler(tmp_path, lag,
                                              train_children):
    import jax

    from repro.common.tracing import SPANS as NAMES

    now = [0.0]
    eng = _engine(lag, lambda: now[0])
    _rounds(eng, now, 1)            # compiles outside the trace
    done0 = eng.counters["completed"]
    with jax.profiler.trace(str(tmp_path)):
        _rounds(eng, now, 2)
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    sp = spans.reduce(path)["spans"]
    completed = eng.counters["completed"] - done0
    assert completed == 4096

    assert set(sp) <= set(NAMES)
    parents = {name: set(rec["parents"]) for name, rec in sp.items()}
    assert parents["engine.pump"] == {""}
    for child, parent in [
            ("engine.decide", "engine.pump"),
            ("engine.dispatch", "engine.pump"),
            ("engine.serve", "engine.pump"),
            ("engine.finalize", "engine.serve"),
            ("router.decide", "engine.decide"),
            ("router.decide.prep", "router.decide"),
            ("router.decide.launch", "router.decide"),
            ("router.decide.fetch", "router.decide"),
            ("router.update", "engine.finalize"),
            ("router.update.prep", "router.update"),
            ("router.update.launch", "router.update"),
            ("router.end_slice", "engine.end_slice")]:
        assert parents[child] == {parent}, child
    assert {n for n, p in parents.items() if p == {"router.end_slice"}} \
        == train_children
    assert sp["engine.end_slice"]["count"] == 2

    # rows: each decided, dispatched, served and finalized once
    for name in ("engine.decide", "engine.dispatch", "engine.serve",
                 "engine.finalize", "router.decide", "router.update"):
        assert sp[name]["stats"]["rows"] == completed, name
    assert sp["engine.submit"]["stats"]["rows"] == completed
    # the queue wait: 0.25 s a row, summed on the engine's clock
    assert sp["engine.decide"]["stats"]["wait_us"] == \
        pytest.approx(completed * 0.25e6)
    # spans per call or microbatch, never per row
    n_spans = sum(rec["count"] for rec in sp.values())
    assert n_spans < 0.05 * completed
