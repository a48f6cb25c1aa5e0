"""BENCHMARK.json against the benchmark's contract, and the harness's
refusal to run off a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                   r"proj|head|expand|experts_per_tok|num_experts_per")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert os.path.isdir(os.path.join(ROOT, p))
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_check_budget_fits_with_24_cells(bench):
    """2 + 14 runs a cell, each run_seconds + 60 s, 180 s of compile a
    cell, 1200 s spare: within 43,200 s at the full 24 cells."""
    cells = 24
    total = ((2 + 14 * cells) * (bench["run_seconds"] + 60)
             + cells * 2 * 90 + 1200)
    assert total <= 43200


def test_names_units_and_sources(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"])
        assert _line(c["why"]) and len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k), k
        names.append(("config", c["name"]))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        names.append(("cell", w["name"]))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        names.append(("metric", m["name"]))
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert _line(m["layer"])
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_cells_find_their_files_by_name(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        c = configs[w["config"]]
        path = os.path.join(ROOT, c["file"])
        assert os.path.isfile(path)
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        tpath = os.path.join(ROOT, "bench", "traffic", w["traffic"] + ".json")
        with open(tpath) as f:
            traffic = json.load(f)
        assert os.path.isfile(os.path.join(
            ROOT, "bench", "drivers", traffic["driver"] + ".py"))
        assert set(cfg["limits"]) >= {"lost"}
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    assert {w["config"] for w in bench["workloads"]} == set(configs)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_every_metric_has_a_reader_and_its_cells_report_what_it_moves(
        bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}

    def reports(metric, cell):
        return cell in metric.get("workloads", cells)

    for cell in cells:
        assert reports(e2e["setup_s"], cell)
        assert any(reports(m, cell) for n, m in e2e.items()
                   if n != "setup_s")
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        assert m["moves"] in e2e
        for cell in m.get("workloads", []):
            assert cell in cells and reports(e2e[m["moves"]], cell)
        if m["unit"] == "%" and m["name"].endswith("_roofline"):
            assert m["source"] == "device_trace"
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"], []).append(m["name"])
    assert all(_line(k) for k in layers)


def test_run_refuses_without_a_tpu():
    """Off a TPU the harness exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "rb11.serve_closed",
         "--seed", str(2 ** 40 + 3), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr
