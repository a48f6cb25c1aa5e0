"""Run one cell of ``BENCHMARK.json`` once and print its result line.

Everything is found by name: the cell's configuration in
``bench/configs/<config>.json``, its traffic mix in
``bench/traffic/<traffic>.json`` (which names its driver,
``bench/drivers/<driver>.py``), and each per-layer metric's reader in
``bench/metrics/<metric>.py``. Adding a cell, a mix or a metric adds
files and entries; nothing here changes.

The run fails, printing no result, unless JAX sees the cell's number of
TPU chips and the kernel backend resolves to the compiled Pallas
kernels. With ``--trace 0`` the result carries the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics and the device's busy
time from the profiler's trace of the window.
"""
from __future__ import annotations

import argparse
import copy
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time

from bench.common import BENCH, ROOT, load_json

OUT_DIR = os.path.join(ROOT, ".bench_out")


class DeviceError(RuntimeError):
    """The machine is not what the cell asks for."""


def check_device(n_chips: int) -> dict:
    """Fail unless JAX sees ``n_chips`` TPUs and every kernel op
    resolves to the compiled Pallas kernel on its own."""
    import jax

    from repro.kernels.backend import PALLAS, resolve_backend

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if dev["platform"] != "tpu":
        raise DeviceError(f"no TPU: JAX runs on {dev['platform']!r}")
    if dev["count"] != n_chips:
        raise DeviceError(f"the cell asks for {n_chips} chip(s), JAX sees "
                          f"{dev['count']}")
    if "REPRO_KERNEL_BACKEND" in os.environ:
        raise DeviceError("REPRO_KERNEL_BACKEND is set; the platform must "
                          "choose the kernel backend")
    backend = resolve_backend(None)
    if backend != PALLAS:
        raise DeviceError(f"kernel backend resolves to {backend!r}")
    return dev


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks, default=0))


def enable_cache() -> str:
    """The program's persistent compile cache (inside the checkout, or
    ``$JAX_COMPILATION_CACHE_DIR``), keeping every program however fast
    it compiled, so that only a checkout's first run compiles."""
    import jax

    from repro.common.compilation import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _merge(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in (over or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def find_cell(bench: dict, workload: str):
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    return cell, config, traffic


def applies(metric: dict, cell: dict, reported=()) -> bool:
    """Whether ``cell`` reports ``metric``: listed under its
    ``workloads``, or, without the key, every cell that reports the
    end-to-end metric it moves."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _xplane(trace_dir: str) -> str:
    found = []
    for dirpath, _, files in os.walk(trace_dir):
        found += [os.path.join(dirpath, f) for f in files
                  if f.endswith(".xplane.pb")]
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    return sorted(found)[-1]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, overrides: dict = None, need_chip: bool = True,
             control: bool = False) -> dict:
    """One run; returns the result line as a dict (``checks`` last).
    ``control``: the reference in the configuration's
    ``control_precision`` takes the program's place in the comparison."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell, config, traffic = find_cell(bench, workload)
    config = _merge(config, (overrides or {}).get("config"))
    traffic = _merge(traffic, (overrides or {}).get("traffic"))
    dev = check_device(cell["chips"]) if need_chip else device_info()
    if need_chip:
        enable_cache()
    # the precision the configuration states for float32 products, the
    # program's and the kernels' alike (None: the platform's default)
    import jax
    jax.config.update("jax_default_matmul_precision",
                      config.get("matmul_precision"))
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    trace_dir = None
    if trace:
        trace_dir = os.path.join(OUT_DIR, f"trace-{workload}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"workload": workload, "config": config, "traffic": traffic,
           "seed": int(seed), "seconds": float(seconds),
           "trace": bool(trace), "trace_dir": trace_dir,
           "memory_peak": memory_peak,
           "control": config["control_precision"] if control else None}
    res = driver.run(ctx)
    setup_s = res["t_window"] - t_start

    from bench import flops
    peak = flops.peaks(dev["kind"]) if need_chip else None
    summary = None
    if trace:
        from bench.trace import reduce
        summary = reduce(_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    e2e = {m["name"]: m for m in bench["end_to_end"] if applies(m, cell)}
    metrics = {}
    if not trace:
        values = dict(res["end_to_end"], setup_s=setup_s)
        for name, m in e2e.items():
            metrics[name] = {"value": float(values[name]), "unit": m["unit"]}
    else:
        data = {"layer": res["layer"], "trace": summary, "peak": peak,
                "chips": dev["count"], "config": config,
                "traffic": traffic}
        for m in bench["per_layer"]:
            if not applies(m, cell, e2e):
                continue
            v = reader(m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    limits = config["limits"]
    # a reading that is not finite fails, and is printed as 1e300
    checks = {k: {"value": float(v) if math.isfinite(v) else 1e300,
                  "limit": float(limits[k])}
              for k, v in res["numbers"].items()}
    device = dict(dev, memory_peak_bytes=int(res["memory_peak_bytes"]))
    if summary is not None:
        device.update(busy_s=summary["busy_s"],
                      window_s=summary["window_s"])
    out = {"correct": all(c["value"] <= c["limit"]
                          for c in checks.values()),
           "attempted": int(res["attempted"]), "failed": int(res["failed"]),
           "metrics": metrics, "device": device}
    if summary is not None:
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["compiles_in_window"] = int(res["compiles_in_window"])
    out["check_s"] = float(res["check_s"])
    out["checks"] = checks
    return out


def main(argv=None, t_start: float = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except DeviceError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
