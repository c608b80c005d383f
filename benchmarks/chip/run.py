#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout.  The cell (``BENCHMARK.json``'s
``workloads``) names a configuration (``configs/<name>.json``) and a
traffic mix (``traffic/<name>.json``, whose ``driver`` names the module
that runs it); per-layer metrics are read by ``metrics/<name>.py`` and the
limits of the correctness numbers sit in ``limits/<cell>.json``.

A run loads, warms up (set-up), measures whole units of work until
``--seconds`` have passed, reads the device's peak memory, frees the
program's state, compares what the timed path produced with the plain
reference, and prints the result as the last line of standard output.
With ``--trace 1`` the window is traced instead (``trace_units`` units)
and the per-layer metrics are reported.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# the TPU runtime's own logs would go to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def load_cell(name: str, root: Path = ROOT) -> tuple[dict, dict, dict]:
    """(BENCHMARK.json, its workload entry, the traffic file)."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: "
                         f"{', '.join(cells)}")
    cell = cells[name]
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, traffic


def load_limits(name: str) -> dict:
    """The limit of each correctness number of a cell."""
    return json.loads((HERE / "limits" / f"{name}.json").read_text())[
        "limits"]


def cell_metrics(bench: dict, cell: dict, kind: str) -> list:
    """The metrics of ``kind`` ("end_to_end" | "per_layer") this cell
    reports."""
    out = []
    for m in bench[kind]:
        if "workloads" in m:
            if cell["name"] in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end":
            out.append(m)
    return out


def read_metric(name: str, ctx: dict):
    """Run ``metrics/<name>.py``'s ``read(ctx)``; None when it finds
    nothing to read."""
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", HERE / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def need_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} devices")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devs)}")
    return devs[:n]


def compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when set,
    else the fixed ``<checkout>/.cache/jax_compile``; every program is
    kept, however fast it compiled."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".cache" / "jax_compile")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Counts backend compilations (each a cache miss) and cache hits."""

    def __init__(self):
        import jax
        self.compiles = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, _secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def memory_peak(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def make_cell(cfg_name: str, traffic: dict, seed: int, chips: int, **kw):
    from dims import load
    d = load(cfg_name)
    driver = importlib.import_module(traffic["driver"])
    return d, driver.Cell(d, traffic, seed, chips, log=log, **kw)


def window(cell, seconds: float) -> tuple[int, int, float]:
    """Whole units until ``seconds`` have passed: (units, work, seconds)."""
    units = work = 0
    t0 = time.perf_counter()
    while True:
        work += cell.unit()
        units += 1
        dt = time.perf_counter() - t0
        if dt >= seconds:
            return units, work, dt


def traced(cell, units: int, out_dir: Path):
    """Run ``units`` units under the profiler; returns (trace, flops)."""
    import jax
    import devtrace as tr
    shutil.rmtree(out_dir, ignore_errors=True)
    out_dir.mkdir(parents=True)
    f0 = getattr(cell, "flops", 0.0)
    jax.profiler.start_trace(str(out_dir))
    try:
        for _ in range(units):
            cell.unit()
    finally:
        jax.profiler.stop_trace()
    flops = (cell.flops - f0 if hasattr(cell, "flops")
             else units * cell.flops_per_unit)
    return tr.load(str(out_dir)), flops


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell_spec, traffic = load_cell(args.workload)
    chips = cell_spec["chips"]
    try:
        devs = need_chips(chips)
    except NoChip as e:
        log(f"run: {e}")
        return 2
    import jax
    import checks
    import counts
    cache = compile_cache()
    counter = CompileCounter()
    dev = devs[0]
    log(f"run: {args.workload} on {chips} x {dev.device_kind}, seed "
        f"{args.seed}, jax {jax.__version__}, compile cache {cache}")

    d, cell = make_cell(cell_spec["config"], traffic, args.seed, chips)
    setup_s = time.perf_counter() - T_START
    compiles_setup = counter.compiles
    log(f"run: set-up {setup_s:.3f} s, {compiles_setup} compiles, "
        f"{counter.hits} cache hits")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": chips}
    metrics, breakdown = {}, None
    if args.trace:
        tr_dir = ROOT / ".cache" / "bench_trace" / args.workload
        trace, flops = traced(cell, traffic["trace_units"], tr_dir)
        import devtrace as tr
        busy, win = tr.idle_share(trace)
        device["memory_peak_bytes"] = memory_peak(devs)
        device["busy_s"], device["window_s"] = busy, win
        ctx = {"trace": trace, "cell": cell, "dims": d, "traffic": traffic,
               "peak": counts.peaks(dev.device_kind), "chips": chips,
               "units": traffic["trace_units"], "flops": flops,
               "memory_peak_bytes": device["memory_peak_bytes"],
               "busy_s": busy, "window_s": win}
        for m in cell_metrics(bench, cell_spec, "per_layer"):
            v = read_metric(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(trace),
                     "idle_gaps": tr.idle_gaps(trace)}
        shutil.rmtree(tr_dir, ignore_errors=True)
        units = traffic["trace_units"]
    else:
        units, work, secs = window(cell, args.seconds)
        device["memory_peak_bytes"] = memory_peak(devs)
        measured = cell.end_to_end(units, work, secs)
        measured["setup_s"] = (setup_s, "s")
        for m in cell_metrics(bench, cell_spec, "end_to_end"):
            v, unit = measured[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": unit}
        log(f"run: window {units} units, {work} tokens in {secs:.3f} s")
    compiles_window = counter.compiles - compiles_setup
    log(f"run: {compiles_window} compiles after set-up")

    cell.free()
    gc.collect()
    limits = load_limits(args.workload)
    t_check = time.perf_counter()
    numbers = cell.check()
    log(f"run: reference check {time.perf_counter() - t_check:.3f} s")
    ok, rows = checks.verdict(numbers, limits)
    failed, attempted = cell.failed, cell.attempted
    ok = ok and failed == 0
    for name, value, lim in rows:
        log(f"check {name}: {value:.6g} limit {lim} "
            f"{numbers[name][1]}".rstrip())
    result = {"correct": bool(ok), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {name: {"value": value if math.isfinite(value)
                               else str(value), "limit": lim}
                        for name, value, lim in rows}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
