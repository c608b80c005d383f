#!/usr/bin/env python3
"""Readings that the limits in ``limits/<cell>.json`` are set from, taken
on the chip at the cell's own size, many seeds in one process:

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,3 [--control 1,2,3] [--faults 1,2,3]

For each of ``--seeds`` the program's numbers (set-up and its first unit
of work, as a run makes them, against the float32 reference).  For each of
``--control`` the control's: the reference computed in ``--prec`` (int8:
weights per output channel, activations per row; fp8: both operands in
e4m3) put in the program's place.  For each of ``--faults`` the planted
fault of a step that averages over half of each batch, in the reference
put in the program's place.  Prints one JSON line per reading and a
summary of the largest and smallest.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402  (sets up sys.path and the runtime's log dir)


def seeds(text):
    return [int(s) for s in text.split(",") if s]


def emit(kind, seed, numbers):
    row = {"kind": kind, "seed": seed,
           "numbers": {k: v for k, (v, _) in numbers.items()},
           "where": {k: w for k, (_, w) in numbers.items() if w}}
    print(json.dumps(row), flush=True)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control", type=seeds, default=[])
    ap.add_argument("--faults", type=seeds, default=[])
    ap.add_argument("--prec", default="int8",
                    help="the control's precisions, comma-separated "
                         "(int8, fp8)")
    args = ap.parse_args(argv)
    _, spec, traffic = run.load_cell(args.workload)
    run.need_chips(spec["chips"])
    run.compile_cache()
    import jax

    import checks
    import reference
    rows = []
    for seed in sorted(set(args.seeds) | set(args.control)
                       | set(args.faults)):
        t0 = time.perf_counter()
        _, cell = run.make_cell(spec["config"], traffic, seed, spec["chips"])
        cell.free()
        b = cell.data[0]
        base = (cell.base, jax.device_put(cell.theta0), b["batch"],
                b["server"], b["personal"], cell.d, cell.job)
        ref = jax.device_get(reference.pipeline(*base))
        if seed in args.seeds:
            rows.append(emit("program", seed, checks.train_numbers(
                cell.theta0, cell.first, ref)))
        for prec in (args.prec.split(",") if seed in args.control else ()):
            ctl = jax.device_get(reference.pipeline(*base, prec=prec))
            rows.append(emit(f"control_{prec}", seed, checks.train_numbers(
                cell.theta0, ctl, ref)))
        if seed in args.faults:
            half = jax.device_get(reference.pipeline(*base, drop_half=True))
            rows.append(emit("fault_half_batch", seed, checks.train_numbers(
                cell.theta0, half, ref)))
        del cell, base, ref
        gc.collect()
        run.log(f"calibrate: seed {seed} in {time.perf_counter() - t0:.1f} s")
    summary = {}
    for kind in sorted({r["kind"] for r in rows}):
        for name in rows[0]["numbers"]:
            vals = [r["numbers"][name] for r in rows if r["kind"] == kind]
            summary.setdefault(name, {})[kind] = {
                "max": max(vals), "min": min(vals), "n": len(vals)}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
