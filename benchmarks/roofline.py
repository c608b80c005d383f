"""Roofline table from dry-run artifacts (experiments/dryrun/*.json).

One row per (arch × shape × mesh): the three terms, dominant bottleneck,
MODEL_FLOPS/HLO ratio, memory fit.  This is the §Roofline source of truth
— also exported into EXPERIMENTS.md by scripts in launch/report.py.
"""
from __future__ import annotations

import glob
import json
import os

DRYRUN_DIR = os.environ.get("REPRO_DRYRUN_DIR", "experiments/dryrun")


def load_records(path: str = DRYRUN_DIR) -> list[dict]:
    recs = []
    for f in sorted(glob.glob(os.path.join(path, "*.json"))):
        with open(f) as fh:
            recs.append(json.load(fh))
    return recs


def table(recs: list[dict]) -> list[str]:
    hdr = (f"{'arch':24s} {'shape':11s} {'mesh':8s} {'ok':3s} "
           f"{'mem GB':>7s} {'compute_s':>10s} {'memory_s':>10s} "
           f"{'coll_s':>10s} {'dom':10s} {'useful%':>8s}")
    lines = [hdr, "-" * len(hdr)]
    for r in recs:
        if r.get("status") != "ok":
            lines.append(f"{r['arch']:24s} {r['shape']:11s} {r['mesh']:8s} "
                         f"ERR {str(r.get('error'))[:60]}")
            continue
        ro = r["roofline"]
        name = r['arch']
        if r.get('variant', 'baseline') != 'baseline':
            name += f"+{r['variant']}"
        lines.append(
            f"{name:24s} {r['shape']:11s} {r['mesh']:8s} "
            f"{'y' if r['fits_16g'] else 'N':3s} "
            f"{r['memory']['peak_estimate_bytes']/1e9:7.2f} "
            f"{ro['compute_s']:10.3e} {ro['memory_s']:10.3e} "
            f"{ro['collective_s']:10.3e} {ro['dominant']:10s} "
            f"{100*ro['useful_flops_ratio']:8.1f}")
    return lines


def quant_decode_table() -> list[str]:
    """Analytic batch-1 decode roofline for the quantized backbone on
    the serve bench config: decode reads every live weight byte once per
    token, so step time is tree_bytes/HBM_BW and the f32/int8 byte ratio
    IS the bandwidth-bound decode speedup (adapters + logit-critical
    leaves stay f32; see docs/quantization.md)."""
    import jax

    from benchmarks.common import BENCH_CFG
    from repro.kernels.quant_matmul.ops import quantize_backbone
    from repro.launch.analysis import HBM_BW
    from repro.models import model as M
    from repro.utils import pytree as pt

    base = M.init_params(jax.random.PRNGKey(0), BENCH_CFG)
    f32 = pt.tree_bytes(base)
    lines = [f"{'decode backbone':24s} {'bytes':>10s} {'step_s':>10s} "
             f"{'speedup':>8s}   (batch-1, weight-bytes-bound)"]
    for mode, tree in [("f32", base),
                       ("int8", quantize_backbone(base, "int8")),
                       ("int4", quantize_backbone(base, "int4"))]:
        b = pt.tree_bytes(tree)
        lines.append(f"{BENCH_CFG.name + '/' + mode:24s} {b:10d} "
                     f"{b / HBM_BW:10.3e} {f32 / b:7.2f}x")
    return lines


def main():
    recs = load_records()
    print()
    for line in quant_decode_table():
        print(line)
    print()
    if not recs:
        print("no dry-run records found — run "
              "`python -m repro.launch.dryrun --all` first")
        print("name,us_per_call,derived")
        return []
    for line in table(recs):
        print(line)
    print()
    print("name,us_per_call,derived")
    for r in recs:
        if r.get("status") != "ok":
            continue
        ro = r["roofline"]
        step_s = max(ro["compute_s"], ro["memory_s"], ro["collective_s"])
        vtag = "" if r.get("variant", "baseline") == "baseline" \
            else f"+{r['variant']}"
        print(f"roofline/{r['arch']}{vtag}/{r['shape']}/{r['mesh']},"
              f"{step_s*1e6:.1f},"
              f"dom={ro['dominant']};fits={r['fits_16g']};"
              f"useful={ro['useful_flops_ratio']:.3f}")
    return recs


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
