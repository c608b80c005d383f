"""Benchmark harness — one entry per paper table/figure + infra perf.

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig1,...]

Prints ``name,us_per_call,derived`` CSV per run (plus human-readable
logs) and writes JSON to experiments/bench/.  Every row is recorded
through a ``repro.obs.MetricsRegistry`` — the CSV and the ``metrics``
key in results.json are both rendered from its ``snapshot()``, so the
bench results share the exact schema the engines' telemetry emits.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.obs import MetricsRegistry

ALL = ("table1", "table2", "fig1", "fig3", "perf", "het", "cohort",
       "dist", "pipeline", "quant", "serve", "tier", "obs", "roofline")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list from: " + ",".join(ALL))
    ap.add_argument("--fresh", action="store_true",
                    help="ignore cached per-bench JSON results")
    args = ap.parse_args()
    which = args.only.split(",") if args.only else list(ALL)

    def cached(name, fn):
        path = f"experiments/bench/{name}.json"
        if not args.fresh and os.path.exists(path):
            print(f"[{name}] using cached results from {path}")
            with open(path) as f:
                return json.load(f)
        out = fn()
        with open(path, "w") as f:
            json.dump(out, f, indent=1, default=str)
        return out

    os.makedirs("experiments/bench", exist_ok=True)
    results = {}
    reg = MetricsRegistry()

    def record(name, us, derived):
        # one labeled series per bench row; the CSV below and the
        # results.json "metrics" key render from reg.snapshot()
        reg.gauge("bench/us_per_call").set(float(us), name=name,
                                           derived=str(derived))

    t00 = time.time()
    if "table1" in which:
        from benchmarks import table1_accuracy
        rows = cached("table1", table1_accuracy.run)
        results["table1"] = rows
        for r in rows:
            record(f"table1/{r['dataset']}/{r['method']}", r['wall_s']*1e6,
                   f"global_acc={r['global_acc']:.4f};"
                   f"local_acc={r['local_acc']:.4f}")
    if "table2" in which:
        from benchmarks import table2_rank
        rows = cached("table2", table2_rank.run)
        results["table2"] = rows
        for r in rows:
            record(f"table2/r{r['r']}xn{r['n']}", r['wall_s']*1e6,
                   f"acc={r['acc']:.4f};pct_params={r['pct_params']:.4f}")
    if "fig1" in which:
        from benchmarks import fig1_sensitivity
        rep = cached("fig1", fig1_sensitivity.run)
        results["fig1"] = rep
        record("fig1/sensitivity", rep['wall_s']*1e6,
               f"dirA_over_dirB={rep['obs1_dir_ratio_A_over_B']:.3f};"
               f"magB_over_magA={rep['obs2_mag_ratio_B_over_A']:.3f}")
    if "fig3" in which:
        from benchmarks import fig3_pipeline
        rows = cached("fig3", fig3_pipeline.run)
        results["fig3"] = rows
        for r in rows:
            tag = "post-serial" if r["pipeline"] else "pre-serial"
            record(f"fig3/{tag}", r['wall_s']*1e6,
                   f"local_acc={r['local_acc']:.4f}")
    if "perf" in which:
        from benchmarks import perf_micro
        rows = cached("perf", perf_micro.run)
        results["perf"] = rows
        for r in rows:
            record(f"perf/{r['arch']}/fwd", r['fwd_us'], "smoke_cpu")
            record(f"perf/{r['arch']}/decode", r['dec_us'], "smoke_cpu")
    if "het" in which:
        from benchmarks import perf_micro
        rows = cached("het", lambda: perf_micro.run_het_round()[0])
        results["het"] = rows
        for r in rows:
            record(f"perf/{r['arch']}", r['us'],
                   f"ratio_vs_uniform={r['ratio']:.2f}")
    if "cohort" in which:
        from benchmarks import perf_micro
        rows = cached("cohort", lambda: perf_micro.run_cohort()[0])
        results["cohort"] = rows
        for r in rows:
            record(f"perf/{r['arch']}", r['us'],
                   f"ratio_vs_full={r['ratio']:.2f}")
    if "dist" in which:
        from benchmarks import perf_micro
        rows = cached("dist", lambda: perf_micro.run_dist_round()[0])
        results["dist"] = rows
        for r in rows:
            record(f"perf/{r['arch']}", r['us'],
                   f"ratio_vs_engine={r['ratio']:.2f}")
    if "pipeline" in which:
        from benchmarks import perf_micro
        rows = cached("pipeline", lambda: perf_micro.run_pipeline()[0])
        results["pipeline"] = rows
        for r in rows:
            record(f"perf/{r['arch']}", r['us'],
                   f"ratio_vs_engine={r['ratio']:.2f}")
    if "quant" in which:
        from benchmarks import perf_micro
        rows = cached("quant", lambda: perf_micro.run_quant()[0])
        results["quant"] = rows
        for r in rows:
            extra = (f"bytes_ratio={r['bytes_ratio']:.2f}"
                     if "bytes_ratio" in r else "smoke_cpu")
            record(f"perf/{r['arch']}", r['us'], extra)
    if "serve" in which:
        from benchmarks import serve_multitenant
        rows = cached("serve", lambda: (serve_multitenant.run()[0]
                                        + serve_multitenant.run_quant()[0]))
        results["serve"] = rows
        for r in rows:
            record(r['arch'], r['us'], f"tokens_s={r['tokens_s']:.1f}")
    if "tier" in which:
        from benchmarks import serve_multitenant
        rows = cached("tier", lambda: serve_multitenant.run_churn()[0])
        results["tier"] = rows
        for r in rows:
            extra = (f";ratio={r['ratio']:.2f}" if "ratio" in r else "")
            record(r['arch'], r['us'],
                   f"tokens_s={r['tokens_s']:.1f}" + extra)
    if "obs" in which:
        from benchmarks import perf_micro
        rows = cached("obs", lambda: perf_micro.run_obs()[0])
        results["obs"] = rows
        for r in rows:
            record(f"perf/{r['arch']}", r['us'],
                   f"ratio_vs_disabled={r['ratio']:.3f}")
    if "roofline" in which:
        from benchmarks import roofline
        recs = roofline.load_records()
        results["roofline_n"] = len(recs)
        for line in roofline.quant_decode_table():
            print(line)
        for line in roofline.table(recs):
            print(line)
        for r in recs:
            if r.get("status") != "ok":
                continue
            ro = r["roofline"]
            step_s = max(ro["compute_s"], ro["memory_s"], ro["collective_s"])
            vtag = "" if r.get("variant", "baseline") == "baseline" \
                else f"+{r['variant']}"
            record(f"roofline/{r['arch']}{vtag}/{r['shape']}/{r['mesh']}",
                   step_s*1e6,
                   f"dom={ro['dominant']};fits={r['fits_16g']}")

    snap = reg.snapshot()
    results["metrics"] = snap
    csv_lines = ["name,us_per_call,derived"]
    for s in snap["gauges"].get("bench/us_per_call", []):
        csv_lines.append(f"{s['labels']['name']},{s['value']:.0f},"
                         f"{s['labels']['derived']}")
    with open("experiments/bench/results.json", "w") as f:
        json.dump(results, f, indent=1, default=str)
    print()
    print("\n".join(csv_lines))
    print(f"\n[benchmarks done in {time.time()-t00:.0f}s; "
          f"JSON -> experiments/bench/results.json]")


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
