"""Fig. 3 — pipeline (global→local) vs non-pipeline (local-only) ablation.

Paper: DeepSeek-7B on Dolly; pipeline-structured (global optimizer stage
before personalization) beats feeding the LoRA-tuned model straight to
the local optimizer, on all three tasks.
"""
from __future__ import annotations

import time

from benchmarks.common import BENCH_CFG, bench_base, build_setting, PAPER_TASKS
from repro.core.fedlora import run_federated
from repro.fed.simulate import FedHyper


def run(rounds: int = 6, log=print) -> list[dict]:
    base = bench_base("ni", log=lambda s: log(f"  {s}"))
    cds, sds, eg, el = build_setting("ni")
    rows = []
    for pipeline in (True, False):
        hp = FedHyper(method="fedlora_opt", n_clients=len(cds),
                      rounds=rounds, local_steps=3, batch=8, seq_len=48,
                      lr=3e-3, server_lr=5e-4, global_steps=2,
                      personal_steps=10, lam=1e-3, pipeline=pipeline, seed=0)
        t0 = time.time()
        res = run_federated(BENCH_CFG, hp, cds, sds, eg, el, base=base)
        # per-client == per-task accuracies (client c specializes task c)
        per_task = {PAPER_TASKS[i % len(PAPER_TASKS)]: float(a)
                    for i, a in enumerate(res.per_client)}
        row = {"pipeline": pipeline, "local_acc": res.local_acc,
               "global_acc": res.global_acc, "per_task": per_task,
               "wall_s": time.time() - t0}
        rows.append(row)
        log(f"[fig3] pipeline={pipeline}: local={res.local_acc:.3f} "
            f"per-task={ {k: round(v,3) for k,v in per_task.items()} }")
    return rows


def main():
    rows = run()
    print("name,us_per_call,derived")
    for r in rows:
        tag = "post-serial" if r["pipeline"] else "pre-serial"
        per = ";".join(f"{k}={v:.4f}" for k, v in r["per_task"].items())
        print(f"fig3/{tag},{r['wall_s']*1e6:.0f},local_acc={r['local_acc']:.4f};{per}")
    return rows


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
