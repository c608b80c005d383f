"""Table I — FedLoRA-Optimizer vs baselines on two dataset families.

Paper: LLaMA2-7B / DeepSeek-7B on Dolly-15k & Natural-Instructions;
here: reduced llama-family backbone on the two synthetic families
(DESIGN.md §9 — we validate the *ordering* ours > LoRA on global AND
local, not absolute accuracies).  FFA-LoRA added from related work.
"""
from __future__ import annotations

import time

from benchmarks.common import BENCH_CFG, bench_base, build_setting
from repro.core.fedlora import run_federated
from repro.fed.simulate import FedHyper

METHODS = ("fedlora_opt", "lora", "ffa_lora", "prompt", "adapter")
DATASETS = ("dolly", "ni")


def run(rounds: int = 6, log=print) -> list[dict]:
    rows = []
    for ds_name in DATASETS:
        base = bench_base(ds_name, log=lambda s: log(f"  {s}"))
        cds, sds, eg, el = build_setting(ds_name)
        for method in METHODS:
            hp = FedHyper(method=method, n_clients=len(cds), rounds=rounds,
                          local_steps=3, batch=8, seq_len=48, lr=3e-3,
                          server_lr=5e-4, global_steps=2, personal_steps=10,
                          lam=1e-3, prox_mu=0.0, seed=0)
            t0 = time.time()
            res = run_federated(BENCH_CFG, hp, cds, sds, eg, el, base=base)
            row = {"dataset": ds_name, "method": method,
                   "global_acc": res.global_acc, "local_acc": res.local_acc,
                   "comm_mb": res.comm_bytes / 1e6,
                   "wall_s": time.time() - t0}
            rows.append(row)
            log(f"[table1] {ds_name:6s} {method:12s} "
                f"global={res.global_acc:.3f} local={res.local_acc:.3f} "
                f"comm={row['comm_mb']:.2f}MB ({row['wall_s']:.0f}s)")
    return rows


def main():
    rows = run()
    print("name,us_per_call,derived")
    for r in rows:
        print(f"table1/{r['dataset']}/{r['method']},"
              f"{r['wall_s']*1e6:.0f},"
              f"global_acc={r['global_acc']:.4f};local_acc={r['local_acc']:.4f}")
    return rows


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
