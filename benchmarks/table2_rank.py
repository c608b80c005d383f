"""Table II — LoRA hyperparameters: rank r × number of adapted modules n.

Paper sweeps r×n on the Causal task (Dolly); n is the number of adapted
attention projections (n=1: Q; n=2: Q,V — the paper's default; n=4:
Q,K,V,O).  Reports Causal-task accuracy + trainable-parameter fraction.
"""
from __future__ import annotations

import dataclasses
import time

import jax

from benchmarks.common import BENCH_CFG, bench_base, build_setting
from repro.core.fedlora import run_federated
from repro.fed.simulate import FedHyper
from repro.utils import pytree as pt
from repro.core import peft

GRID = [(4, 1), (8, 1), (16, 1), (8, 2), (4, 4)]
N_TARGETS = {1: ("q_proj",), 2: ("q_proj", "v_proj"),
             4: ("q_proj", "k_proj", "v_proj", "o_proj")}


def run(rounds: int = 5, log=print) -> list[dict]:
    base = bench_base("dolly", log=lambda s: log(f"  {s}"))
    cds, sds, eg, el = build_setting("dolly")
    n_base = pt.tree_count_params(base)
    rows = []
    for r, n in GRID:
        cfg = dataclasses.replace(BENCH_CFG, lora_rank=r,
                                  lora_targets=N_TARGETS[n])
        ad = peft.add_lora(base, cfg, jax.random.PRNGKey(0), decomposed=True)
        # count only live factor params (exclude the dA/dB pipeline deltas)
        n_ad = sum(x.size for p, x in
                   zip(pt.tree_paths(ad), jax.tree.leaves(ad))
                   if not p.endswith(("dA_dir", "dB_mag")))
        hp = FedHyper(method="fedlora_opt", n_clients=len(cds),
                      rounds=rounds, local_steps=3, batch=8, seq_len=48,
                      lr=3e-3, personal_steps=8, global_steps=2, seed=0)
        t0 = time.time()
        res = run_federated(cfg, hp, cds, sds, eg, el, base=base)
        row = {"r": r, "n": n, "acc": res.local_acc,
               "global_acc": res.global_acc,
               "pct_params": 100.0 * n_ad / n_base,
               "wall_s": time.time() - t0}
        rows.append(row)
        log(f"[table2] r={r} n={n}: local_acc={row['acc']:.3f} "
            f"%params={row['pct_params']:.3f}")
    return rows


def main():
    rows = run()
    print("name,us_per_call,derived")
    for r in rows:
        print(f"table2/r{r['r']}xn{r['n']},{r['wall_s']*1e6:.0f},"
              f"acc={r['acc']:.4f};pct_params={r['pct_params']:.4f}")
    return rows


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
