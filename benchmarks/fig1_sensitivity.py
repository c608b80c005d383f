"""Fig. 1 — sensitivity of LoRA factor direction/magnitude (Eqs. 2-3).

Fine-tune decomposed-LoRA per downstream task and on the all-task mixture
from the same pretrained base, then measure ΔM/ΔD of A and B between each
task fine-tune and the all-task fine-tune.  Paper observations to verify
qualitatively: ΔD(A) > ΔD(B)  (≈1.7×)  and  ΔM(B) ≫ ΔM(A)  (≈41×).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import BENCH_CFG, bench_base, PAPER_TASKS, task_probs, mixture_probs
from repro.core import peft
from repro.core.sensitivity import sensitivity_report
from repro.data.synthetic import SyntheticInstructionDataset, make_dataset_family
from repro.models import model as M
from repro.optim import adamw, masked, chain_clip
from repro.optim.optimizers import apply_updates
from repro.utils import pytree as pt


def _finetune_lora(base, cfg, dataset, steps=80, lr=3e-3, seed=0):
    adapters = peft.add_lora(base, cfg, jax.random.PRNGKey(seed),
                             decomposed=True)
    mask = peft.mask_stage_local_pretrain(adapters)
    opt = chain_clip(masked(adamw(lr), mask), 1.0)
    ost = opt.init(adapters)

    @jax.jit
    def step(ad, ost, b, i):
        def loss(ad):
            return M.loss_and_metrics(pt.merge_trees(base, ad), b, cfg)[0]
        g = jax.grad(loss)(ad)
        upd, ost = opt.update(g, ost, ad, i)
        return apply_updates(ad, upd), ost

    rng = np.random.default_rng(seed)
    for i in range(steps):
        b = {k: jnp.asarray(v)
             for k, v in dataset.sample_batch(rng, 16, 48).items()}
        adapters, ost = step(adapters, ost, b, jnp.asarray(i))
    return adapters


def run(steps: int = 60, log=print) -> dict:
    t0 = time.time()
    base = bench_base("dolly", log=lambda s: log(f"  {s}"))
    fam = make_dataset_family("dolly")
    task_ads = {}
    for t in PAPER_TASKS:
        ds = SyntheticInstructionDataset(fam, task_probs(t), client_seed=0)
        task_ads[t] = _finetune_lora(base, BENCH_CFG, ds, steps=steps)
        log(f"[fig1] fine-tuned task {t}")
    mix = SyntheticInstructionDataset(fam, mixture_probs(), client_seed=0)
    all_ad = _finetune_lora(base, BENCH_CFG, mix, steps=steps)
    rep = sensitivity_report(task_ads, all_ad)
    rep["wall_s"] = time.time() - t0
    log(f"[fig1] mean ΔD_A={rep['mean']['dD_A']:.4f} ΔD_B={rep['mean']['dD_B']:.4f} "
        f"ratio={rep['obs1_dir_ratio_A_over_B']:.2f}  (paper: 1.7)")
    log(f"[fig1] mean ΔM_A={rep['mean']['dM_A']:.4f} ΔM_B={rep['mean']['dM_B']:.4f} "
        f"ratio={rep['obs2_mag_ratio_B_over_A']:.2f}  (paper: 41)")
    return rep


def main():
    rep = run()
    print("name,us_per_call,derived")
    print(f"fig1/sensitivity,{rep['wall_s']*1e6:.0f},"
          f"dirA_over_dirB={rep['obs1_dir_ratio_A_over_B']:.3f};"
          f"magB_over_magA={rep['obs2_mag_ratio_B_over_A']:.3f}")
    return rep


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
