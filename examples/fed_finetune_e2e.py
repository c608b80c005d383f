"""End-to-end federated fine-tuning driver.

    PYTHONPATH=src python examples/fed_finetune_e2e.py [--profile 25m|100m]
        [--rounds 8] [--pretrain-steps 300]

Full path: backbone pretraining → heterogeneous client split (one task
per client, like the paper) → FedLoRA-Optimizer rounds (stage-1 local,
Eqs. 5-8 aggregation, stage-2 global ΔA_D) → stage-3 ΔB_M
personalization → eval table + checkpoint.

The 100m profile is the deliverable-scale run (~95 M params — budget a
few hours on this 1-core container); 25m is the default demonstrator.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.checkpoint import save_checkpoint  # noqa: E402
from repro.core.fedlora import run_federated  # noqa: E402
from repro.data.loader import eval_batches  # noqa: E402
from repro.data.synthetic import (SyntheticInstructionDataset,  # noqa: E402
                                  make_dataset_family)
from repro.fed.pretrain import get_pretrained_base  # noqa: E402
from repro.fed.simulate import FedHyper  # noqa: E402
from repro.models.config import ArchConfig  # noqa: E402

PROFILES = {
    "25m": ArchConfig(name="e2e-25m", family="dense", n_layers=6,
                      d_model=384, n_heads=6, n_kv_heads=2, d_ff=1536,
                      vocab_size=2048, dtype="float32", lora_rank=8,
                      lora_dropout=0.0),
    "100m": ArchConfig(name="e2e-100m", family="dense", n_layers=12,
                       d_model=768, n_heads=12, n_kv_heads=4, d_ff=3072,
                       vocab_size=8192, dtype="float32", lora_rank=8,
                       lora_dropout=0.0),
}
TASKS = ("causal", "qa", "ie")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", default="25m", choices=PROFILES)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--pretrain-steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = PROFILES[args.profile]
    from repro.utils.pytree import tree_count_params
    fam = make_dataset_family("dolly", vocab_size=cfg.vocab_size)
    mix = SyntheticInstructionDataset(fam, [1 / 3, 1 / 3, 1 / 3, 0],
                                      client_seed=0)
    t0 = time.time()
    base = get_pretrained_base(cfg, mix, steps=args.pretrain_steps, log=print)
    print(f"backbone: {tree_count_params(base)/1e6:.1f} M params "
          f"(pretrain {time.time()-t0:.0f}s)")

    from repro.data.synthetic import TASK_TYPES
    cds = [SyntheticInstructionDataset(
        fam, [1.0 if t == TASKS[c] else 0.0 for t in TASK_TYPES],
        client_seed=0) for c in range(3)]
    eg = eval_batches(mix, 32, args.seq, 4)
    rng = np.random.default_rng(1)
    el = []
    for _ in range(3):
        outs = [d.sample_batch(rng, 32, args.seq) for d in cds]
        el.append({k: jnp.asarray(np.stack([o[k] for o in outs]))
                   for k in outs[0]})

    hp = FedHyper(method="fedlora_opt", n_clients=3, rounds=args.rounds,
                  local_steps=5, batch=8, seq_len=args.seq, lr=2e-3,
                  server_lr=5e-4, global_steps=3, personal_steps=20,
                  lam=1e-3)
    res = run_federated(cfg, hp, cds, mix, eg, el, base=base, log=print)
    print("\n=== results ===")
    print(f"global model acc : {res.global_acc:.3f}")
    print(f"personalized acc : {res.local_acc:.3f}")
    for c, a in enumerate(res.per_client):
        print(f"  client {c} ({TASKS[c]}): {a:.3f}")
    print(f"adapter comm     : {res.comm_bytes/1e6:.2f} MB "
          f"over {args.rounds} rounds")
    save_checkpoint(f"experiments/e2e_{args.profile}.msgpack",
                    {"history": jnp.asarray([h['acc'] for h in res.history])})
    print("history checkpoint → experiments/")


if __name__ == "__main__":
    from repro.utils.runtime import enable_compile_cache
    enable_compile_cache()
    main()
