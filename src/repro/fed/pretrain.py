"""Backbone pretraining for CPU-scale experiments.

The paper fine-tunes *pretrained* 7B checkpoints; offline we must make our
own backbone competence.  ``get_pretrained_base`` full-param-trains the
reduced model on the task-family mixture, then freezes it — the federated
PEFT experiments adapt on top, exactly mirroring the paper's setting.
Checkpoints are cached on disk keyed by (config, steps, seed).
"""
from __future__ import annotations

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_checkpoint, restore_checkpoint
from repro.data.synthetic import SyntheticInstructionDataset
from repro.models import model as M
from repro.models.config import ArchConfig
from repro.optim import adamw, chain_clip
from repro.optim.optimizers import apply_updates
from repro.utils.runtime import checkout_root

CACHE_DIR = os.environ.get("REPRO_CACHE", str(checkout_root() / ".cache"))


def _key(cfg: ArchConfig, steps: int, seed: int, family: str) -> str:
    blob = f"{cfg}|{steps}|{seed}|{family}".encode()
    return hashlib.blake2s(blob).hexdigest()[:16]


def pretrain_base(cfg: ArchConfig, dataset: SyntheticInstructionDataset,
                  steps: int = 600, batch: int = 32, seq_len: int = 48,
                  lr: float = 3e-3, seed: int = 0, log=lambda s: None):
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    opt = chain_clip(adamw(lr), 1.0)
    ost = opt.init(params)

    @jax.jit
    def step(params, ost, b, i):
        (_, met), g = jax.value_and_grad(
            lambda p: M.loss_and_metrics(p, b, cfg), has_aux=True)(params)
        upd, ost = opt.update(g, ost, params, i)
        return apply_updates(params, upd), ost, met

    rng = np.random.default_rng(seed)
    met = {}
    for i in range(steps):
        b = {k: jnp.asarray(v)
             for k, v in dataset.sample_batch(rng, batch, seq_len).items()}
        params, ost, met = step(params, ost, b, jnp.asarray(i))
        if i % 100 == 0:
            log(f"pretrain step {i}: ce={float(met['ce']):.3f} "
                f"acc={float(met['acc']):.3f}")
    log(f"pretrain done: acc={float(met['acc']):.3f}")
    return params


def get_pretrained_base(cfg: ArchConfig,
                        dataset: SyntheticInstructionDataset,
                        steps: int = 600, seed: int = 0,
                        log=lambda s: None):
    """Disk-cached pretrained backbone."""
    key = _key(cfg, steps, seed, dataset.family.name)
    path = os.path.join(CACHE_DIR, f"base_{cfg.name}_{key}.msgpack")
    template = M.init_params(jax.random.PRNGKey(seed), cfg)
    if os.path.exists(path):
        params, _ = restore_checkpoint(path, template)
        log(f"restored pretrained base from {path}")
        return params
    params = pretrain_base(cfg, dataset, steps=steps, seed=seed, log=log)
    save_checkpoint(path, params, step=steps)
    return params
