"""Unified multi-architecture transformer.

One engine covers the 10 assigned architectures via superblock patterns
(config.py).  Layer params are stacked (n_superblocks, ...) and the main
body is a single ``lax.scan``; an unrolled tail handles layer counts that
don't divide the pattern length.

Entry points:
  init_params(rng, cfg)                     → param pytree (no adapters)
  forward(params, batch, cfg, ...)          → (hidden, cache, aux)
  logits_from_hidden / loss_and_metrics     → chunked-CE training loss
  prefill(...) / decode_step(...)           → serving path with caches
  init_cache(cfg, batch, seq_len)           → per-layer cache pytree
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import ssm as S
from repro.models.config import ArchConfig, SubLayer
from repro.obs.tracing import scoped

Params = Any


def _dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_linear(rng, d_in, d_out, scale, dtype):
    return {"kernel": (jax.random.normal(rng, (d_in, d_out), jnp.float32)
                       * scale).astype(dtype)}


def _init_sublayer(rng, cfg: ArchConfig, sub: SubLayer, dtype):
    D, F = cfg.d_model, cfg.d_ff
    H, K, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 16)
    sc = 0.02
    out_sc = 0.02 / math.sqrt(max(2 * cfg.n_layers, 1))
    p: dict = {"input_norm": jnp.ones((D,), jnp.float32)}
    if sub.mixer in ("attn", "cross_attn"):
        p["attn"] = {
            "q_proj": _init_linear(ks[0], D, H * dh, sc, dtype),
            "k_proj": _init_linear(ks[1], D, K * dh, sc, dtype),
            "v_proj": _init_linear(ks[2], D, K * dh, sc, dtype),
            "o_proj": _init_linear(ks[3], H * dh, D, out_sc, dtype),
        }
        if cfg.qk_norm:
            p["attn"]["q_norm"] = jnp.ones((dh,), jnp.float32)
            p["attn"]["k_norm"] = jnp.ones((dh,), jnp.float32)
    elif sub.mixer == "ssm":
        Hs = D * cfg.ssm_expand // cfg.ssm_headdim
        d_inner = Hs * cfg.ssm_headdim
        GN = cfg.ssm_groups * cfg.ssm_state
        p["ssm"] = {
            "z_proj": _init_linear(ks[0], D, d_inner, sc, dtype),
            "x_proj": _init_linear(ks[1], D, d_inner, sc, dtype),
            "B_proj": _init_linear(ks[2], D, GN, sc, dtype),
            "C_proj": _init_linear(ks[3], D, GN, sc, dtype),
            "dt_proj": _init_linear(ks[4], D, Hs, sc, dtype),
            "conv_x": (jax.random.normal(ks[5], (d_inner, cfg.ssm_conv)) * 0.1).astype(dtype),
            "conv_B": (jax.random.normal(ks[6], (GN, cfg.ssm_conv)) * 0.1).astype(dtype),
            "conv_C": (jax.random.normal(ks[7], (GN, cfg.ssm_conv)) * 0.1).astype(dtype),
            "A_log": jnp.log(jnp.linspace(1.0, 16.0, Hs)).astype(jnp.float32),
            "D_skip": jnp.ones((Hs,), jnp.float32),
            "dt_bias": jnp.full((Hs,), -2.0, jnp.float32),
            "norm_w": jnp.ones((d_inner,), jnp.float32),
            "out_proj": _init_linear(ks[8], d_inner, D, out_sc, dtype),
        }
    if sub.ffn == "dense":
        p["ffn_norm"] = jnp.ones((D,), jnp.float32)
        p["mlp"] = {
            "gate_proj": _init_linear(ks[9], D, F, sc, dtype),
            "up_proj": _init_linear(ks[10], D, F, sc, dtype),
            "down_proj": _init_linear(ks[11], F, D, out_sc, dtype),
        }
    elif sub.ffn == "moe":
        E_slots = cfg.n_experts * cfg.ep_fsplit
        F_eff = F // cfg.ep_fsplit
        p["ffn_norm"] = jnp.ones((D,), jnp.float32)
        p["moe"] = {
            "router": {"kernel": (jax.random.normal(ks[12], (D, cfg.n_experts))
                                  * sc).astype(jnp.float32)},
            "experts": {
                "gate": (jax.random.normal(ks[13], (E_slots, D, F_eff)) * sc).astype(dtype),
                "up": (jax.random.normal(ks[14], (E_slots, D, F_eff)) * sc).astype(dtype),
                "down": (jax.random.normal(ks[15], (E_slots, F_eff, D)) * out_sc).astype(dtype),
            },
        }
    return p


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _init_block_stack(rng, cfg, pattern, n_sb, tail, dtype):
    """Returns (stacked_blocks, tail_blocks)."""
    def one_superblock(r):
        rs = jax.random.split(r, len(pattern))
        return {f"sub{i}": _init_sublayer(rs[i], cfg, sub, dtype)
                for i, sub in enumerate(pattern)}

    rngs = jax.random.split(rng, n_sb + 1)
    blocks = _stack([one_superblock(rngs[i]) for i in range(n_sb)]) if n_sb else {}
    tail_blocks = {}
    if tail:
        rs = jax.random.split(rngs[-1], tail)
        tail_blocks = {f"sub{i}": _init_sublayer(rs[i], cfg, pattern[i], dtype)
                       for i in range(tail)}
    return blocks, tail_blocks


def init_params(rng, cfg: ArchConfig) -> Params:
    dtype = _dtype(cfg)
    k_embed, k_blocks, k_enc, k_head = jax.random.split(rng, 4)
    n_sb, tail, pattern = cfg.blocks_layout()
    if cfg.n_enc_layers:
        pattern = cfg.dec_pattern()
        n_sb, tail = cfg.n_layers, 0
    params: dict = {
        "embed": {"embedding": (jax.random.normal(
            k_embed, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dtype)},
        "final_norm": jnp.ones((cfg.d_model,), jnp.float32),
    }
    blocks, tail_blocks = _init_block_stack(k_blocks, cfg, pattern, n_sb,
                                            tail, dtype)
    params["blocks"] = blocks
    if tail_blocks:
        params["tail"] = tail_blocks
    if cfg.n_enc_layers:
        enc_pat = [SubLayer("attn", "dense", "global")]
        enc_blocks, _ = _init_block_stack(k_enc, cfg, enc_pat,
                                          cfg.n_enc_layers, 0, dtype)
        params["encoder"] = {"blocks": enc_blocks,
                             "final_norm": jnp.ones((cfg.d_model,), jnp.float32)}
    if not cfg.tie_embeddings:
        params["lm_head"] = _init_linear(k_head, cfg.d_model, cfg.vocab_size,
                                         0.02, dtype)
    return params


# ---------------------------------------------------------------------------
# sublayer application
# ---------------------------------------------------------------------------

def _apply_sublayer(p, x, sub: SubLayer, cfg, *, positions, cache=None,
                    cache_index=None, enc_out=None, lora_scale=0.0,
                    dropout_rng=None, mesh=None, causal=True,
                    return_cache=False, cache_len=0, adapter_idx=None):
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    h = L.rms_norm(x, p["input_norm"], cfg.norm_eps)
    if sub.mixer in ("attn", "cross_attn"):
        kv_src = enc_out if sub.mixer == "cross_attn" else None
        acache = cache.get("attn") if cache else None
        y, nc = L.attention(
            p["attn"], h, positions, cfg, kind=sub.attn_kind,
            causal=causal and sub.mixer != "cross_attn",
            cache=acache, cache_index=cache_index, kv_source=kv_src,
            lora_scale=lora_scale, dropout_rng=dropout_rng,
            return_cache=return_cache, cache_len=cache_len,
            adapter_idx=adapter_idx)
        if nc is not None:
            new_cache["attn"] = nc
        x = x + y
    elif sub.mixer == "ssm":
        scache = cache.get("ssm") if cache else None
        y, nc = S.mamba2_mixer(p["ssm"], h, cfg, cache=scache,
                               cache_index=cache_index,
                               lora_scale=lora_scale, dropout_rng=dropout_rng,
                               return_cache=return_cache)
        if nc is not None:
            new_cache["ssm"] = nc
        x = x + y
    if sub.ffn == "dense":
        h = L.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        x = x + L.dense_ffn(p["mlp"], h, cfg, lora_scale,
                            adapter_idx=adapter_idx)
    elif sub.ffn == "moe":
        h = L.rms_norm(x, p["ffn_norm"], cfg.norm_eps)
        if isinstance(mesh, tuple) and mesh[0] == "manual":
            # inside a manual region over the data axes (launch/train.py)
            y, a = L.moe_ffn_manual(p["moe"], h, cfg, mesh[1])
        elif mesh is not None and mesh.devices.size > 1:
            y, a = L.moe_ffn_ep(p["moe"], h, cfg, mesh)
        else:
            y, a = L.moe_ffn_local(p["moe"], h, cfg)
        aux = aux + a
        x = x + y
    return x, new_cache, aux


def _superblock_fn(pattern, cfg, *, causal=True, mesh=None, remat=False,
                   return_cache=False, cache_len=0, adapter_idx=None):
    """Returns body(x, p_sb, cache_sb, positions, cache_index, enc_out, rng)."""

    def body(x, p_sb, cache_sb, positions, cache_index, enc_out, rng):
        aux = jnp.zeros((), jnp.float32)
        new_cache = {}
        scale = cfg.lora_alpha / cfg.lora_rank
        for i, sub in enumerate(pattern):
            key = f"sub{i}"
            if key not in p_sb:      # tail shorter than pattern
                continue
            r = None if rng is None else jax.random.fold_in(rng, i)
            c = cache_sb.get(key) if cache_sb else None
            x, nc, a = _apply_sublayer(
                p_sb[key], x, sub, cfg, positions=positions, cache=c,
                cache_index=cache_index, enc_out=enc_out,
                lora_scale=scale, dropout_rng=r, mesh=mesh, causal=causal,
                return_cache=return_cache, cache_len=cache_len,
                adapter_idx=adapter_idx)
            if nc:
                new_cache[key] = nc
            aux = aux + a
        return x, new_cache, aux

    if remat == "dots":
        # save matmul outputs; recompute only cheap elementwise ops in the
        # backward pass (≈2× fwd FLOPs instead of 3×, at higher residency)
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_saveable)
    elif remat:
        body = jax.checkpoint(body)
    return body


# ---------------------------------------------------------------------------
# backbone forward
# ---------------------------------------------------------------------------

def _run_blocks(blocks, tail, x, pattern, cfg, *, positions, cache=None,
                cache_index=None, enc_out=None, rng=None, mesh=None,
                causal=True, remat=False, return_cache=False,
                cache_len=0, adapter_idx=None):
    """Scan over stacked superblocks, then unrolled tail."""
    body = _superblock_fn(pattern, cfg, causal=causal, mesh=mesh,
                          remat=remat, return_cache=return_cache,
                          cache_len=cache_len,
                          adapter_idx=adapter_idx)
    n_sb = 0
    if blocks:
        some_leaf = jax.tree.leaves(blocks)[0]
        n_sb = some_leaf.shape[0]

    new_cache = {"blocks": None, "tail": {}}
    aux_total = jnp.zeros((), jnp.float32)

    if n_sb:
        rngs = None if rng is None else jax.random.split(rng, n_sb)

        def scan_body(carry, xs):
            x, aux = carry
            p_sb, cache_sb, r = xs
            x, nc, a = body(x, p_sb, cache_sb, positions, cache_index,
                            enc_out, r)
            return (x, aux + a), nc

        xs = (blocks,
              cache["blocks"] if cache is not None else None,
              rngs)
        # lax.scan needs every xs leaf to have the leading n_sb dim; None
        # subtrees are fine (empty pytrees).
        (x, aux_total), cache_out = jax.lax.scan(
            scan_body, (x, aux_total), xs)
        new_cache["blocks"] = cache_out

    if tail:
        r = None if rng is None else jax.random.fold_in(rng, 999)
        x, nc, a = body(x, tail,
                        cache["tail"] if cache is not None else None,
                        positions, cache_index, enc_out, r)
        new_cache["tail"] = nc
        aux_total = aux_total + a
    return x, new_cache, aux_total


def _embed(params, tokens, cfg, frontend_emb=None):
    emb = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    if cfg.frontend and frontend_emb is not None:
        emb = jnp.concatenate([frontend_emb.astype(emb.dtype), emb], axis=1)
    return emb


def forward(params, batch, cfg: ArchConfig, *, rng=None, mesh=None,
            remat=False, causal=True, return_cache=False, cache_len=0):
    """Training/prefill forward → (hidden (B,S,D), cache, aux)."""
    tokens = batch["tokens"]
    frontend_emb = None if cfg.n_enc_layers else batch.get("frontend_emb")
    x = _embed(params, tokens, cfg, frontend_emb)
    B, Stot = x.shape[0], x.shape[1]

    if "prompt_embed" in params:                      # prompt-tuning baseline
        n_p = params["prompt_embed"].shape[0]
        pe = jnp.broadcast_to(params["prompt_embed"][None].astype(x.dtype),
                              (B, n_p, x.shape[-1]))
        x = jnp.concatenate([pe, x], axis=1)
        Stot = Stot + n_p

    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(Stot)[None], (B, Stot))

    enc_out = None
    if cfg.n_enc_layers:
        enc_tokens_emb = batch["frontend_emb"]        # audio frames → encoder
        enc_pat = [SubLayer("attn", "dense", "global")]
        e_pos = jnp.broadcast_to(
            jnp.arange(enc_tokens_emb.shape[1])[None],
            enc_tokens_emb.shape[:2])
        # fold the dropout rng onto a branch of its own: sharing `rng`
        # between the encoder and decoder stacks gives layer i of both
        # the same fold_in(rng, i) key → identical dropout masks (R3)
        enc_rng = None if rng is None else jax.random.fold_in(rng, 998)
        enc_out, _, _ = _run_blocks(
            params["encoder"]["blocks"], {}, enc_tokens_emb.astype(x.dtype),
            enc_pat, cfg, positions=e_pos, rng=enc_rng, mesh=mesh,
            causal=False, remat=remat)
        enc_out = L.rms_norm(enc_out, params["encoder"]["final_norm"],
                             cfg.norm_eps)

    n_sb, tail, pattern = cfg.blocks_layout()
    if cfg.n_enc_layers:
        pattern = cfg.dec_pattern()
        n_sb, tail = cfg.n_layers, 0

    x, cache, aux = _run_blocks(
        params["blocks"], params.get("tail", {}), x, pattern, cfg,
        positions=positions, enc_out=enc_out, rng=rng, mesh=mesh,
        causal=causal, remat=remat, return_cache=return_cache,
        cache_len=cache_len, adapter_idx=batch.get("adapter_idx"))

    if "prompt_embed" in params:
        x = x[:, params["prompt_embed"].shape[0]:]
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, cache, aux


# ---------------------------------------------------------------------------
# loss (chunked cross-entropy — unrolled chunks so the dry-run sees the
# full lm_head FLOPs; memory per chunk = B·Sc·V/n_chunks)
# ---------------------------------------------------------------------------

def _head_kernel(params, cfg):
    if cfg.tie_embeddings or "lm_head" not in params:
        return params["embed"]["embedding"].T
    return params["lm_head"]["kernel"]


def loss_and_metrics(params, batch, cfg, *, rng=None, mesh=None,
                     remat=False, n_loss_chunks: int = 0, aux_weight=0.01):
    hidden, _, aux = forward(params, batch, cfg, rng=rng, mesh=mesh,
                             remat=remat)
    tokens, mask = batch["tokens"], batch["loss_mask"]
    if cfg.frontend and not cfg.n_enc_layers and "frontend_emb" in batch:
        hidden = hidden[:, batch["frontend_emb"].shape[1]:]
    B, Stot, D = hidden.shape
    targets = tokens[:, 1:]
    h = hidden[:, :-1]
    m = mask[:, :-1]
    Sl = Stot - 1
    kern = _head_kernel(params, cfg)
    V = kern.shape[-1]
    if n_loss_chunks <= 0:
        n_loss_chunks = max(1, min(32, (B * Sl * V) // (1 << 26)))
    while Sl % n_loss_chunks:
        n_loss_chunks -= 1
    Sc = Sl // n_loss_chunks

    # CE over vocab in seq chunks via lax.scan with a rematerialized body:
    # scan serializes the per-chunk backward (an unrolled loop lets XLA keep
    # every chunk's (B,Sc,V) softmax grads alive at once — measured 17 GB on
    # gemma3 train_4k), and remat keeps only the (B,Sc,D) chunk inputs as
    # residuals, recomputing logits in the backward sweep.
    @jax.checkpoint
    @scoped("ce")
    def _ce_chunk(kern, hb, tb, mb):
        logits = hb @ kern.astype(hb.dtype)
        lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
        tgt = jnp.take_along_axis(
            logits, tb[..., None], axis=-1)[..., 0].astype(jnp.float32)
        loss = jnp.sum((lse - tgt) * mb)
        pred = jnp.argmax(logits, axis=-1)
        # accuracy counts only full-weight (answer) positions; fractional
        # mask weights are auxiliary LM signal
        amb = (mb >= 0.999).astype(jnp.float32)
        correct = jnp.sum((pred == tb) * amb)
        return loss, correct, jnp.sum(amb)

    hc = h.reshape(B, n_loss_chunks, Sc, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, n_loss_chunks, Sc).transpose(1, 0, 2)
    mc = m.reshape(B, n_loss_chunks, Sc).transpose(1, 0, 2)

    def _ce_scan(carry, xs):
        hb, tb, mb = xs
        l_c, a_c, n_c = _ce_chunk(kern, hb, tb, mb)
        return (carry[0] + l_c, carry[1] + a_c, carry[2] + n_c), None

    (tot_loss, tot_correct, tot_ans), _ = jax.lax.scan(
        _ce_scan, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
                   jnp.zeros((), jnp.float32)),
        (hc, tc, mc))

    denom = jnp.maximum(jnp.sum(m), 1.0)
    loss = tot_loss / denom + aux_weight * aux
    return loss, {"ce": tot_loss / denom,
                  "acc": tot_correct / jnp.maximum(tot_ans, 1.0),
                  "aux": aux, "n_tok": denom}


# ---------------------------------------------------------------------------
# serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, seq_len: int):
    dtype = _dtype(cfg)
    n_sb, tail, pattern = cfg.blocks_layout()
    if cfg.n_enc_layers:
        pattern = cfg.dec_pattern()
        n_sb, tail = cfg.n_layers, 0

    def one(sub: SubLayer):
        if sub.mixer == "attn":
            return {"attn": L.init_attn_cache(cfg, batch, seq_len,
                                              sub.attn_kind, dtype)}
        if sub.mixer == "ssm":
            return {"ssm": S.init_ssm_cache(cfg, batch, dtype)}
        return {}

    def stack_n(tree, n):
        return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (n,) + x.shape),
                            tree)

    blocks = {}
    if n_sb:
        per_sb = {f"sub{i}": one(s) for i, s in enumerate(pattern)}
        per_sb = {k: v for k, v in per_sb.items() if v}
        blocks = stack_n(per_sb, n_sb)
    tail_c = {f"sub{i}": one(pattern[i]) for i in range(tail)}
    tail_c = {k: v for k, v in tail_c.items() if v}
    return {"blocks": blocks, "tail": tail_c}


def decode_step(params, new_token, cache, cache_index, cfg: ArchConfig, *,
                mesh=None, enc_out=None, adapter_idx=None):
    """One-token decode.  new_token: (B,) int32; cache_index: () int32
    shared position or (B,) int32 per-row positions (mixed batching).
    adapter_idx: optional (B,) pool slots for batched-LoRA serving.
    Returns (logits (B,V), new_cache)."""
    x = jnp.take(params["embed"]["embedding"], new_token[:, None], axis=0)
    B = x.shape[0]
    if jnp.ndim(cache_index) == 1:
        positions = cache_index[:, None].astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(cache_index[None, None],
                                     (B, 1)).astype(jnp.int32)

    n_sb, tail, pattern = cfg.blocks_layout()
    if cfg.n_enc_layers:
        pattern = cfg.dec_pattern()
        n_sb, tail = cfg.n_layers, 0

    x, new_cache, _ = _run_blocks(
        params["blocks"], params.get("tail", {}), x, pattern, cfg,
        positions=positions, cache=cache, cache_index=cache_index,
        enc_out=enc_out, mesh=mesh, adapter_idx=adapter_idx)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = (x[:, 0] @ _head_kernel(params, cfg).astype(x.dtype)).astype(jnp.float32)
    return logits, new_cache


def prefill(params, batch, cfg: ArchConfig, *, mesh=None, cache_len=0):
    """Process a prompt, returning (last_logits, cache).  cache_len pads
    full-attention caches with headroom for subsequent decode steps."""
    hidden, cache, _ = forward(params, batch, cfg, mesh=mesh,
                               return_cache=True, cache_len=cache_len)
    logits = (hidden[:, -1] @ _head_kernel(params, cfg).astype(hidden.dtype)
              ).astype(jnp.float32)
    return logits, cache
