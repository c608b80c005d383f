"""Core layers for the multi-arch transformer zoo.

Everything is a pure function over nested-dict params.  Linear layers
understand adapter params living alongside their kernel:

  {kernel}                                  — plain frozen projection
  {kernel_q, kernel_scale}                  — weight-only quantized frozen
                                              projection (int8 / packed
                                              int4 + per-group f32 scales;
                                              see kernels/quant_matmul) —
                                              adapters ride alongside in
                                              full precision
  {kernel, lora_A, lora_B}                  — raw LoRA (baseline)
  {kernel, A_dir, A_mag, B_dir, B_mag,
   dA_dir, dB_mag}                          — DoRA-decomposed LoRA
                                              (the paper's representation;
                                              dA_dir is the global-stage
                                              delta, dB_mag the local-stage
                                              delta)

Kernels use (d_in, d_out) layout; per-column magnitude in the DoRA sense
is the norm over the *output* axis for each input feature — A_mag:(d_in,),
B_mag:(r,).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = Any


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def head_rms_norm(x, w, eps: float = 1e-6):
    """qk-norm: normalize over the head dim (..., dh)."""
    return rms_norm(x, w, eps)


# ---------------------------------------------------------------------------
# rotary embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------

def _rope_freqs(dh: int, theta: float):
    return theta ** (-jnp.arange(0, dh // 2, dtype=jnp.float32) / (dh // 2))


def apply_rope(x, positions, theta: float = 1e4):
    """x: (B, S, H, dh); positions: (B, S) int32."""
    dh = x.shape[-1]
    freqs = _rope_freqs(dh, theta)                       # (dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,dh/2)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta: float = 1e4,
                sections=(0.25, 0.375, 0.375)):
    """Qwen2-VL multimodal rotary: positions3 (B, S, 3) = (t, h, w) ids.

    The dh/2 frequency bands are split into three sections, each rotated by
    its own position component.  For text-only inputs all three components
    are equal and this degrades exactly to standard RoPE.
    """
    dh = x.shape[-1]
    half = dh // 2
    freqs = _rope_freqs(dh, theta)
    n0 = int(half * sections[0])
    n1 = int(half * sections[1])
    sel = jnp.concatenate([
        jnp.zeros((n0,), jnp.int32),
        jnp.ones((n1,), jnp.int32),
        jnp.full((half - n0 - n1,), 2, jnp.int32),
    ])                                                    # (dh/2,)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),                   # (B,S,3)
        jnp.broadcast_to(sel, positions3.shape[:2] + (half,)).astype(jnp.int32) * 0
        + sel[None, None, :], axis=-1)                    # (B,S,dh/2)
    ang = pos * freqs
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# adapter-aware linear
# ---------------------------------------------------------------------------

def lora_delta(p: Params, x, scale: float, dropout_rng=None,
               dropout: float = 0.0):
    """Low-rank adapter contribution for input x (..., d_in)."""
    if dropout_rng is not None and dropout > 0.0:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout, x.shape)
        x = jnp.where(keep, x / (1.0 - dropout), 0.0).astype(x.dtype)
    if "lora_A" in p:                                    # raw LoRA
        h = x @ p["lora_A"].astype(x.dtype)
        y = (h @ p["lora_B"].astype(x.dtype)) * scale
        if "local_A" in p:                               # FedALT dual pair
            hl = x @ p["local_A"].astype(x.dtype)
            y = y + (hl @ p["local_B"].astype(x.dtype)) * scale
        return y
    # DoRA-decomposed LoRA (the paper's form):
    #   A = (A_dir + dA_dir) * A_mag[:, None]
    #   B = B_dir * (B_mag + dB_mag)[:, None]
    a_dir = p["A_dir"] + p.get("dA_dir", 0.0)
    h = (x * p["A_mag"].astype(x.dtype)) @ a_dir.astype(x.dtype)
    b_mag = p["B_mag"] + p.get("dB_mag", 0.0)
    return ((h * b_mag.astype(x.dtype)) @ p["B_dir"].astype(x.dtype)) * scale


def lora_delta_batched(p: Params, x, adapter_idx, scale: float):
    """Mixed-tenant adapter contribution: row i of x (B, ..., d_in) uses
    the adapter in pool slot adapter_idx[i] (BGMV — see
    kernels/batched_lora and serve/adapter_store).  Pooled leaves:

      {pool_A, pool_B}                        — per-slot LoRA pairs
      {bgmv_A_dir, bgmv_A_mag, bgmv_B_mag,
       bgmv_B_dir, pool_dB_mag}               — decomposed-DoRA: shared
                                                direction/magnitude
                                                factors, per-slot RAW
                                                ΔB_M deltas (the paper's
                                                deployment shape; the
                                                kernel forms
                                                B_mag + ΔB_M itself)

    An optional {pool_ranks} leaf ((L,) int32) marks a heterogeneous
    pool: slots are padded to r_max and the kernel masks each row's
    intermediate at its slot's own rank — on the magnitude layout that
    mask covers the shared B_mag rows too, so each tenant gets its own
    rank-slice of the shared model and a rank-0 slot gets none of it.
    """
    from repro.kernels import bgmv, bgmv_mag
    ranks = p.get("pool_ranks")
    if "pool_A" in p:
        return bgmv(x, p["pool_A"], p["pool_B"], adapter_idx, scale=scale,
                    ranks=ranks)
    return bgmv_mag(x, p["bgmv_A_dir"], p["bgmv_A_mag"], p["bgmv_B_mag"],
                    p["pool_dB_mag"], p["bgmv_B_dir"], adapter_idx,
                    scale=scale, ranks=ranks)


def _has_pooled(p: Params) -> bool:
    return "pool_A" in p or "pool_dB_mag" in p


def linear(p: Params, x, *, lora_scale: float = 0.0, dropout_rng=None,
           dropout: float = 0.0, fused: bool = False, adapter_idx=None):
    if (fused and "A_dir" in p and lora_scale
            and (adapter_idx is None or not _has_pooled(p))
            and (dropout_rng is None or dropout == 0.0)
            and "bias" not in p and "kernel" in p
            and p["kernel"].ndim == 2):
        # (pooled per-row routing outranks the fused single-adapter path:
        # taking the fused branch here would silently serve every tenant
        # the shared adapter)
        # fused base+adapter matmul (Pallas on TPU, jnp oracle elsewhere).
        # Forward/serving only: pallas_call has no VJP here, so training
        # paths keep fused=False.
        from repro.kernels import fused_dora
        return fused_dora(x, p["kernel"], p["A_dir"], p["A_mag"],
                          p["B_dir"], p["B_mag"], p.get("dA_dir"),
                          p.get("dB_mag"), scale=lora_scale)
    if "kernel_q" in p:
        # quantized frozen backbone: dequant-fused matmul (Pallas on TPU,
        # XLA oracle elsewhere); all adapter deltas below stay f32 on top
        from repro.kernels import quant_matmul
        y = quant_matmul(x, p["kernel_q"], p["kernel_scale"])
    else:
        y = x @ p["kernel"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    if adapter_idx is not None and lora_scale and _has_pooled(p):
        y = y + lora_delta_batched(p, x, adapter_idx, lora_scale)
    elif ("lora_A" in p or "A_dir" in p) and lora_scale:
        y = y + lora_delta(p, x, lora_scale, dropout_rng, dropout)
    return y


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _causal_window_mask(S_q, S_k, q_offset, window: Optional[int],
                        causal: bool):
    """(S_q, S_k) boolean mask; q position i attends k position j."""
    qi = jnp.arange(S_q)[:, None] + q_offset
    kj = jnp.arange(S_k)[None, :]
    m = jnp.ones((S_q, S_k), bool)
    if causal:
        m &= kj <= qi
    if window is not None:
        m &= kj > qi - window
    return m


def _sdpa(q, k, v, mask, softmax_scale):
    """q:(B,Sq,H,dh) k,v:(B,Sk,K,dh) GQA; mask (..., Sq,Sk) or None.

    Grouped-head einsums instead of jnp.repeat (a repeated 32k KV cache
    materializes H/K× the cache bytes), and bf16 operands with f32
    accumulation instead of .astype(f32) casts (XLA hoists a full-cache
    f32 copy out of the layer scan otherwise — measured 8.6 GB on
    qwen3-32b decode)."""
    B, Sq, H, dh = q.shape
    K = k.shape[2]
    rep = H // K
    qg = q.reshape(B, Sq, K, rep, dh)
    scores = jnp.einsum("bqkrd,bskd->bkrqs", qg, k,
                        preferred_element_type=jnp.float32) * softmax_scale
    if mask is not None:
        m = mask
        if m.ndim == 4:                       # (B?,1,Sq,Sk) → (B?,1,1,Sq,Sk)
            m = m[:, :, None]
        scores = jnp.where(m, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkrqs,bskd->bqkrd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, H, dh).astype(q.dtype)


def _sdpa_chunked(q, k, v, softmax_scale, window, causal, q_block: int = 512):
    """Flash-style online-softmax over query blocks in pure JAX (lax.scan)
    — bounds activation memory for 32k-token prefill in the dry-run the
    same way the Pallas kernel does on TPU."""
    B, Sq, H, dh = q.shape
    Sk = k.shape[1]
    K = k.shape[2]
    rep = H // K
    nb = Sq // q_block
    qb = q.reshape(B, nb, q_block, K, rep, dh).transpose(1, 0, 2, 3, 4, 5)

    @jax.checkpoint
    def _block(qi, idx):
        # remat: the (bq × Sk) score/weight tensors are recomputed in the
        # backward pass — without this every q-block's softmax weights stay
        # live as scan residuals (measured ~2 GB/layer on 4k×1152 trains).
        # Grouped-head bf16 einsums w/ f32 accumulation (see _sdpa).
        scores = jnp.einsum("bqkrd,bskd->bkrqs", qi, k,
                            preferred_element_type=jnp.float32)
        scores = scores * softmax_scale
        mask = _causal_window_mask(q_block, Sk, idx * q_block, window, causal)
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkrqs,bskd->bqkrd", w.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, q_block, H, dh).astype(q.dtype)

    def body(_, qi_and_idx):
        qi, idx = qi_and_idx
        return None, _block(qi, idx)

    _, outs = jax.lax.scan(body, None, (qb, jnp.arange(nb)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, dh)


def attention(p: Params, x, positions, cfg, *, kind: str = "global",
              causal: bool = True, cache=None, cache_index=None,
              kv_source=None, lora_scale: float = 0.0, dropout_rng=None,
              chunk_q: bool = False, return_cache: bool = False,
              cache_len: int = 0, adapter_idx=None):
    """Full attention sublayer (pre-norm outside).  Returns (y, new_cache).

    cache: dict(k=(B,Sc,K,dh), v=...) — decode ring/linear buffer.
    cache_index: () int32 shared write position, or (B,) int32 per-row
    positions (mixed-tenant serving: rows admitted at different times).
    kv_source: encoder output for cross-attention (keys/values from there).
    adapter_idx: (B,) int32 pool-slot per row for batched-LoRA serving.
    """
    B, S, D = x.shape
    H, Kh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    window = cfg.sliding_window if kind == "local" else None
    scale = 1.0 / math.sqrt(dh)

    # one dropout key per projection: sharing dropout_rng across q/k/v
    # makes the adapter-dropout masks identical (q and k/v see the same
    # input tensor in self-attention) — lint rule R3
    if dropout_rng is None:
        q_rng = k_rng = v_rng = None
    else:
        q_rng, k_rng, v_rng = jax.random.split(dropout_rng, 3)
    q = linear(p["q_proj"], x, lora_scale=lora_scale if "q_proj" in cfg.lora_targets else 0.0,
               dropout_rng=q_rng, dropout=cfg.lora_dropout,
               fused=cfg.use_fused_dora, adapter_idx=adapter_idx)
    kv_in = x if kv_source is None else kv_source
    k = linear(p["k_proj"], kv_in, lora_scale=lora_scale if "k_proj" in cfg.lora_targets else 0.0,
               dropout_rng=k_rng, dropout=cfg.lora_dropout,
               fused=cfg.use_fused_dora, adapter_idx=adapter_idx)
    v = linear(p["v_proj"], kv_in, lora_scale=lora_scale if "v_proj" in cfg.lora_targets else 0.0,
               dropout_rng=v_rng, dropout=cfg.lora_dropout,
               fused=cfg.use_fused_dora, adapter_idx=adapter_idx)
    Skv = kv_in.shape[1]
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, Skv, Kh, dh)
    v = v.reshape(B, Skv, Kh, dh)

    if "q_norm" in p:                                      # qwen3 qk-norm
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"], cfg.norm_eps)

    if kv_source is None:                                  # self-attn: rope
        if cfg.mrope:
            pos3 = positions if positions.ndim == 3 else jnp.repeat(
                positions[..., None], 3, axis=-1)
            q = apply_mrope(q, pos3, cfg.rope_theta)
            k = apply_mrope(k, pos3, cfg.rope_theta)
        else:
            pos = positions if positions.ndim == 2 else positions[..., 0]
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)

    new_cache = None
    if cache is not None and kv_source is None:
        # decode: write the new token's k/v into the buffer.
        Sc = cache["k"].shape[1]
        if window is not None and Sc == window:
            slot = cache_index % window                    # ring buffer
        else:
            slot = cache_index
        if jnp.ndim(cache_index) == 1:
            # per-row write positions (continuous batching: each row is
            # at its own sequence offset) — scatter one slot per row.
            rows = jnp.arange(B)
            ck = cache["k"].at[rows, slot].set(k[:, 0])
            cv = cache["v"].at[rows, slot].set(v[:, 0])
            valid = (jnp.arange(Sc)[None, :]
                     < jnp.minimum(cache_index + 1, Sc)[:, None])
            mask = valid[:, None, None, :]                 # (B,1,1,Sc)
        else:
            ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
            valid = jnp.arange(Sc) < jnp.minimum(cache_index + 1, Sc)
            mask = valid[None, None, None, :]              # (1,1,1,Sc)
        new_cache = {"k": ck, "v": cv}
        out = _sdpa(q, ck, cv, mask, scale)
    elif cache is not None and kv_source is not None:
        # cross-attention during decode: kv from the (static) encoder output.
        out = _sdpa(q, k, v, None, scale)
        new_cache = cache
    else:
        if chunk_q and S >= 2048 and S % 512 == 0:
            out = _sdpa_chunked(q, k, v, scale, window, causal)
        else:
            mask = None
            if causal or window is not None:
                mask = _causal_window_mask(S, Skv, 0, window, causal)[None, None]
            out = _sdpa(q, k, v, mask, scale)
        if return_cache and kv_source is None:
            if window is not None:
                if S > window:
                    # keep last `window` kv, rotated so pos p sits at slot
                    # p % window (ring layout the decode path expects)
                    kk = jnp.roll(k[:, -window:], S % window, axis=1)
                    vv = jnp.roll(v[:, -window:], S % window, axis=1)
                else:                       # pad up to the ring size
                    pad = [(0, 0), (0, window - S), (0, 0), (0, 0)]
                    kk, vv = jnp.pad(k, pad), jnp.pad(v, pad)
            else:
                tgt = max(cache_len, S)
                pad = [(0, 0), (0, tgt - S), (0, 0), (0, 0)]
                kk, vv = jnp.pad(k, pad), jnp.pad(v, pad)
            new_cache = {"k": kk, "v": vv}

    y = linear(p["o_proj"], out.reshape(B, S, H * dh),
               lora_scale=lora_scale if "o_proj" in cfg.lora_targets else 0.0,
               fused=cfg.use_fused_dora, adapter_idx=adapter_idx)
    return y, new_cache


def init_attn_cache(cfg, batch: int, seq_len: int, kind: str, dtype):
    window = cfg.sliding_window if kind == "local" else None
    Sc = min(seq_len, window) if window is not None else seq_len
    shape = (batch, Sc, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


# ---------------------------------------------------------------------------
# dense FFN (SwiGLU)
# ---------------------------------------------------------------------------

def dense_ffn(p: Params, x, cfg, lora_scale: float = 0.0, adapter_idx=None):
    g = linear(p["gate_proj"], x,
               lora_scale=lora_scale if "gate_proj" in cfg.lora_targets else 0.0,
               fused=cfg.use_fused_dora, adapter_idx=adapter_idx)
    u = linear(p["up_proj"], x,
               lora_scale=lora_scale if "up_proj" in cfg.lora_targets else 0.0,
               fused=cfg.use_fused_dora, adapter_idx=adapter_idx)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = linear(p["down_proj"], h,
               lora_scale=lora_scale if "down_proj" in cfg.lora_targets else 0.0,
               fused=cfg.use_fused_dora, adapter_idx=adapter_idx)
    if "adapter_down" in p:                                # Houlsby adapter
        a = jax.nn.gelu((y @ p["adapter_down"]).astype(jnp.float32)).astype(y.dtype)
        y = y + a @ p["adapter_up"]
    return y


# ---------------------------------------------------------------------------
# MoE FFN — sort+capacity grouped matmul, optional expert-parallel a2a
# ---------------------------------------------------------------------------

def _group_by_expert(xt, top_i, top_w, E_slots: int, C: int, fsplit: int):
    """Token grouping: returns (xg (E_slots*C, D), combine info).

    Tokens routed to logical expert e are duplicated onto the fsplit
    physical slots [e*fsplit, (e+1)*fsplit) — each slot holds a 1/fsplit
    slice of d_ff, and the down-projection partial sums recombine in the
    weighted scatter-add (expert tensor-parallel trick for E < EP-degree).
    """
    T, k = top_i.shape
    if fsplit > 1:
        top_i = (top_i[..., None] * fsplit
                 + jnp.arange(fsplit)[None, None, :]).reshape(T, k * fsplit)
        top_w = jnp.repeat(top_w, fsplit, axis=-1)
        k = k * fsplit
    flat_e = top_i.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    first = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(T * k) - first
    keep = pos < C
    dest = jnp.where(keep, se * C + pos, E_slots * C)      # overflow → dump row
    xg = jnp.zeros((E_slots * C + 1, xt.shape[-1]), xt.dtype)
    xg = xg.at[dest].add(xt[st])
    return xg[:-1], (st, sw, dest, keep)


def _combine_from_expert(yg, combine, T: int):
    st, sw, dest, keep = combine
    D = yg.shape[-1]
    yg1 = jnp.concatenate([yg, jnp.zeros((1, D), yg.dtype)], axis=0)
    vals = yg1[jnp.where(keep, dest, yg.shape[0])] * (sw * keep)[:, None].astype(yg.dtype)
    return jnp.zeros((T, D), yg.dtype).at[st].add(vals)


def _expert_mlp(xg, wg, wu, wd):
    """xg: (E_loc, C, D); weights (E_loc, D, F_loc)/(E_loc, F_loc, D)."""
    g = jnp.einsum("ecd,edf->ecf", xg, wg.astype(xg.dtype))
    u = jnp.einsum("ecd,edf->ecf", xg, wu.astype(xg.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xg.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, wd.astype(xg.dtype))


def moe_router(p, xt, cfg, fsplit: int):
    logits = (xt @ p["router"]["kernel"].astype(xt.dtype)).astype(jnp.float32)
    top_w, top_i = jax.lax.top_k(logits, cfg.top_k)
    top_w = jax.nn.softmax(top_w, axis=-1).astype(xt.dtype)
    # load-balance aux (Switch): E * sum_e f_e * p_e
    probs = jax.nn.softmax(logits, axis=-1)
    counts = jnp.zeros((cfg.n_experts,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f = counts / jnp.maximum(counts.sum(), 1.0)
    aux = cfg.n_experts * jnp.sum(f * probs.mean(0))
    return top_i, top_w, aux


def moe_ffn_local(p: Params, x, cfg):
    """Single-shard sort+capacity grouped-matmul MoE.

    Expert weights are stored in *slot layout* ``(E·fsplit, D, F/fsplit)``
    (see ArchConfig.ep_fsplit); for fsplit == 1 this is the plain layout.
    Also serves as the math oracle target for the expert-parallel path.
    """
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    fsplit = cfg.ep_fsplit
    E_slots = cfg.n_experts * fsplit
    C = max(1, int(math.ceil(cfg.top_k * T * cfg.capacity_factor / cfg.n_experts)))
    C = min(C, T)
    top_i, top_w, aux = moe_router(p, xt, cfg, fsplit)
    xg, combine = _group_by_expert(xt, top_i, top_w, E_slots, C, fsplit)
    wg, wu, wd = p["experts"]["gate"], p["experts"]["up"], p["experts"]["down"]
    yg = _expert_mlp(xg.reshape(E_slots, C, D), wg, wu, wd).reshape(E_slots * C, D)
    y = _combine_from_expert(yg, combine, T)
    return y.reshape(B, S, D), aux


def moe_ffn_manual(p: Params, x, cfg, dp: int, ep_axis: str = "data"):
    """MoE body for code already running inside a manual region over the
    data axes (launch/train.py's client shard_map).  Tokens are per-shard;
    expert slots are manual-sharded over ``ep_axis`` (E_loc per shard); the
    'model' axis stays auto — XLA inserts the F-partial all-reduce.
    """
    B_l, S, D = x.shape
    T = B_l * S
    xt = x.reshape(T, D)
    fsplit = cfg.ep_fsplit
    E_slots = cfg.n_experts * fsplit
    E_loc = E_slots // dp
    top_i, top_w, aux = moe_router(p, xt, cfg, fsplit)
    C = max(1, int(math.ceil(cfg.top_k * T * cfg.capacity_factor / cfg.n_experts)))
    C = min(C, T)
    xg, combine = _group_by_expert(xt, top_i, top_w, E_slots, C, fsplit)
    xg = xg.reshape(dp, E_loc, C, D)
    xr = jax.lax.all_to_all(xg, ep_axis, split_axis=0, concat_axis=0)
    xr = xr.transpose(1, 0, 2, 3).reshape(E_loc, dp * C, D)
    wg, wu, wd = p["experts"]["gate"], p["experts"]["up"], p["experts"]["down"]
    yr = _expert_mlp(xr, wg, wu, wd)
    yr = yr.reshape(E_loc, dp, C, D).transpose(1, 0, 2, 3)
    yg = jax.lax.all_to_all(yr, ep_axis, split_axis=0, concat_axis=0)
    y = _combine_from_expert(yg.reshape(E_slots * C, D), combine, T)
    return y.reshape(B_l, S, D), aux


def moe_ffn_ep(p: Params, x, cfg, mesh, ep_axis: str = "data"):
    """Expert-parallel MoE via shard_map + all_to_all over ``ep_axis``.

    Layout: expert slots sharded ``P(ep_axis, None, 'model')``; tokens
    sharded over the batch axes.  Per shard: local routing → group by slot
    → a2a (dispatch) → local grouped matmul on resident slots → a2a
    (return) → weighted combine → psum over 'model' (deferred from the
    down-projection partial sums — cheaper after combine).
    This is the GShard/Switch communication pattern expressed TPU-natively.
    """
    dp = mesh.shape[ep_axis]
    fsplit = cfg.ep_fsplit
    E_slots = cfg.n_experts * fsplit
    assert E_slots % dp == 0, (E_slots, dp)
    E_loc = E_slots // dp
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_total = 1
    for a in batch_axes:
        dp_total *= mesh.shape[a]

    if x.shape[0] % dp_total:
        # Small-batch (decode) path: activations replicated, experts stay
        # parallel — each shard computes its resident slots and the token
        # outputs are summed with a psum over the EP axis.
        def small_fn(x_l, router, wg, wu, wd):
            B_l, S, D = x_l.shape
            T = B_l * S
            xt = x_l.reshape(T, D)
            top_i, top_w, aux = moe_router({"router": {"kernel": router}},
                                           xt, cfg, fsplit)
            C = max(1, int(math.ceil(
                cfg.top_k * T * cfg.capacity_factor / cfg.n_experts)))
            C = min(C, T)
            xg, combine = _group_by_expert(xt, top_i, top_w, E_slots, C,
                                           fsplit)
            idx = jax.lax.axis_index(ep_axis)
            x_loc = jax.lax.dynamic_slice_in_dim(
                xg.reshape(E_slots, C, D), idx * E_loc, E_loc, 0)
            y_loc = _expert_mlp(x_loc, wg, wu, wd)
            yg = jnp.zeros((E_slots, C, D), y_loc.dtype)
            yg = jax.lax.dynamic_update_slice_in_dim(yg, y_loc, idx * E_loc, 0)
            y = _combine_from_expert(yg.reshape(E_slots * C, D), combine, T)
            y = jax.lax.psum(y, (ep_axis, "model"))
            aux = jax.lax.pmean(aux, batch_axes)
            return y.reshape(B_l, S, D), aux

        out = jax.shard_map(
            small_fn, mesh=mesh,
            in_specs=(P(None, None, None), P(None, None),
                      P(ep_axis, None, "model"), P(ep_axis, None, "model"),
                      P(ep_axis, "model", None)),
            out_specs=(P(None, None, None), P()),
            check_vma=False,
        )(x, p["router"]["kernel"], p["experts"]["gate"],
          p["experts"]["up"], p["experts"]["down"])
        return out

    def local_fn(x_l, router, wg, wu, wd):
        B_l, S, D = x_l.shape
        T = B_l * S
        xt = x_l.reshape(T, D)
        top_i, top_w, aux = moe_router({"router": {"kernel": router}}, xt,
                                       cfg, fsplit)
        C = max(1, int(math.ceil(
            cfg.top_k * T * cfg.capacity_factor / cfg.n_experts)))
        C = min(C, T)
        xg, combine = _group_by_expert(xt, top_i, top_w, E_slots, C, fsplit)
        xg = xg.reshape(dp, E_loc, C, D)
        # dispatch: swap device axis <-> slot-owner axis
        xr = jax.lax.all_to_all(xg, ep_axis, split_axis=0, concat_axis=0)
        xr = xr.transpose(1, 0, 2, 3).reshape(E_loc, dp * C, D)
        yr = _expert_mlp(xr, wg, wu, wd)                   # partial over F_loc
        yr = yr.reshape(E_loc, dp, C, D).transpose(1, 0, 2, 3)
        yg = jax.lax.all_to_all(yr, ep_axis, split_axis=0, concat_axis=0)
        y = _combine_from_expert(yg.reshape(E_slots * C, D), combine, T)
        y = jax.lax.psum(y, "model")                       # F_loc partials
        aux = jax.lax.pmean(aux, batch_axes)
        return y.reshape(B_l, S, D), aux

    x_spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0],
               None, None)
    out = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), P(ep_axis, None, "model"),
                  P(ep_axis, None, "model"), P(ep_axis, "model", None)),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"]["kernel"], p["experts"]["gate"], p["experts"]["up"],
      p["experts"]["down"])
    return out


def moe_ffn_dense_ref(p: Params, x, cfg):
    """Oracle: compute every expert for every token, mask by router top-k.
    O(E·T·D·F) — tiny models only (tests)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    top_i, top_w, aux = moe_router(p, xt, cfg, 1)
    wg, wu, wd = p["experts"]["gate"], p["experts"]["up"], p["experts"]["down"]
    g = jnp.einsum("td,edf->tef", xt, wg.astype(xt.dtype))
    u = jnp.einsum("td,edf->tef", xt, wu.astype(xt.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
    y_all = jnp.einsum("tef,efd->ted", h, wd.astype(xt.dtype))   # (T,E,D)
    gates = jnp.zeros((xt.shape[0], cfg.n_experts), xt.dtype).at[
        jnp.arange(xt.shape[0])[:, None], top_i].add(top_w)
    y = jnp.einsum("ted,te->td", y_all, gates.astype(y_all.dtype))
    return y.reshape(B, S, D), aux
