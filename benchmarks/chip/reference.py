"""Plain reference of the models and of the paper's three-stage pipeline.

Written from the published descriptions (a pre-norm decoder with RMSNorm,
rotary embeddings, causal multi-head attention with grouped keys, a SwiGLU
FFN or a top-k routed expert layer, an untied LM head) and from the paper
(decomposed LoRA: A = A_mag ⊙ (A_dir + ΔA_D), B = (B_M + ΔB_M) ⊙ B_dir;
stage 1 trains the factors, stage 2 ΔA_D on the server mixture, stage 3
ΔB_M under ½λ‖ΔB_M‖²).  It imports nothing of the program.

Everything is float32 at full matmul precision.  ``prec`` switches the
frozen backbone's matmuls (projections, FFN, experts, LM head) to an
emulated lower precision for the control: "int8" rounds weights per output
channel and activations per row to 8-bit integers, "fp8" rounds both
operands to float8 e4m3.  Adapters, router and attention stay float32.

Memory: every layer is rematerialised, attention runs in query blocks and
the loss in sequence chunks, so the reference fits beside the weights on
one chip.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from dims import Dims

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
LOSS_CHUNK = 256

# which adapter leaves each stage trains (paper Eqs. 5-11)
STAGE_LEAVES = {1: ("A_dir", "A_mag", "B_dir", "B_mag"),
                2: ("dA_dir",), 3: ("dB_mag",)}


# ---------------------------------------------------------------------------
# matmuls

def _round_int8(x, axis):
    """Symmetric 8-bit rounding along ``axis``; the gradient passes
    straight through the rounding, as in quantised training."""
    s = jax.lax.stop_gradient(
        jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0)
    s = jnp.where(s > 0, s, 1.0)
    q = x / s
    return q + jax.lax.stop_gradient(jnp.round(q) - q), s


def matmul(x, w, prec="f32"):
    """x (..., d_in) float32 @ w (d_in, d_out), accumulated in float32."""
    w = w.astype(F32)
    if prec == "f32":
        return jnp.matmul(x, w, precision=HIGHEST)
    if prec == "int8":
        xq, sx = _round_int8(x, -1)
        wq, sw = _round_int8(w, 0)
        # integers up to 127 are exact in one bf16 pass
        return jnp.matmul(xq, wq) * sx * sw
    if prec == "fp8":
        def f8(a):                       # rounded, gradient straight through
            return a + jax.lax.stop_gradient(
                a.astype(jnp.float8_e4m3fn).astype(F32) - a)
        return jnp.matmul(f8(x), f8(w))
    raise ValueError(f"unknown precision {prec!r}")


def lora(x, a, scale):
    """The decomposed adapter's contribution, float32."""
    A = a["A_mag"][:, None] * (a["A_dir"] + a["dA_dir"])
    B = (a["B_mag"] + a["dB_mag"])[:, None] * a["B_dir"]
    h = jnp.matmul(x, A, precision=HIGHEST)
    return scale * jnp.matmul(h, B, precision=HIGHEST)


def project(x, p, a, name, d: Dims, prec):
    y = matmul(x, p[name]["kernel"], prec)
    if a is not None and name in a:
        y = y + lora(x, a[name], d.lora_scale)
    return y


# ---------------------------------------------------------------------------
# layers

def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (B, S, H, dh), rotating the two halves of each head."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, :, None, None].astype(F32) * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def causal_attention(q, k, v):
    """q (B, S, H, dh), k/v (B, S, K, dh): softmax(q kᵀ/√dh) v over the
    keys at or before each query, in query blocks."""
    B, S, H, dh = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    qb = Q_BLOCK if S % Q_BLOCK == 0 else S

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HIGHEST)
        s = s / math.sqrt(dh)
        keep = (jnp.arange(S)[None, :]
                <= (i * qb + jnp.arange(qb))[:, None])
        s = jnp.where(keep, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(S // qb))       # (nb, B, qb, H, dh)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, dh)


def attention_block(x, pos, p, a, d: Dims, prec):
    B, S, _ = x.shape
    H, K, dh = d.heads, d.kv_heads, d.head_dim
    q = project(x, p, a, "q_proj", d, prec).reshape(B, S, H, dh)
    k = project(x, p, a, "k_proj", d, prec).reshape(B, S, K, dh)
    v = project(x, p, a, "v_proj", d, prec).reshape(B, S, K, dh)
    if d.qk_norm:
        q = rms_norm(q, p["q_norm"], d.norm_eps)
        k = rms_norm(k, p["k_norm"], d.norm_eps)
    q, k = rope(q, pos, d.rope_theta), rope(k, pos, d.rope_theta)
    o = causal_attention(q, k, v).reshape(B, S, H * dh)
    return project(o, p, a, "o_proj", d, prec)


def swiglu(x, wg, wu, wd, prec):
    return matmul(jax.nn.silu(matmul(x, wg, prec)) * matmul(x, wu, prec),
                  wd, prec)


def expert_layer(x, p, d: Dims, prec):
    """Top-k routing over all experts, softmax over the k chosen logits.
    An expert takes the tokens routed to it in token order until it holds
    ceil(k · T · capacity_factor / E) of them; later ones lose that expert.
    Returns (y, Switch load-balance term)."""
    B, S, D = x.shape
    T, E, k = B * S, d.experts, d.top_k
    xt = x.reshape(T, D)
    logits = jnp.matmul(xt, p["router"]["kernel"], precision=HIGHEST)
    top_l, top_e = jax.lax.top_k(logits, k)                  # (T, k)
    gate = jax.nn.softmax(top_l, axis=-1)
    chosen = jax.nn.one_hot(top_e, E, dtype=jnp.int32).sum(1)   # (T, E)
    aux = E * jnp.sum(chosen.sum(0) / (T * k)
                      * jax.nn.softmax(logits, -1).mean(0))
    cap = min(T, math.ceil(k * T * d.capacity_factor / E))
    # this token's place in each chosen expert's queue
    place = jnp.take_along_axis(jnp.cumsum(chosen, 0) - chosen, top_e, 1)
    keep = place < cap
    # expert e's queue: slot s holds token table[e, s] with weight wt[e, s]
    slot = jnp.where(keep, top_e * cap + place, E * cap)     # E*cap: dropped
    tok = jnp.broadcast_to(jnp.arange(T)[:, None], (T, k))
    table = jnp.zeros(E * cap + 1, jnp.int32).at[slot].set(tok)[:-1]
    wt = jnp.zeros(E * cap + 1, F32).at[slot].set(gate)[:-1]
    xe = xt[table].reshape(E, cap, D)
    ex = p["experts"]

    def one(xs, wg, wu, wd):
        return swiglu(xs, wg, wu, wd, prec)

    ye = jax.vmap(one)(xe, ex["gate"], ex["up"], ex["down"])
    y = jnp.zeros((T, D), F32).at[table].add(
        ye.reshape(E * cap, D) * wt[:, None])
    return y.reshape(B, S, D), aux


def decoder_layer(x, pos, p, a, d: Dims, prec):
    x = x + attention_block(rms_norm(x, p["input_norm"], d.norm_eps), pos,
                            p["attn"], a, d, prec)
    h = rms_norm(x, p["ffn_norm"], d.norm_eps)
    if d.experts:
        y, aux = expert_layer(h, p["moe"], d, prec)
    else:
        m = p["mlp"]
        y = swiglu(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                   m["down_proj"]["kernel"], prec)
        aux = jnp.zeros((), F32)
    return x + y, aux


def hidden_states(base, adapters, tokens, d: Dims, prec="f32"):
    """Final normed hidden states (B, S, D) and the summed aux term.
    ``adapters``: one client's tree (no client axis)."""
    B, S = tokens.shape
    x = base["embed"]["embedding"][tokens].astype(F32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    blk = base["blocks"]["sub0"]
    ad = adapters["blocks"]["sub0"]["attn"]

    @jax.checkpoint
    def body(carry, layer):
        x, aux = carry
        p, a = layer
        x, aux_l = decoder_layer(x, pos, p, a, d, prec)
        return (x, aux + aux_l), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), F32)), (blk, ad))
    return rms_norm(x, base["final_norm"], d.norm_eps), aux


def _chunks(x, n):
    pad = -x.shape[1] % n
    x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    return jnp.moveaxis(x.reshape(x.shape[0], -1, n, *x.shape[2:]), 1, 0)


def cross_entropy(h, head, tokens, mask, prec="f32"):
    """Mean next-token cross-entropy under ``mask`` (positions 0..S-2
    predict tokens 1..S-1), in sequence chunks."""
    hs = _chunks(h[:, :-1], LOSS_CHUNK)
    ts = _chunks(tokens[:, 1:], LOSS_CHUNK)
    ms = _chunks(mask[:, :-1], LOSS_CHUNK)       # padding carries weight 0

    @jax.checkpoint
    def chunk(carry, xs):
        hc, tc, mc = xs
        logits = matmul(hc, head, prec)
        nll = (jax.nn.logsumexp(logits, -1)
               - jnp.take_along_axis(logits, tc[..., None], -1)[..., 0])
        return carry + jnp.sum(nll * mc), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), F32), (hs, ts, ms))
    return total / jnp.maximum(jnp.sum(mask[:, :-1]), 1.0)


# ---------------------------------------------------------------------------
# training: one optimizer step of one stage, and the pipeline

def _leaf_name(path):
    return path[-1].key


def stage_loss(adapters, base, batch, d: Dims, lam, prec):
    h, aux = hidden_states(base, adapters, batch["tokens"], d, prec)
    ce = cross_entropy(h, base["lm_head"]["kernel"], batch["tokens"],
                       batch["loss_mask"], prec)
    loss = ce + d.aux_weight * aux
    if lam:
        reg = sum(jnp.sum(jnp.square(x)) for p, x in
                  jax.tree_util.tree_leaves_with_path(adapters)
                  if _leaf_name(p) == "dB_mag")
        loss = loss + 0.5 * lam * reg
    return loss, ce


@partial(jax.jit, static_argnames=("d", "stage", "lam", "prec", "hp"))
def adam_step(adapters, mu, nu, t, base, batch, *, d: Dims, stage: int,
              lam: float, prec: str, hp: tuple):
    """One AdamW step (no weight decay) of ``stage``'s leaves on the whole
    batch; the gradient of every adapter leaf is clipped by their joint
    norm first.  ``t``: 1-based step of this stage's optimizer.  ``hp``:
    (lr, clip, b1, b2, eps).  Returns (adapters, mu, nu, ce)."""
    lr, clip, b1, b2, eps = hp
    (_, ce), g = jax.value_and_grad(stage_loss, has_aux=True)(
        adapters, base, batch, d, lam, prec)
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (norm + 1e-9)), g)
    trained = STAGE_LEAVES[stage]

    def upd(path, x, gx, m, v):
        if _leaf_name(path) not in trained:
            return x, m, v
        m = b1 * m + (1 - b1) * gx
        v = b2 * v + (1 - b2) * gx * gx
        mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return x - lr * mhat / (jnp.sqrt(vhat) + eps), m, v

    out = jax.tree_util.tree_map_with_path(upd, adapters, g, mu, nu)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), ce


def _zeros(tree):
    return jax.tree.map(jnp.zeros_like, tree)


def _rows(batch, i, n):
    return {k: v[i * n:(i + 1) * n] for k, v in batch.items()}


def pipeline(base, adapters, batch, server, personal, d: Dims, job,
             prec="f32", drop_half=False):
    """One iteration of the paper's pipeline for C clients.

    adapters: client-stacked tree (C, ...); batch/personal: {tokens,
    loss_mask} of (C, steps·rows, S); server: (global_steps·rows', S).
    ``job``: the traffic's training settings.  ``drop_half``: the planted
    fault of a step that averages over half of each batch.
    Returns {"ce": (stage-1, stage-2, stage-3 last step), "mu1"/"mu3":
    stage 1's and 3's first-step first moments (C-stacked), "mu2": stage
    2's, "adapters": the result (C-stacked)}."""
    hp1 = (job["lr"], job["clip"], 0.9, 0.999, 1e-8)
    hp2 = (job["server_lr"],) + hp1[1:]
    C = jax.tree.leaves(adapters)[0].shape[0]
    rows = job["rows"]

    def run(stage, ad, b, steps, n, hp, lam):
        """``steps`` optimizer steps; returns (adapters, first step's
        first moments, last step's ce)."""
        mu, nu = _zeros(ad), _zeros(ad)
        ce = mu1 = None
        for t in range(steps):
            bt = _rows(b, t, n)
            if drop_half:
                bt = _rows(bt, 0, n // 2)
            ad, mu, nu, ce = adam_step(ad, mu, nu, t + 1, base, bt, d=d,
                                       stage=stage, lam=lam, prec=prec, hp=hp)
            mu1 = mu if mu1 is None else mu1
        return ad, mu1, ce

    client = lambda tree, c: jax.tree.map(lambda x: x[c], tree)
    s1 = [run(1, client(adapters, c), client(batch, c), job["local_steps"],
              rows, hp1, 0.0) for c in range(C)]
    ce1 = sum(float(ce) for _, _, ce in s1) / C
    agg = jax.tree.map(lambda *xs: sum(xs) / C, *[ad for ad, _, _ in s1])
    agg, mu2, ce2 = run(2, agg, server, job["global_steps"],
                      server["tokens"].shape[0] // job["global_steps"], hp2,
                      0.0)
    # the server model goes back to every client; ΔB_M stays each client's
    s3 = []
    for c in range(C):
        own = jax.tree_util.tree_map_with_path(
            lambda p, g, o: o if _leaf_name(p) == "dB_mag" else g,
            agg, s1[c][0])
        s3.append(run(3, own, client(personal, c), job["personal_steps"],
                      rows, hp1, job["lam"]))
    ce3 = sum(float(ce) for _, _, ce in s3) / C
    stack = lambda trees: jax.tree.map(lambda *xs: jnp.stack(xs), *trees)
    return {"ce": (ce1, float(ce2), ce3),
            "mu1": stack([mu for _, mu, _ in s1]),
            "mu2": mu2, "mu3": stack([mu for _, mu, _ in s3]),
            "adapters": stack([ad for ad, _, _ in s3])}

