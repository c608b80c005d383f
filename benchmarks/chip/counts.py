"""Operations and bytes, counted from shapes; the table of chip peaks.

Model FLOPs count the matrix products a step needs over the parameters
that are active, and nothing that is recomputed:

* forward: every projection and the LM head (not the embedding lookup);
  for an expert layer the router and the top-k experts a token is routed
  to (capacity padding is not counted); each LoRA factor; causal
  attention counts the half of QKᵀ and PV that the mask keeps.
* training: the forward, plus the activation gradients (one more pass
  of every projection, the backbone being frozen; two of attention, whose
  both operands are activations; none for the first layer's q/k/v, whose
  input needs no gradient), plus the LoRA factors' own gradients.
"""
from __future__ import annotations

import json
from pathlib import Path

from dims import Dims

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    """The peak table's row for a device; an unknown device is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device {device_kind!r} in peaks.json")
    return table[device_kind]


def proj_flops(d: Dims) -> float:
    """Forward FLOPs of one token through one layer's weights (attention
    projections and FFN or router + top-k experts), LoRA excluded."""
    D, H, K, dh = d.d_model, d.heads, d.kv_heads, d.head_dim
    attn = 2 * D * (H * dh + 2 * K * dh) + 2 * H * dh * D
    if d.experts:
        ffn = 2 * D * d.experts + d.top_k * 3 * 2 * D * d.d_ff
    else:
        ffn = 3 * 2 * D * d.d_ff
    return float(attn + ffn)


def qkv_flops(d: Dims) -> float:
    return float(2 * d.d_model * (d.heads + 2 * d.kv_heads) * d.head_dim)


def lora_flops(d: Dims) -> float:
    """Forward FLOPs of one token through one layer's LoRA factors."""
    return float(sum(2 * d.rank * (d.d_model + d_out)
                     for _, d_out in d.targets()))


def head_flops(d: Dims) -> float:
    return float(2 * d.d_model * d.vocab)


def attn_flops(d: Dims, S: int) -> float:
    """Forward FLOPs of causal attention over one sequence of S tokens in
    one layer: QKᵀ and PV over the S(S+1)/2 kept query-key pairs."""
    return float(2 * 2 * d.heads * d.head_dim * S * (S + 1) / 2)


def train_flops(d: Dims, rows: int, S: int) -> float:
    """Model FLOPs of one optimizer step on ``rows`` sequences of S."""
    T, L = rows * S, d.layers
    fwd_w = L * T * proj_flops(d) + T * head_flops(d)
    bwd_w = fwd_w - T * qkv_flops(d)          # layer 0's input: no grad
    lora = 3 * L * T * lora_flops(d)          # forward, dX, dA/dB
    attn = 3 * L * rows * attn_flops(d, S)    # forward, dQ/dK, dP/dV
    return fwd_w + bwd_w + lora + attn


def roofline_seconds(ops: float, nbytes: float, peak: dict) -> tuple:
    """(least seconds the chip could take, which bound sets it)."""
    t_c = ops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")
