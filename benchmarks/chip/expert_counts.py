"""Operations and bytes of the expert layer's matmuls, counted from shapes.

The three expert matmuls (gate, up, down) are counted over the routed
token-slots, T·k, and not over the capacity-padded rows the program
multiplies: padding, like dropped slots, shows as lost share.  A training
step runs them three times in every layer:

* the forward;
* the activation backward: the experts are frozen, so each weight gives
  one matmul (dh = dy·Wdᵀ, dx = dg·Wgᵀ + du·Wuᵀ) and no weight gradient;
* the recompute of the forward under the layer remat.

Bytes are the least a pass moves: each weight read once, each matmul's
input read and output written once, all in bf16.
"""
from __future__ import annotations

from dims import Dims

PASSES = 3                # forward, activation backward, recompute
BF16 = 2


def slots(d: Dims, rows: int, S: int) -> int:
    """Routed token-slots of one step: every token goes to top_k
    experts."""
    return rows * S * d.top_k


def pass_flops(d: Dims, n: int) -> float:
    """One pass of the three matmuls over ``n`` token-slots, one layer."""
    return float(3 * 2 * n * d.d_model * d.d_ff)


def pass_bytes(d: Dims, n: int) -> float:
    """One pass, one layer: the three expert weights once, and each
    matmul's input and output over ``n`` token-slots (gate and up read
    (n, D) and write (n, F); down reads (n, F) and writes (n, D); the
    backward moves the same shapes the other way)."""
    weights = 3 * d.experts * d.d_model * d.d_ff
    acts = 3 * n * (d.d_model + d.d_ff)
    return float(BF16 * (weights + acts))


def train_step(d: Dims, rows: int, S: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the expert matmuls in one training step on
    ``rows`` sequences of S, every layer and pass."""
    n = slots(d, rows, S)
    k = PASSES * d.layers
    return k * pass_flops(d, n), k * pass_bytes(d, n)
