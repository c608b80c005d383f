"""jit'd public wrapper: model-layout SSD scan (b,S,H,P).

``interpret=None`` runs the compiled kernel on a TPU and the jnp oracle
elsewhere; ``interpret=True`` runs the kernel body in the Pallas
interpreter; ``interpret=False`` off-TPU raises (``kernels.dispatch``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.dispatch import impl_of_interpret, resolve_impl
from repro.kernels.ssd_scan.ssd_scan import ssd_scan_bh
from repro.kernels.ssd_scan.ref import ssd_ref, ssd_naive  # noqa: F401  (re-exported via repro.kernels)


def ssd_scan(x, dt, A_log, B, C, *, chunk: int = 256,
             interpret: bool | None = None):
    """x (b,S,H,P); dt (b,S,H); A_log (H,); B,C (b,S,G,N).
    Returns (y (b,S,H,P), final_state (b,H,P,N))."""
    impl = resolve_impl(impl_of_interpret(interpret), "ssd_scan")
    if impl == "einsum":
        return ssd_ref(x, dt, A_log, B, C, chunk)
    b, S, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    xf = x.transpose(0, 2, 1, 3).reshape(b * H, S, P)
    dtf = dt.transpose(0, 2, 1).reshape(b * H, S)
    Bf = B.transpose(0, 2, 1, 3).reshape(b * G, S, N)
    Cf = C.transpose(0, 2, 1, 3).reshape(b * G, S, N)
    alog = jnp.broadcast_to(A_log[None, :], (b, H)).reshape(b * H).astype(jnp.float32)
    y, st = ssd_scan_bh(xf, dtf, alog, Bf, Cf, chunk=chunk,
                        interpret=impl == "interpret")
    y = y.reshape(b, H, S, P).transpose(0, 2, 1, 3)
    st = st.reshape(b, H, N, P).transpose(0, 1, 3, 2)     # → (b,H,P,N)
    return y, st


__all__ = ["ssd_scan", "ssd_ref", "ssd_naive"]
