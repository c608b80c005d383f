"""Backend resolution shared by every kernel wrapper.

Each op has three implementations:

  pallas     the compiled Pallas TPU kernel — TPU backends only
  interpret  the same kernel body run by the Pallas interpreter (tests)
  einsum     the pure-jnp oracle from the kernel's ``ref.py``

``None`` picks ``pallas`` on a TPU and ``einsum`` elsewhere, so CPU runs
take the XLA oracle path.  An explicit ``pallas`` request off-TPU raises
instead of quietly interpreting: a run that asked for the kernel must
never report the interpreter's behaviour as the kernel's.
"""
from __future__ import annotations

import jax

IMPLS = ("pallas", "interpret", "einsum")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(impl, op: str) -> str:
    if impl is None:
        return "pallas" if on_tpu() else "einsum"
    if impl not in IMPLS:
        raise ValueError(f"unknown {op} impl {impl!r}")
    if impl == "pallas" and not on_tpu():
        raise RuntimeError(
            f"{op}: the compiled Pallas kernel needs a TPU backend, this "
            f"process runs on {jax.default_backend()!r}; pass "
            "impl='interpret' (or interpret=True) to run the kernel body "
            "in the Pallas interpreter")
    return impl


def impl_of_interpret(interpret) -> str | None:
    """Map the ``interpret`` flag some wrappers take onto an impl:
    None → backend default, True → interpreter, False → compiled."""
    if interpret is None:
        return None
    return "interpret" if interpret else "pallas"
