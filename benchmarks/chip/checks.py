"""The numbers that decide ``correct``: what the timed path produced set
against the reference, each a gap that is 0 for an exact match.

Training (per iteration checked):
  ce1, ce2, ce3  each stage's reported cross-entropy (stage 3: its last
                 step), as |program − reference| / reference
  grad1          the first gradient as stage 1's optimizer got it, read
                 back from its first moment (mu = (1 − b1)·g): per leaf
                 |‖program‖ − ‖reference‖| / max(‖reference‖, median leaf),
                 the worst leaf
  delta          each leaf's change over the iteration, the same measure
Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of grad1 and delta: round-off alone moves them.
"""
from __future__ import annotations

import math

import jax
import numpy as np

EXCLUDE_BELOW = 1e-3


def _leaves(tree) -> dict:
    return {jax.tree_util.keystr(p): np.asarray(x, np.float64)
            for p, x in jax.tree_util.tree_leaves_with_path(tree)}


def worst_leaf_gap(prog: dict, ref: dict, keep) -> tuple[float, str]:
    """max over ``keep`` leaves of |‖p‖ − ‖r‖| / max(‖r‖, median ‖r‖)."""
    norms = {k: float(np.linalg.norm(ref[k])) for k in keep}
    if not norms:
        return math.inf, "no leaf"
    med = float(np.median(list(norms.values())))
    worst, which = 0.0, ""
    for k in keep:
        g = abs(float(np.linalg.norm(prog[k])) - norms[k]) / max(norms[k],
                                                                 med)
        if not math.isfinite(g):
            return math.inf, k
        if g >= worst:
            worst, which = g, k
    return worst, which


def _moved(grad_norms: dict) -> list:
    med = float(np.median(list(grad_norms.values())))
    return [k for k, v in grad_norms.items() if v >= EXCLUDE_BELOW * med]


def train_numbers(theta0, prog: dict, ref: dict) -> dict:
    """{number: (value, detail)} for one checked pipeline iteration.
    ``prog``/``ref``: {"ce": 3 floats, "mu1": stage-1 first moments,
    "adapters": the iteration's result}, client-stacked trees."""
    out = {}
    for i, (p, r) in enumerate(zip(prog["ce"], ref["ce"])):
        out[f"ce{i + 1}"] = (abs(p - r) / abs(r) if math.isfinite(p)
                             else math.inf, "")
    mu_p, mu_r = _leaves(prog["mu1"]), _leaves(ref["mu1"])
    # the masked optimizer keeps moments for stage 1's leaves only
    grads = {k: float(np.linalg.norm(mu_r[k])) for k, v in mu_p.items()
             if v.size and k in mu_r}
    out["grad1"] = worst_leaf_gap(mu_p, mu_r, _moved(grads))
    t0 = _leaves(theta0)
    a_p, a_r = _leaves(prog["adapters"]), _leaves(ref["adapters"])
    ch_p = {k: a_p[k] - t0[k] for k in t0}
    ch_r = {k: a_r[k] - t0[k] for k in t0}
    # every leaf trains in one stage: its reference gradient is that
    # stage's first one
    firsts = [_leaves(ref[f"mu{s}"]) for s in (1, 2, 3)]
    grads = {k: max(float(np.linalg.norm(m[k])) for m in firsts)
             for k in t0}
    out["delta"] = worst_leaf_gap(ch_p, ch_r, _moved(grads))
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, list]:
    """(every number that has a limit within it, [[name, value, limit],
    ...]).  A number the limits file leaves out is reported with the limit
    None and not compared; at least one number has to be compared."""
    rows, ok = [], False
    for name, (value, _) in numbers.items():
        rows.append([name, value, limits.get(name)])
    compared = [(v, lim) for _, v, lim in rows if lim is not None]
    if compared:
        ok = all(math.isfinite(v) and v <= lim for v, lim in compared)
    return ok, rows
