"""Device time of the parts the program names inside its scopes.

``scopes.py`` splits operation time by the innermost of six scopes.
Inside them the program names finer parts: the expert layer's
``moe_router``, ``moe_dispatch``, ``moe_experts`` and ``moe_combine``
(inside ``ffn``, ``models/layers.py``) and stage 2's gradient psum
``grad_sync`` (``launch/train.py``).  An operation counts toward the
innermost of ``PARTS`` among the ``/``-parts of its ``op_name``, whatever
scope holds it.  Operations are joined to their op names as ``scopes.py``
joins them, by the program whose execution encloses them; loop
operations are left out and times are averaged over the chips.

A program that names none of the parts gives nothing to read: the
readers then return None.
"""
from __future__ import annotations

import devtrace
import scopes

PARTS = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine",
         "grad_sync")


def part_of(name: str) -> str | None:
    """The innermost of ``PARTS`` among the ``/``-parts of an op name."""
    for part in reversed(name.split("/")):
        if part in PARTS:
            return part
    return None


def seconds(trace, names: dict) -> dict | None:
    """{part: seconds} of operation time in the traced window, averaged
    over the chips.  ``names``: {program: {instruction: op_name}}.  None
    when no operation joins to an op name."""
    named = False
    out = dict.fromkeys(PARTS, 0.0)
    for d in trace.devices:
        at = scopes._enclosing(d.modules)
        for ev in devtrace.in_window(d.ops, trace):
            if not scopes._kept(ev):
                continue
            inst = scopes._EVENT_INST.match(ev.name).group(1)
            name = names.get(at(ev.start), {}).get(inst, "")
            named = named or bool(name)
            part = part_of(name)
            if part:
                out[part] += ev.dur
    if not named:
        return None
    return {k: v / len(trace.devices) for k, v in out.items()}


def of(ctx) -> dict | None:
    """The traced run's part seconds, kept in ``ctx`` for the next
    reader."""
    if "subscopes" not in ctx:
        ctx["subscopes"] = seconds(ctx["trace"], scopes.op_names(ctx))
    return ctx["subscopes"]


def part_seconds(ctx, parts) -> float | None:
    """Seconds of the operations in any of ``parts``; None when the
    program names none of them."""
    s = of(ctx)
    if s is None or not any(s[p] for p in parts):
        return None
    return sum(s[p] for p in parts)


def ms_per(ctx, parts, per: int) -> float | None:
    """Milliseconds of the operations in any of ``parts`` per ``per``
    (steps or rounds); None when the program names none of them."""
    secs = part_seconds(ctx, parts)
    return None if secs is None else 1e3 * secs / per
