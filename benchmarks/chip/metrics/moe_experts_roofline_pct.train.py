"""The expert matmuls' roofline time over the measured time of the
``moe_experts`` part, in %.  Roofline time: max(FLOPs / bf16 peak,
bytes / HBM bandwidth) of ``peaks.json``, counted by ``expert_counts`` over
the routed token-slots (T·k) of every layer in the forward, the
activation backward and the recompute; each chip trains ``rows`` rows a
step in every stage."""
import counts
import expert_counts
import scopes
import subscopes


def read(ctx):
    d = ctx["dims"]
    secs = subscopes.part_seconds(ctx, ("moe_experts",))
    if not d.experts or secs is None:
        return None
    job = ctx["traffic"]["job"]
    ops, nbytes = expert_counts.train_step(d, job["rows"], job["seq_len"])
    n = scopes.steps(ctx)
    t, _ = counts.roofline_seconds(n * ops, n * nbytes, ctx["peak"])
    return 100.0 * t / secs
