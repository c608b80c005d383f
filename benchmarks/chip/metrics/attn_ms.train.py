"""Device time of the ``attn`` scope (q/k/v/o projections, rope, scores,
softmax, PV; its LoRA deltas count under ``lora``), forward, backward and
recompute together, per training step, in ms."""
import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, "attn", scopes.steps(ctx))
