"""Device time of the ``lora`` scope, the decomposed-LoRA delta, forward,
backward and recompute together, per training step, in ms."""
import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, "lora", scopes.steps(ctx))
