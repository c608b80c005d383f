"""Device time of the ``ce`` scope, the LM head and chunked cross-entropy,
forward, backward and recompute together, per training step, in ms."""
import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, "ce", scopes.steps(ctx))
