"""Device time of the ``ffn`` scope, the dense FFN (gate/up/down matmuls
and the activation), forward, backward and recompute together, per
training step, in ms."""
import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, "ffn", scopes.steps(ctx))
