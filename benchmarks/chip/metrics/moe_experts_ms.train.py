"""Device time of the ``moe_experts`` part (the experts' gate, up and
down matmuls and the activation), forward, backward and recompute
together, per training step, in ms."""
import scopes
import subscopes


def read(ctx):
    return subscopes.ms_per(ctx, ("moe_experts",), scopes.steps(ctx))
