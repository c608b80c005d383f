"""Device time of the expert layer's routing and data movement: the
``moe_router`` (router matmul, top-k, softmax, load-balance term),
``moe_dispatch`` (sort and grouping scatter) and ``moe_combine``
(weighted gather and scatter-add back to tokens) parts, forward, backward
and recompute together, per training step, in ms."""
import scopes
import subscopes


def read(ctx):
    return subscopes.ms_per(
        ctx, ("moe_router", "moe_dispatch", "moe_combine"),
        scopes.steps(ctx))
