"""Device time of the ``optimizer`` scope (gradient norm, clip, AdamW
update, cover mask, apply) per training step, in ms."""
import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, "optimizer", scopes.steps(ctx))
