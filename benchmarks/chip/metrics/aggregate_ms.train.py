"""Device time of the ``aggregate`` scope (the stage-1 collective and the
rebroadcast) per stage-1 round, in ms."""
import scopes


def read(ctx):
    return scopes.scope_ms_per(ctx, "aggregate", ctx["units"])
