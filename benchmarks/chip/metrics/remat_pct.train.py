"""Device time of recomputed operations (op names under
``rematted_computation``) over all operation time, in %."""
import scopes


def read(ctx):
    b = scopes.of(ctx)
    if b is None or not b["total"]:
        return None
    return 100.0 * b["remat"] / b["total"]
