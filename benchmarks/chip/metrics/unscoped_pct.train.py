"""Device time of operations in none of the six named scopes over all
operation time, in %."""
import scopes


def read(ctx):
    b = scopes.of(ctx)
    if b is None or not any(b["scopes"].get(s) for s in scopes.SCOPES):
        return None
    return 100.0 * b["scopes"].get(scopes.NO_SCOPE, 0.0) / b["total"]
