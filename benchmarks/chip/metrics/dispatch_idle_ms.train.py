"""Device idle time while one of the program's ``fed/*`` host spans is
open, per pipeline iteration, in ms."""
import scopes


def read(ctx):
    spans = [s for s in scopes.program_spans(ctx)
             if s.name.startswith("fed/")]
    if not spans:
        return None
    return 1e3 * scopes.idle_under(ctx["trace"], spans) / ctx["units"]
