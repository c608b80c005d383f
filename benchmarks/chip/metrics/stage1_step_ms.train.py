"""Device time of the stage-1 round program (local steps and the
aggregation collective) per local step, in ms."""
import devtrace


def read(ctx):
    secs, calls = devtrace.module_seconds(ctx["trace"], r"round_step")
    if not calls:
        return None
    return 1e3 * secs / (calls * ctx["traffic"]["job"]["local_steps"])
