"""Device time of the stage-3 personalisation program per personal step,
in ms."""
import devtrace


def read(ctx):
    secs, calls = devtrace.module_seconds(ctx["trace"], r"personal_step")
    if not calls:
        return None
    return 1e3 * secs / (calls * ctx["traffic"]["job"]["personal_steps"])
