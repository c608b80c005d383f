"""Device time of the ``grad_sync`` part, stage 2's token-weighted
gradient psum over the clients, per stage-2 step, in ms."""
import subscopes


def read(ctx):
    job = ctx["traffic"]["job"]
    return subscopes.ms_per(ctx, ("grad_sync",),
                            ctx["units"] * job["global_steps"])
