"""Device time of the round's server step (``fedfog.server``: deltas,
fuse, the aggregation kernel, unfuse, server update and bookkeeping) per
window round."""
import phases


def read(ctx):
    return phases.per_round_ms(ctx, "fedfog.server")
