"""Device time of the round's scheduler (``fedfog.schedule``: the Eq. 3
gate over the client registry and the slot assignment) per window
round."""
import phases


def read(ctx):
    return phases.per_round_ms(ctx, "fedfog.schedule")
