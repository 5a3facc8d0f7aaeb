"""Share of the traced window in which no operation ran on the device,
averaged over the cell's chips."""
import xplane as trace


def read(ctx):
    lo, hi = ctx["trace"].window()
    return 100.0 * (1.0 - trace.busy_s(ctx["trace"]) / (hi - lo))
