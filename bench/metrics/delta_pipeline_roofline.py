"""The server step kernel's share of its HBM roofline: the bytes the
FedAvgM step needs at the round's (C, P) and dtypes, at peak bandwidth,
over the kernel's summed device time."""
import counts
import xplane as trace

# The delta pipeline's Pallas kernel as the TPU trace names it: the
# custom call takes the name of its jitted entry, ``delta_pipeline_apply``.
PREFIX = "delta_pipeline_apply"


def match(name):
    return trace.op_head(name).startswith(PREFIX)


def read(ctx):
    t = trace.op_seconds(ctx["trace"], match)
    if t <= 0:
        return None
    w = ctx["work"]
    need = counts.delta_pipeline_bytes(w["slots"], w["param_bytes"], w["params"])
    return 100.0 * need * w["rounds"] / ctx["peak"]["hbm_bytes_per_s"] / t
