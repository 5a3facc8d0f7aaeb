"""Model FLOP utilization of local training alone: the training
operations of the window's local-training tokens, by the count beside the
cell's reference (as ``round_mfu``), over the device time of the round's
``fedfog.local_train`` operations times the chip's peak."""
import common
import phases


def read(ctx):
    prog = phases.round_program()
    if prog is None:
        return None
    t = phases.phase_seconds(ctx["trace"], prog, "fedfog.local_train")
    if t is None:
        return None
    cell = ctx["cell"]
    count = common.reference_module(cell).train_flops_per_token
    flops = count(cell["cfg"]["sizes"]) * ctx["work"]["tokens"]
    return 100.0 * flops / (t * ctx["peak"]["flops"] * ctx["chips"])
