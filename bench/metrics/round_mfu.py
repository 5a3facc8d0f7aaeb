"""The FL round's model FLOP utilization: the training operations the
window's local-training tokens require, by the count that the cell's
plain reference gives (``train_flops_per_token`` in
``refs/<reference>.py``), over the traced window times the chip's peak."""
import common


def read(ctx):
    cell = ctx["cell"]
    count = getattr(common.reference_module(cell), "train_flops_per_token", None)
    if count is None:
        raise SystemExit(f"bench: reference {cell['cfg']['reference']!r} has "
                         "no train_flops_per_token for round_mfu")
    lo, hi = ctx["trace"].window()
    flops = count(cell["cfg"]["sizes"]) * ctx["work"]["tokens"]
    return 100.0 * flops / ((hi - lo) * ctx["peak"]["flops"] * ctx["chips"])
