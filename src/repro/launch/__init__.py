"""Entry points: ``train`` (the FedFog round), ``serve`` (the static and
continuous-batching servers), ``dryrun``; and what they share."""
from __future__ import annotations


def config_from_args(args):
    """The model config a launcher runs for its parsed ``args``: the
    published config on ``--scale full`` (depth cut to ``--layers`` when
    given, widths never), the reduced one on ``--scale tiny`` or with
    ``--reduced``."""
    from repro.configs import get_config, get_reduced

    full = args.scale == "full"
    cfg = (
        get_config(args.arch)
        if full and not args.reduced
        else get_reduced(args.arch, loss_chunk=0)
    )
    if full and args.layers:
        cfg = cfg.with_depth(args.layers)
    return cfg
