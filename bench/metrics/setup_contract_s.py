"""Set-up time the program spent on the compiled round's HLO contract
(``fedfog.setup.contract``: the module's text, its analysis with the
phase map, and the one-all-reduce assertion)."""
import phases


def read(ctx):
    return phases.setup_seconds("fedfog.setup.contract")
