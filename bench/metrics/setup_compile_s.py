"""Set-up time the program spent lowering and compiling its round
(``fedfog.setup.lower`` + ``fedfog.setup.compile``: a compile, or a load
from the persistent cache)."""
import phases


def read(ctx):
    return phases.setup_seconds("fedfog.setup.lower", "fedfog.setup.compile")
