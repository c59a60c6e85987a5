"""Share (%) of the traced window in which no operation ran, on each of
the chips the cell uses, averaged over them."""
from bench.trace import idle_share


def read(view):
    return idle_share(view.trace, view.devices)
