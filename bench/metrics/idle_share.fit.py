"""Share (%) of the traced window in which no operation ran on the chip."""
from bench.trace import idle_share


def read(view):
    return idle_share(view.trace, view.devices)
