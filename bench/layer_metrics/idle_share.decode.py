"""Share of the traced window in which no operation ran on the device,
in the decode cells: 1 - (union of the device's operation intervals) /
window (device layer)."""
from bench import tracing


def read(w):
    return tracing.idle_share(w.trace)
