"""Share of the window in which no operation ran on the device
(1 - busy / window), averaged over the chips, from the trace."""
from bench import xtrace


def read(layer):
    if layer.trace is None or not layer.trace.devices:
        return None
    return xtrace.idle_percent(layer.trace, layer.lo, layer.hi)
