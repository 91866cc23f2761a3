"""Device time a step of the collective ops (``xtrace.COLLECTIVE``: the
all-reduce of the table's and the bias's gradient) in the window,
averaged over the chips, from the traced run.  None where the window's
fit split its batches over fewer devices than the cell's chips."""
from bench import exchange


def read(layer):
    return exchange.per_step_ms(layer)
