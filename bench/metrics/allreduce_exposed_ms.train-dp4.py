"""The part of a step's collective device time in which no other op runs
on the chip (``xtrace.exposed_collective_s``), averaged over the chips,
from the traced run.  None where the window's fit split its batches over
fewer devices than the cell's chips."""
from bench import exchange


def read(layer):
    return exchange.per_step_ms(layer, exposed=True)
