"""Exchange rate of the data-parallel step: the algorithm's all-reduce
bytes a step (``bench/exchange.py``: the table's and the bias's
gradient, F x C x 4 + C x 4) times the steps, over one chip's collective
device time in the window, in GB/s.  None where the window's fit split
its batches over fewer devices than the cell's chips."""
from bench import exchange


def read(layer):
    return exchange.gbps(layer)
