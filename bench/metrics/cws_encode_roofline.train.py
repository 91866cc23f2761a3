"""The CWS encode kernel's share of its roofline in the training window:
the least time the chip could take for the algorithm's operations and
bytes (bench/counts.py), over the kernel's device time in the trace."""
from bench import kernels


def read(layer):
    return kernels.encode_roofline(layer)
