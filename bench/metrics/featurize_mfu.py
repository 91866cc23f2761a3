"""Whole-step share of the chip's peak in featurization: the CWS
operations per row times the rows per second of the traced window, over
the peak."""
from bench import kernels


def read(layer):
    return kernels.step_mfu(layer, head=False)
