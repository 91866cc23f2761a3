"""Whole-step share of the chips' peak in training: the algorithm's
operations per row (CWS, and the head forward and backward) times the
rows per second of the traced window, over chips times the peak."""
from bench import kernels


def read(layer):
    return kernels.step_mfu(layer, head=True)
