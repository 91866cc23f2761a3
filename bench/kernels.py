"""Which trace events are which kernel, and the shares computed from
them with the algorithm's counts (bench/counts.py)."""
from __future__ import annotations

import re

from bench import counts, xtrace

# The CWS encode kernels (stored or regenerated parameters, int32 or
# packed emit) as the trace prints them: a Pallas kernel is a custom
# call named after the function that made it (``cws_encode_pallas``,
# ``cws_encode_rng_packed_pallas``, ...).
CWS_ENCODE = re.compile(r"^cws_encode\w*_pallas(\.\d+)?$")


def is_cws_encode(name: str, hlo: str = "") -> bool:
    return bool(CWS_ENCODE.match(name))


def encode_time(layer) -> float:
    return sum(xtrace.op_time(d, is_cws_encode, layer.lo, layer.hi)
               for d in layer.trace.devices)


def encode_roofline(layer):
    """% of the roofline of the encode kernel over the window, or None
    where the trace shows no encode kernel."""
    q = layer.quantities
    if layer.trace is None or not q.get("rows"):
        return None
    t = encode_time(layer)
    if t <= 0:
        return None
    ops = counts.cws_ops(q["rows"], q["nnz_per_row"],
                         layer.cfg["num_hashes"])
    nbytes = counts.cws_bytes(layer.cfg, q["rows"],
                              q["launches"] * layer.chips)
    share, _ = counts.roofline(ops, nbytes, t, layer.peak)
    return share


def step_mfu(layer, *, head: bool):
    q = layer.quantities
    if not q.get("rows") or layer.hi <= layer.lo:
        return None
    k, c = layer.cfg["num_hashes"], layer.cfg["n_classes"]
    ops = counts.cws_ops(q["rows"], q["nnz_per_row"], k)
    if head:
        ops += counts.head_ops(q["rows"], k, c, backward=True)
    rate = ops / (layer.hi - layer.lo)
    return 100.0 * rate / (layer.chips * layer.peak["flops_per_s"])
