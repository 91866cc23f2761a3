"""The algorithm's work, in operations and bytes, from shapes and counts.

The count is of the algorithm, not of an implementation, so it stays the
same whatever kernel computes it: a kernel that skips zero entries reads
as doing the same work faster, and one that works on zeros reads as
wasting time.

CWS operations per (nonzero entry, hash), from the update in log space
(core of arXiv:1503.01737 with Ioffe's sampler):

    t     = floor(log u / r + beta)      divide, add, floor         3
    log a = log c - r (t - beta + 1)     subtract, add, multiply,
                                         subtract                   4
    running argmin                       compare, select the value,
                                         select the index           3
                                                                  ---
                                                          CWS_OPS = 10

plus one logarithm per nonzero entry.  Parameter regeneration is the
implementation's choice and counts nothing.

The linear head adds k * C operations forward (the gathered rows summed)
and k * C backward (the scatter-add of the row gradients) per row.

Bytes are what a user's data and the model force through memory: the
rows as users send them (dense float32), the parameter matrices once per
launch when they are stored (3 * D * k * 4; none when regenerated), and
the emitted output (int32 indices, or packed words).
"""
from __future__ import annotations

CWS_OPS = 10


def cws_ops(rows: int, nnz_per_row: float, k: int) -> float:
    """Operations of featurizing ``rows`` rows."""
    return rows * nnz_per_row * (CWS_OPS * k + 1)


def head_ops(rows: int, k: int, n_classes: int, *, backward: bool) -> float:
    return rows * k * n_classes * (2 if backward else 1)


def out_bytes_per_row(cfg: dict) -> int:
    k = cfg["num_hashes"]
    if cfg.get("packed"):
        bits = cfg["b_i"] + cfg.get("b_t", 0)
        return -(-k * bits // 32) * 4
    return k * 4


def param_bytes(cfg: dict) -> int:
    if cfg["params"] == "regen":
        return 0
    return 3 * cfg["dim"] * cfg["num_hashes"] * 4


def cws_bytes(cfg: dict, rows: int, launches: int) -> float:
    """Bytes of ``rows`` rows featurized over ``launches`` kernel launches."""
    return (rows * (cfg["dim"] * 4 + out_bytes_per_row(cfg)) +
            launches * param_bytes(cfg))


def roofline(ops: float, nbytes: float, seconds: float, peak: dict):
    """(share of the roofline in %, the bound that applies): the least
    time the chip could take, over the time taken."""
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "ops" if t_ops >= t_bytes else "bytes"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
