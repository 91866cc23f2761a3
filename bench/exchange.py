"""The data-parallel training step's exchange between chips: the bytes
the algorithm sums over the chips each step, and the collective device
time a traced run shows, for the readers of a cell on several chips.

The bytes belong to the algorithm, not to an implementation: each step
sums the gradient of the bag table and of the bias over the chips, F x C
+ C float32 values (F = k * 2^(b_i + b_t) features, C classes), whatever
collective carries them.  A reduce-scatter or an exchange in a narrower
type then reads as the same work done faster or slower.

The readers read nothing where the window's fit split its batches over
fewer devices than the cell's chips (``repro.fit.setup``'s ``shards``,
``bench/fit_shards.py``): a run that silently trains on one chip has no
exchange to measure.
"""
from __future__ import annotations

from bench import fit_shards, xtrace

F32_BYTES = 4
UPDATE = "jit_update"       # the trainer's step executable, one run a step


def allreduce_bytes(cfg: dict) -> int:
    """Bytes of one step's gradient sum: the table's and the bias's."""
    features = cfg["num_hashes"] << (cfg["b_i"] + cfg.get("b_t", 0))
    return (features + 1) * cfg["n_classes"] * F32_BYTES


def _is_collective(name: str, hlo: str = "") -> bool:
    return bool(xtrace.COLLECTIVE.search(name))


def _steps_seen(dev, lo: float, hi: float) -> int:
    return sum(1 for n, s, _ in dev.modules
               if n.startswith(UPDATE) and lo <= s < hi)


def step_s(layer, *, exposed: bool = False):
    """Collective device seconds a step (``exposed``: only the time in
    which no other op runs on the chip), averaged over the chips, or None
    where the window's fit did not split over every chip.

    Each chip's time is divided by the steps its own trace shows (runs of
    the trainer's ``jit_update``): the profiler can drop a chip's later
    events, and did on four v5e chips, where it kept 3.6 s of TPU:0's
    8.6 s window."""
    if layer.trace is None or not layer.trace.devices:
        return None
    shards = fit_shards.read(layer)
    if shards is None or shards < layer.chips:
        return None
    per_chip = []
    for d in layer.trace.devices:
        steps = _steps_seen(d, layer.lo, layer.hi)
        if steps:
            t = (xtrace.exposed_collective_s(d, layer.lo, layer.hi) if exposed
                 else xtrace.op_time(d, _is_collective, layer.lo, layer.hi))
            per_chip.append(t / steps)
    return sum(per_chip) / len(per_chip) if per_chip else None


def per_step_ms(layer, *, exposed: bool = False):
    """Collective device time a step in ms, averaged over the chips."""
    t = step_s(layer, exposed=exposed)
    return None if t is None else t * 1e3


def gbps(layer):
    """The algorithm's exchange bytes a step over one chip's collective
    device time a step, in GB/s; None where there is none."""
    t = step_s(layer)
    return allreduce_bytes(layer.cfg) / t / 1e9 if t else None
