"""Kernel implementation registry with backend-capability dispatch.

Every logical op (``cws_hash``, ``cws_encode``, ``cws_hash_rng``,
``cws_encode_rng``, ``minmax_gram``, ``min_sum``) has named
implementations:

  * ``pallas``            — the Mosaic kernel, requires a TPU backend;
  * ``pallas-interpret``  — the same kernel body through the Pallas
                            interpreter (any backend; the correctness path
                            on this CPU container);
  * ``reference``         — pure-JAX composition with identical semantics
                            (fast on CPU, the oracle everywhere).

Dispatch is by capability: ``resolve(op)`` picks ``pallas`` when a TPU is
attached and ``reference`` otherwise, so production code never hard-codes
a backend.  ``resolve(op, "pallas-interpret")`` pins an implementation
explicitly (tests, benchmarks).

Block sizes are no longer hardcoded at the call sites: ``choose_blocks``
consults a small autotune table keyed on pow2-bucketed (n, D, k) and falls
back to a VMEM-budget heuristic (see DESIGN.md §2 for the roofline that
motivates the defaults).  The table is process-global and extendable via
``update_block_table`` so future TPU sweeps can refine it without touching
call sites.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import pathlib
from typing import Callable, Dict, List, Tuple

import jax

__all__ = [
    "KernelImpl", "register", "resolve", "impl_names", "backend",
    "on_tpu", "auto_impl", "pallas_impl", "donate_argnums",
    "choose_blocks",
    "update_block_table", "save_block_table", "load_block_table",
    "block_candidates", "vmem_bytes", "table_key", "BLOCK_TABLE",
    "serve_buckets", "update_serve_buckets", "save_serve_buckets",
    "load_serve_buckets", "SERVE_BUCKET_TABLE", "DEFAULT_SERVE_BUCKETS",
    # introspection surface consumed by repro.analysis / tools/kernel_lint
    "registered_ops", "family", "model_families", "vmem_budget",
    "has_vmem_model", "LaunchProbe", "register_probe", "family_probes",
    "probe_families", "force_donation", "register_donation_site",
    "donation_sites", "register_collective_site", "collective_sites",
    "register_numerics_site", "numerics_sites",
    "TrioProbe", "register_trio", "trio_probes",
]


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    op: str
    name: str
    fn: Callable
    requires: Tuple[str, ...] = ()     # backend capabilities, e.g. ("tpu",)

    def available(self) -> bool:
        return all(cap == backend() for cap in self.requires)


_REGISTRY: Dict[str, Dict[str, KernelImpl]] = {}


def backend() -> str:
    return jax.default_backend()


def on_tpu() -> bool:
    return backend() == "tpu"


def register(op: str, name: str, *, requires: Tuple[str, ...] = ()):
    """Decorator: register ``fn`` as implementation ``name`` of ``op``."""
    def deco(fn: Callable) -> Callable:
        _REGISTRY.setdefault(op, {})[name] = KernelImpl(
            op=op, name=name, fn=fn, requires=tuple(requires))
        return fn
    return deco


def impl_names(op: str) -> Tuple[str, ...]:
    return tuple(_REGISTRY.get(op, {}))


def auto_impl(op: str) -> str:
    """Capability-based default: the Mosaic kernel on TPU, the pure-JAX
    reference elsewhere (the interpreter is a correctness tool, not a
    production path)."""
    return "pallas" if on_tpu() else "reference"


def pallas_impl(op: str = "") -> str:
    """The kernel-body path for the current backend (interpret off-TPU)."""
    return "pallas" if on_tpu() else "pallas-interpret"


_FORCE_DONATE = False


def donate_argnums(*argnums: int) -> Tuple[int, ...]:
    """THE donation policy for launch-shaped jits: donate on TPU (XLA
    reuses the buffer for the output), empty elsewhere (an int32 output
    can never alias an fp32 input on CPU, so donation would only warn).
    Shared by the pipeline chunk fns and the streaming trainer so every
    donating call site gates identically."""
    return tuple(argnums) if (on_tpu() or _FORCE_DONATE) else ()


@contextlib.contextmanager
def force_donation():
    """Make donate_argnums return its argnums regardless of backend.

    Tracing a donating jit never compiles, so the donation analyzer can
    reconstruct the TPU-shaped ``donated_invars`` on any host.  Jits built
    *before* entering the context keep their (empty) donation; callers
    must construct the entry points they want audited inside the block.
    """
    global _FORCE_DONATE
    prev = _FORCE_DONATE
    _FORCE_DONATE = True
    try:
        yield
    finally:
        _FORCE_DONATE = prev


def resolve(op: str, impl: str | None = None) -> KernelImpl:
    """Look up an implementation; ``impl=None`` dispatches by capability."""
    table = _REGISTRY.get(op)
    if not table:
        raise KeyError(f"no implementations registered for op {op!r}")
    name = impl or auto_impl(op)
    if name not in table:
        raise KeyError(f"op {op!r} has no impl {name!r}; "
                       f"registered: {sorted(table)}")
    chosen = table[name]
    if not chosen.available():
        raise RuntimeError(
            f"impl {name!r} of op {op!r} requires backend "
            f"{chosen.requires} but default backend is {backend()!r}")
    return chosen


# ---------------------------------------------------------------------------
# block-size selection
# ---------------------------------------------------------------------------

# Tuned entries keyed on (op_family, pow2-bucketed (n, D, k)) ->
# (bn, bk, bd).  Families keep measured entries from silently applying to
# kernels whose axis meanings and VMEM footprint differ:
#   "cws"     — stored-param CWS (rows x dims x hashes);
#   "cws_rng" — regenerated-param CWS (same grid, params live in scratch
#               and cost VPU work instead of HBM reads, so the measured
#               optimum can differ — typically larger bn, since the
#               regeneration cost amortizes over the row block);
#   "min_sum" — the gram kernels (rows x dims x cols).
# Seeded from the VMEM model below at the shapes the benchmarks exercise;
# autotune sweeps (tools/autotune_blocks.py) replace entries with measured
# winners via update_block_table / load_block_table.
BLOCK_TABLE: Dict[Tuple[str, int, int, int], Tuple[int, int, int]] = {
    ("cws", 256, 512, 512):    (128, 128, 512),
    ("cws", 1024, 512, 512):   (128, 128, 512),
    ("cws", 4096, 1024, 1024): (256, 128, 512),
    ("cws", 8192, 65536, 1024): (128, 128, 512),
}

_VMEM_BUDGET = 8 * 2 ** 20   # conservative half of ~16MB/core

# Per-family fp32 working-set models (b1, b2, bd) -> bytes.  Axis naming
# follows choose_blocks: b1 tiles the first problem axis (rows), b2 the
# third (hashes/cols), bd the contraction/dims axis.
_VMEM_MODELS: Dict[str, Callable[[int, int, int], int]] = {
    # x tile + its transposed log u scratch + 3 param tiles
    # + 3 accumulators + 2 output tiles
    "cws": lambda bn, bk, bd: 4 * (2 * bn * bd + 3 * bd * bk + 5 * bn * bk),
    # x tile + transposed log u + 3 regenerated param tiles (scratch,
    # single-buffered — no pipelined second copy) + 3 accumulators
    # + 2 output tiles
    "cws_rng": lambda bn, bk, bd: 4 * (2 * bn * bd + 3 * bd * bk
                                       + 5 * bn * bk),
    # packed-emit twins: 3 fp32 accumulators (best_a/best_i/best_t) plus
    # the packed uint32 output tile of bn*bk*b/32 words — modeled at the
    # widest packed b (8 -> bk/4 words -> bn*bk bytes), so every legal b
    # fits whatever these admit.  Audited against the BlockSpec/scratch
    # footprint the kernels actually declare by repro.analysis.vmem.
    "cws_packed": lambda bn, bk, bd: 4 * (2 * bn * bd + 3 * bd * bk
                                          + 3 * bn * bk) + bn * bk,
    "cws_rng_packed": lambda bn, bk, bd: 4 * (2 * bn * bd + 3 * bd * bk
                                              + 3 * bn * bk) + bn * bk,
    # x tile + y tile, each with its transposed copy, + accumulator
    # + output tile
    "min_sum": lambda bm, bn, bd: 4 * (2 * bm * bd + 2 * bn * bd
                                       + 2 * bm * bn),
}
_FAMILY_ALIASES = {"gram": "min_sum", "cws_hash": "cws", "cws_encode": "cws",
                   "cws_hash_rng": "cws_rng", "cws_encode_rng": "cws_rng",
                   "cws_encode_packed": "cws_packed",
                   "cws_encode_rng_packed": "cws_rng_packed",
                   "minmax_gram": "min_sum"}


def _family(op: str) -> str:
    return _FAMILY_ALIASES.get(op, op)


def vmem_bytes(b1: int, b2: int, bd: int, *, op: str = "cws") -> int:
    return _VMEM_MODELS[_family(op)](b1, b2, bd)


def update_block_table(entries: Dict[Tuple[str, int, int, int],
                                     Tuple[int, int, int]]) -> None:
    BLOCK_TABLE.update({(_family(op), n, d, k): tuple(v)
                        for (op, n, d, k), v in entries.items()})


def save_block_table(path, entries: Dict | None = None) -> None:
    """Persist (a subset of) the block table as JSON: "family:n:d:k" ->
    [b1, b2, bd].  The file round-trips through load_block_table, so a
    measured TPU sweep can be checked in and replayed on any host."""
    entries = BLOCK_TABLE if entries is None else entries
    obj = {f"{op}:{n}:{d}:{k}": list(v)
           for (op, n, d, k), v in sorted(entries.items())}
    pathlib.Path(path).write_text(json.dumps(obj, indent=1))


def load_block_table(path) -> Dict[Tuple[str, int, int, int],
                                   Tuple[int, int, int]]:
    """Load a save_block_table JSON file into BLOCK_TABLE; returns the
    parsed entries."""
    obj = json.loads(pathlib.Path(path).read_text())
    entries = {}
    for key, v in obj.items():
        op, n, d, k = key.split(":")
        entries[(op, int(n), int(d), int(k))] = tuple(int(x) for x in v)
    update_block_table(entries)
    return entries


def _pow2_at_most(v: int, lo: int, hi: int) -> int:
    p = lo
    while p * 2 <= min(v, hi):
        p *= 2
    return p


def _bucket(v: int) -> int:
    p = 1
    while p < v:
        p *= 2
    return p


def table_key(op: str, n: int, d: int, k: int) -> Tuple[str, int, int, int]:
    """The BLOCK_TABLE key for a problem shape: family + pow2-bucketed
    dims.  The PUBLIC way to build keys for update/save_block_table —
    persisted tables stay consistent with choose_blocks lookups even if
    the bucketing scheme changes."""
    return (_family(op), _bucket(n), _bucket(d), _bucket(k))


def block_candidates(n: int, d: int, k: int, *,
                     op: str = "cws") -> Tuple[Tuple[int, int, int], ...]:
    """The measured-autotune sweep grid for one problem shape: every pow2
    (b1, b2, bd) combination at or below the problem dims whose working
    set fits the VMEM budget, with b1/b2 at or above the fp32 native tile
    (8, 128) when the problem allows.  Shared by tools/autotune_blocks.py
    so the harness and the heuristic agree on the legal space."""
    fam = _family(op)
    b1s = [b for b in (8, 16, 32, 64, 128, 256) if b <= max(n, 8)]
    b2s = [b for b in (128, 256, 512) if b <= max(k, 128)]
    bds = [b for b in (128, 256, 512, 1024, 2048, 4096) if b <= max(d, 128)]
    out = []
    for b1 in b1s:
        for b2 in b2s:
            for bd in bds:
                if _VMEM_MODELS[fam](b1, b2, bd) <= _VMEM_BUDGET:
                    out.append((b1, b2, bd))
    return tuple(out)


def choose_blocks(n: int, d: int, k: int, *,
                  op: str = "cws") -> Tuple[int, int, int]:
    """(b1, b2, bd) for a kernel family at problem size (n, D, k) —
    (bn, bk, bd) for the cws families, (bm, bn, bd) for min_sum.

    Consults the autotune table first (family + pow2-bucketed key), then
    a VMEM heuristic: start from the VPU-friendly (128, 128, 4096)
    ceiling, clamp to the problem, and shrink bd -> b1 -> b2 until the
    family's working-set model fits the budget.  Never returns a block
    below the fp32 (8, 128) native tile unless the problem itself is
    smaller.
    """
    fam = _family(op)
    key = table_key(op, n, d, k)
    if key in BLOCK_TABLE:
        b1, b2, bd = BLOCK_TABLE[key]
        return min(b1, n), min(b2, k), min(bd, d)
    model = _VMEM_MODELS[fam]
    b1 = _pow2_at_most(n, 1, 128)
    b2 = _pow2_at_most(k, 1, 128)
    # bd ceiling of 4096 lets the parameter fetch amortize on huge-D data
    # (the paper's 65536-dim word vectors); the budget loops below bring
    # it back down when the (b1, b2) tile leaves too little VMEM.
    bd = _pow2_at_most(d, 1, 4096)
    while model(b1, b2, bd) > _VMEM_BUDGET and bd > 128:
        bd //= 2
    while model(b1, b2, bd) > _VMEM_BUDGET and b1 > 8:
        b1 //= 2
    while model(b1, b2, bd) > _VMEM_BUDGET and b2 > 8:
        b2 //= 2
    return b1, b2, bd


# ---------------------------------------------------------------------------
# serving shape buckets
# ---------------------------------------------------------------------------

# Padded request-batch shapes the online serving runner pre-compiles, per
# kernel family (the block table's sibling: blocks tile ONE launch, buckets
# enumerate WHICH launch shapes exist).  Every incoming micro-batch is
# padded up to the smallest bucket that holds it, so mixed traffic over B
# buckets compiles exactly B fused featurize+score executables — the
# serving-side twin of the streaming single-compile invariant (DESIGN.md
# §9).  Measured sweeps (latency-vs-pad-waste on real hardware) refine the
# default ladder per family via update_serve_buckets / load_serve_buckets,
# exactly like the autotuned block table.
DEFAULT_SERVE_BUCKETS: Tuple[int, ...] = (1, 8, 32, 128, 512)

SERVE_BUCKET_TABLE: Dict[str, Tuple[int, ...]] = {}


def _check_buckets(buckets) -> Tuple[int, ...]:
    out = tuple(int(b) for b in buckets)
    if not out or any(b <= 0 for b in out) or list(out) != sorted(set(out)):
        raise ValueError(
            f"serve buckets must be a strictly increasing tuple of "
            f"positive row counts; got {buckets!r}")
    return out


def serve_buckets(op: str = "cws") -> Tuple[int, ...]:
    """The padded-batch ladder the serving runner compiles for ``op``'s
    family: the persisted per-family entry if a sweep installed one, else
    the default ladder."""
    return SERVE_BUCKET_TABLE.get(_family(op), DEFAULT_SERVE_BUCKETS)


def update_serve_buckets(entries: Dict[str, Tuple[int, ...]]) -> None:
    SERVE_BUCKET_TABLE.update(
        {_family(op): _check_buckets(v) for op, v in entries.items()})


def save_serve_buckets(path, entries: Dict | None = None) -> None:
    """Persist the bucket table as JSON ("family" -> [rows...]), next to
    the block table format; round-trips through load_serve_buckets so a
    measured ladder can be checked in and replayed on any host."""
    entries = SERVE_BUCKET_TABLE if entries is None else entries
    obj = {op: list(v) for op, v in sorted(entries.items())}
    pathlib.Path(path).write_text(json.dumps(obj, indent=1))


def load_serve_buckets(path) -> Dict[str, Tuple[int, ...]]:
    """Load a save_serve_buckets JSON file into SERVE_BUCKET_TABLE;
    returns the parsed entries."""
    obj = json.loads(pathlib.Path(path).read_text())
    entries = {op: tuple(int(x) for x in v) for op, v in obj.items()}
    update_serve_buckets(entries)
    return entries


# ---------------------------------------------------------------------------
# introspection surface (consumed by repro.analysis / tools/kernel_lint)
# ---------------------------------------------------------------------------
# The registry is the single place that knows which op families exist, what
# VMEM model each claims, and (via the hooks below) how to build a traceable
# launch for any block choice plus which jitted/shard_mapped entry points
# declare donation or collectives.  Kernel and pipeline modules self-register
# against these hooks at import, so a new op family that skips any of them is
# caught mechanically by the completeness check rather than per-PR review.


def registered_ops() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def lookup(op: str, name: str) -> KernelImpl:
    """Like resolve, but without the backend-availability gate — for
    introspection (signature checks) of impls the current host cannot
    run."""
    return _REGISTRY[op][name]


def family(op: str) -> str:
    """Public alias-resolution: op name -> VMEM-model family name."""
    return _family(op)


def model_families() -> Tuple[str, ...]:
    return tuple(sorted(_VMEM_MODELS))


def vmem_budget() -> int:
    return _VMEM_BUDGET


def has_vmem_model(op: str) -> bool:
    return _family(op) in _VMEM_MODELS


@dataclasses.dataclass(frozen=True)
class LaunchProbe:
    """A recipe for tracing one family member at a chosen block size.

    ``build(b1, b2, bd)`` returns ``(fn, args, blocks)`` where tracing
    ``fn(*args)`` (args may be ShapeDtypeStructs — nothing executes)
    contains at least one pallas_call whose tile sizes are exactly
    ``blocks``, the post-legalization (b1, b2, bd) the kernel will use.
    Probe shapes are sized so no block is clamped and every axis has a
    ragged tail, which makes the same trace serve both the VMEM audit and
    the emit-coverage check.
    """
    family: str
    op: str
    build: Callable[[int, int, int], tuple]


_PROBES: Dict[str, List[LaunchProbe]] = {}


def register_probe(fam: str, *, op: str):
    """Decorator: register a LaunchProbe builder for a model family."""
    def deco(build: Callable) -> Callable:
        _PROBES.setdefault(fam, []).append(
            LaunchProbe(family=fam, op=op, build=build))
        return build
    return deco


def family_probes(fam: str) -> Tuple[LaunchProbe, ...]:
    return tuple(_PROBES.get(fam, ()))


def probe_families() -> Tuple[str, ...]:
    return tuple(sorted(_PROBES))


@dataclasses.dataclass(frozen=True)
class AnalysisSite:
    """A named entry point the analyzer audits: ``build()`` returns a
    check-specific case object (see repro.analysis.donation/collectives).
    Builders are lazy — they may construct pipelines/meshes — and must be
    cheap enough to run under CI."""
    name: str
    build: Callable[[], object]


_DONATION_SITES: Dict[str, AnalysisSite] = {}
_COLLECTIVE_SITES: Dict[str, AnalysisSite] = {}


def register_donation_site(name: str):
    def deco(build: Callable) -> Callable:
        _DONATION_SITES[name] = AnalysisSite(name=name, build=build)
        return build
    return deco


def donation_sites() -> Tuple[AnalysisSite, ...]:
    return tuple(_DONATION_SITES[k] for k in sorted(_DONATION_SITES))


def register_collective_site(name: str):
    def deco(build: Callable) -> Callable:
        _COLLECTIVE_SITES[name] = AnalysisSite(name=name, build=build)
        return build
    return deco


def collective_sites() -> Tuple[AnalysisSite, ...]:
    return tuple(_COLLECTIVE_SITES[k] for k in sorted(_COLLECTIVE_SITES))


_NUMERICS_SITES: Dict[str, AnalysisSite] = {}


def register_numerics_site(name: str):
    """Decorator: register a numerics-audit site.  ``build()`` returns a
    dict with ``fn`` and ``args`` (ShapeDtypeStructs, concrete arrays, or
    analysis.intervals.IVal range seeds) plus optional knobs:
    ``allow_wrap`` (modular integer arithmetic is intended — threefry),
    ``allow_narrow`` (blessed float narrowings, e.g.
    ``("float32->bfloat16",)``), ``allow`` (blessed determinism prims,
    e.g. ``("scatter-add",)``), and ``checks`` (subset of the numerics
    checks to run; default all three)."""
    def deco(build: Callable) -> Callable:
        _NUMERICS_SITES[name] = AnalysisSite(name=name, build=build)
        return build
    return deco


def numerics_sites() -> Tuple[AnalysisSite, ...]:
    return tuple(_NUMERICS_SITES[k] for k in sorted(_NUMERICS_SITES))


@dataclasses.dataclass(frozen=True)
class TrioProbe:
    """A recipe for signature-checking one op's impl trio: ``build()``
    returns ``(args, kwargs)`` such that every impl in ``impls`` accepts
    ``impl.fn(*args, **kwargs)`` under jax.eval_shape (args may be
    ShapeDtypeStructs — nothing executes).  The determinism check
    requires the resulting output shape/dtype trees to agree exactly."""
    op: str
    impls: Tuple[str, ...]
    build: Callable[[], tuple]


_TRIO_PROBES: Dict[str, TrioProbe] = {}


def register_trio(op: str, *, impls: Tuple[str, ...] = (
        "pallas", "pallas-interpret", "reference")):
    """Decorator: register a trio-signature probe for ``op``."""
    def deco(build: Callable) -> Callable:
        _TRIO_PROBES[op] = TrioProbe(op=op, impls=tuple(impls), build=build)
        return build
    return deco


def trio_probes() -> Tuple[TrioProbe, ...]:
    return tuple(_TRIO_PROBES[k] for k in sorted(_TRIO_PROBES))
