"""Registry-backed public ops: one call site per logical kernel.

Each op has ``pallas`` / ``pallas-interpret`` / ``reference``
implementations registered in :mod:`repro.kernels.registry`; dispatch is
by backend capability (Mosaic on TPU, pure-JAX reference on CPU), with the
interpreter available everywhere as the kernel-body correctness path — the
BlockSpec tiling it executes is exactly what ships to TPU.

Block sizes default to ``registry.choose_blocks`` (autotune table +
VMEM-budget heuristic keyed on (n, D, k)) instead of hardcoded constants;
explicit ``bn/bk/bd`` kwargs still pin them for tests and sweeps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.cws import CWSParams
from repro.core import cws as core_cws
from repro.core import hashing as core_hashing
from repro.kernels import ref
from repro.kernels import registry
from repro.kernels.cws_hash import (cws_pallas, hash_count, param_source,
                                    _packed_bk)
from repro.kernels.minmax_gram import minmax_gram_pallas, min_sum_pallas


def _blocks(n: int, d: int, k: int, bn, bk, bd, *, op: str):
    hn, hk, hd = registry.choose_blocks(n, d, k, op=op)
    return (bn or hn, bk or hk, bd or hd)


# ---------------------------------------------------------------------------
# implementation registration
# ---------------------------------------------------------------------------

@registry.register("cws", "pallas", requires=("tpu",))
def _cws_tpu(x, state, *, emit, num_hashes, b_i, b_t, bn, bk, bd):
    return cws_pallas(x, state, num_hashes, emit=emit, b_i=b_i, b_t=b_t,
                      bn=bn, bk=bk, bd=bd, interpret=False)


@registry.register("cws", "pallas-interpret")
def _cws_interp(x, state, *, emit, num_hashes, b_i, b_t, bn, bk, bd):
    return cws_pallas(x, state, num_hashes, emit=emit, b_i=b_i, b_t=b_t,
                      bn=bn, bk=bk, bd=bd, interpret=True)


@registry.register("cws", "reference")
def _cws_ref(x, state, *, emit, num_hashes, b_i, b_t, bn, bk, bd):
    # the staged composition, kept in ONE place as the semantic
    # definition: the chunked pure-JAX hash (block kwargs map onto its
    # chunk sizes; regenerated parameters follow the counter spec of
    # repro.core.regen, bit-identical to the kernel's, DESIGN.md §7),
    # then the b-bit encode, then offsets or packing
    chunks = dict(row_block=max(bn, 8), hash_block=max(bk, 8))
    if param_source(state) == "stored":
        i_star, t_star = core_cws.cws_hash(x, state, **chunks)
    else:
        i_star, t_star = core_cws.cws_hash_regen(x, state, num_hashes,
                                                 **chunks)
    if emit == "hash":
        return i_star, t_star
    codes = core_hashing.encode(i_star, t_star, b_i=b_i, b_t=b_t)
    if emit == "packed":
        return core_hashing.pack_codes(codes, b=b_i + b_t)
    return core_hashing.feature_indices(codes, b_i=b_i, b_t=b_t)


@registry.register("minmax_gram", "pallas", requires=("tpu",))
def _minmax_gram_tpu(x, y, *, bm, bn, bd):
    return minmax_gram_pallas(x, y, bm=bm, bn=bn, bd=bd, interpret=False)


@registry.register("minmax_gram", "pallas-interpret")
def _minmax_gram_interp(x, y, *, bm, bn, bd):
    return minmax_gram_pallas(x, y, bm=bm, bn=bn, bd=bd, interpret=True)


@registry.register("minmax_gram", "reference")
def _minmax_gram_ref(x, y, *, bm, bn, bd):
    return ref.minmax_gram_ref(x, y)


@registry.register("min_sum", "pallas", requires=("tpu",))
def _min_sum_tpu(x, y, *, bm, bn, bd):
    return min_sum_pallas(x, y, bm=bm, bn=bn, bd=bd, interpret=False)


@registry.register("min_sum", "pallas-interpret")
def _min_sum_interp(x, y, *, bm, bn, bd):
    return min_sum_pallas(x, y, bm=bm, bn=bn, bd=bd, interpret=True)


@registry.register("min_sum", "reference")
def _min_sum_ref(x, y, *, bm, bn, bd):
    return ref.min_sum_ref(x, y)


# --- sequence-parallel attention family ----------------------------------
#
# Impl names differ from the cws/min_sum pattern because the interesting
# axis here is the COLLECTIVE schedule, not the kernel body: `reference`
# (naive oracle), `flash` (the unsharded Pallas kernel; interpret
# off-TPU), `flash_allgather` (shard_map wrapper, K/V gathered over the
# seq axes) and `flash_ring` (K/V ring schedule with compute-overlapped
# ppermute, DESIGN.md §12).  All four share one signature so benches and
# parity tests swap them by name.

@registry.register("attention", "reference")
def _attention_ref(q, k, v, *, window, block, mesh=None, seq_axes=(),
                   batch_axes=()):
    from repro.models.attention import _naive_grouped
    b, s, h, d = q.shape
    g = k.shape[2]
    q5 = q.reshape(b, s, g, h // g, d)
    return _naive_grouped(q5, k, v, window=window).reshape(b, s, h, d)


@registry.register("attention", "flash")
def _attention_flash(q, k, v, *, window, block, mesh=None, seq_axes=(),
                     batch_axes=()):
    from repro.kernels.flash_attention import flash_attention
    return flash_attention(q, k, v, window, block, not registry.on_tpu())


@registry.register("attention", "flash_allgather")
def _attention_allgather(q, k, v, *, window, block, mesh, seq_axes,
                         batch_axes=()):
    from repro.kernels.flash_attention import sharded_flash_attention
    return sharded_flash_attention(q, k, v, window, block,
                                   not registry.on_tpu(), mesh,
                                   tuple(seq_axes), tuple(batch_axes))


@registry.register("attention", "flash_ring")
def _attention_ring(q, k, v, *, window, block, mesh, seq_axes,
                    batch_axes=()):
    from repro.kernels.flash_attention import ring_flash_attention
    return ring_flash_attention(q, k, v, window, block,
                                not registry.on_tpu(), mesh,
                                tuple(seq_axes), tuple(batch_axes))


# ---------------------------------------------------------------------------
# public wrappers (stable signatures; dispatch through the registry)
# ---------------------------------------------------------------------------

def _impl_name(interpret: bool | None, impl: str | None) -> str | None:
    """Back-compat shim: the old ``interpret`` kwarg pins the kernel-body
    path; ``impl`` pins a registry name; neither -> capability dispatch
    onto the kernel path (pallas on TPU, interpreter elsewhere — ops.* is
    the kernel-parity layer; use the pipeline for production CPU paths)."""
    if impl is not None:
        return impl
    if interpret is None:
        return registry.pallas_impl()
    return "pallas-interpret" if interpret else "pallas"


def cws(x: jax.Array, state, *, emit: str, num_hashes: int | None = None,
        b_i: int = 0, b_t: int = 0, bn: int | None = None,
        bk: int | None = None, bd: int | None = None,
        interpret: bool | None = None, impl: str | None = None):
    """CWS of x (n, D) nonneg (DESIGN.md §6–§7).

    ``state`` picks the parameter source: stored ``CWSParams``, or PRNG
    key words whose parameters every impl regenerates from the counter
    spec (then ``num_hashes`` gives k).  ``emit`` picks what comes back:
    ``"hash"`` -> (i*, t*) each (n, k) int32; ``"index"`` -> embedding-bag
    indices (n, k) int32 into k * 2^{b_i+b_t} features; ``"packed"`` ->
    (n, ceil(k·b/32)) uint32 words, b = b_i + b_t in {1, 2, 4, 8}.
    Unset blocks come from ``choose_blocks`` for the launch's family."""
    k = hash_count(state, num_hashes)
    bn, bk, bd = _blocks(x.shape[0], x.shape[1], k, bn, bk, bd,
                         op=registry.cws_family(param_source(state), emit))
    fn = registry.resolve("cws", _impl_name(interpret, impl)).fn
    return fn(x, state, emit=emit, num_hashes=k, b_i=b_i, b_t=b_t,
              bn=bn, bk=bk, bd=bd)


def minmax_gram(x: jax.Array, y: jax.Array, *, bm: int | None = None,
                bn: int | None = None, bd: int | None = None,
                interpret: bool | None = None,
                impl: str | None = None) -> jax.Array:
    bm_, bn_, bd_ = _blocks(x.shape[0], x.shape[1], y.shape[0],
                            bm, bn, bd, op="min_sum")
    fn = registry.resolve("minmax_gram", _impl_name(interpret, impl)).fn
    return fn(x, y, bm=bm_, bn=bn_, bd=bd_)


def min_sum(x: jax.Array, y: jax.Array, *, bm: int | None = None,
            bn: int | None = None, bd: int | None = None,
            interpret: bool | None = None,
            impl: str | None = None) -> jax.Array:
    bm_, bn_, bd_ = _blocks(x.shape[0], x.shape[1], y.shape[0],
                            bm, bn, bd, op="min_sum")
    fn = registry.resolve("min_sum", _impl_name(interpret, impl)).fn
    return fn(x, y, bm=bm_, bn=bn_, bd=bd_)


def seq_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  window: int = 0, block: int = 256,
                  impl: str | None = None, mesh=None,
                  seq_axes=("model",), batch_axes=()) -> jax.Array:
    """Registry-dispatched attention: q (B, Sq, H, D), k/v (B, Sk, G, D)
    -> (B, Sq, H, D).  ``impl=None`` picks ``flash`` without a mesh and
    routes ring-vs-all-gather through ``use_ring`` with one; explicit
    names (``reference`` / ``flash`` / ``flash_allgather`` /
    ``flash_ring``) pin a schedule for parity tests and benchmarks."""
    if impl is None:
        if mesh is None:
            impl = "flash"
        else:
            from repro.kernels.flash_attention import use_ring
            from repro.launch.mesh import axis_size
            impl = ("flash_ring"
                    if use_ring(k.shape[1], axis_size(mesh, seq_axes))
                    else "flash_allgather")
    fn = registry.resolve("attention", impl).fn
    return fn(q, k, v, window=window, block=block, mesh=mesh,
              seq_axes=seq_axes, batch_axes=batch_axes)


# re-export oracles for test convenience
cws_hash_ref = ref.cws_hash_ref
minmax_gram_ref = ref.minmax_gram_ref
min_sum_ref = ref.min_sum_ref


# ---------------------------------------------------------------------------
# analysis launch probes (repro.analysis / tools/kernel_lint.py)
# ---------------------------------------------------------------------------
# One LaunchProbe per family member whose BlockSpec+scratch footprint can
# be the family worst case.  Probe shapes are 2x the blocks plus a ragged
# tail on every axis (so nothing clamps AND the pad/coverage logic is
# exercised); args are ShapeDtypeStructs — tracing a probe never
# materializes data or compiles.  The VMEM audit evaluates _VMEM_MODELS
# at the *legalized* blocks each probe returns.

def _probe_sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _probe_shape(b1, b2, bd):
    return 2 * b1 + 3, 2 * bd + 5, 2 * b2 + 3


def _probe_cws(source, emit, b1, b2, bd):
    n, d, k = _probe_shape(b1, b2, bd)
    p = _probe_sds((d, k))
    state = (CWSParams(p, p, p) if source == "stored"
             else jax.random.PRNGKey(0))
    # b_i + b_t = 8 packed: the widest packed b, the footprint the model
    # covers; b_t > 0 unpacked keeps best_t in scratch, as "hash" does
    b_i = 4 if emit == "packed" else 2
    legal = (b1, _packed_bk(b2, k, 8) if emit == "packed" else b2, bd)

    def fn(x, state):
        return cws_pallas(x, state, k, emit=emit, b_i=b_i, b_t=b_i, bn=b1,
                          bk=b2, bd=bd, interpret=True)
    return fn, (_probe_sds((n, d)), state), legal


for _source in registry.CWS_SOURCES:
    for _emit in registry.CWS_EMITS:
        registry.register_probe(registry.cws_family(_source, _emit),
                                op="cws")(
            functools.partial(_probe_cws, _source, _emit))


@registry.register_probe("min_sum", op="min_sum")
def _probe_min_sum(b1, b2, bd):
    m, d, n2 = _probe_shape(b1, b2, bd)

    def fn(x, y):
        return min_sum_pallas(x, y, bm=b1, bn=b2, bd=bd, interpret=True)
    return fn, (_probe_sds((m, d)), _probe_sds((n2, d))), (b1, b2, bd)


# ---------------------------------------------------------------------------
# trio-signature probes (repro.analysis.numerics / tools/kernel_lint.py)
# ---------------------------------------------------------------------------
# One TrioProbe per op: shared ShapeDtypeStruct args every registered impl
# must accept, with output shape/dtype trees required to agree exactly
# under jax.eval_shape (the signature-level half of the bit-identical
# trio guarantee; value parity lives in the equivalence tests).  Shapes
# are small and ragged against the pinned blocks so the padded pallas
# paths and the chunked references all exercise their tails.

_TRIO_X = _probe_sds((19, 23))
_TRIO_P = _probe_sds((23, 17))
_TRIO_KW = dict(bn=8, bk=8, bd=16)
_TRIO_KEY = jax.random.PRNGKey(0)


@registry.register_trio("cws", cases=tuple(
    (s, e) for s in registry.CWS_SOURCES for e in registry.CWS_EMITS))
def _trio_cws(source, emit):
    state = (CWSParams(_TRIO_P, _TRIO_P, _TRIO_P) if source == "stored"
             else _TRIO_KEY)
    b_i = 4 if emit == "packed" else 2
    return (_TRIO_X, state), dict(emit=emit, num_hashes=17, b_i=b_i,
                                  b_t=b_i, **_TRIO_KW)


@registry.register_trio("minmax_gram")
def _trio_minmax_gram():
    return (_probe_sds((19, 23)), _probe_sds((13, 23))), dict(bm=8, bn=8,
                                                              bd=16)


@registry.register_trio("min_sum")
def _trio_min_sum():
    return (_probe_sds((19, 23)), _probe_sds((13, 23))), dict(bm=8, bn=8,
                                                              bd=16)


@registry.register_trio("attention", impls=("reference", "flash"))
def _trio_attention():
    # the mesh-bearing schedules (flash_allgather / flash_ring) carry
    # their own collective-site contracts; signature parity here covers
    # the mesh-free pair every schedule reduces to
    q = _probe_sds((2, 16, 4, 8))
    kv = _probe_sds((2, 16, 2, 8))
    return (q, kv, kv), dict(window=0, block=8)
