"""Pallas TPU flash attention (causal + sliding window, GQA-native).

Scores/probs live in VMEM scratch and never round-trip HBM — the fix for
the dominant memory-roofline term of every *_prefill cell (pure-JAX
chunked attention materializes each (q, kv) score block to HBM between
the two dots; measured 175.8s of HBM time vs 4.4s of compute on
musicgen/prefill_32k — EXPERIMENTS.md §Perf).

Layout: grid (batch, flat_head, q_blocks, kv_blocks), kv innermost.
GQA without repeating K/V: the k/v BlockSpec index_map sends flat head h
to kv head h // (H // G). Running (m, l, acc) accumulators persist in
VMEM scratch across the kv steps (same pattern as cws_hash.py);
the out-of-range kv blocks of the causal/window mask are skipped with
@pl.when (zero FLOPs, zero bytes).

Training uses ``flash_attention`` (custom_vjp): forward = this kernel,
backward = recompute via the pure-JAX chunked path (flash semantics: no
probs are saved). On this CPU container the kernel runs in interpret
mode; on TPU it lowers to Mosaic.

Sequence-parallel wrappers (the production-mesh paths, DESIGN.md §11/§12):
``sharded_flash_attention`` all-gathers K/V over the seq axes (GSPMD);
``ring_flash_attention`` keeps K/V sharded and rotates shards with
``jax.lax.ppermute``, double-buffered so each step's collective overlaps
the previous step's flash loop — the online-softmax (m, l, acc) state is
carried across ring steps by the block-resumable ``flash_attention_step``.
``use_ring`` is the routing predicate models/attention.py consults.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import registry

NEG_INF = -1e30


def _block_update(q_ref, k_ref, v_ref, m_sc, l_sc, acc_sc, q_off, k_off, *,
                  scale: float, window: int, blk_q: int, blk_k: int,
                  k_local_off=None, k_valid: int = 0):
    """One online-softmax step against a (blk_q, blk_k) score tile.

    ``q_off``/``k_off`` are GLOBAL sequence positions of tile row/col 0.
    ``k_valid`` > 0 additionally masks k rows whose LOCAL index
    (``k_local_off + col``) falls in the zero-padding of a k shard —
    ring steps must not let pad rows impersonate the next shard's
    positions."""
    q = q_ref[0, :, 0, :].astype(jnp.float32)      # (blk_q, d)
    k = k_ref[0, :, 0, :].astype(jnp.float32)      # (blk_k, d)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    iq = jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0) + q_off
    ik = jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1) + k_off
    mask = ik <= iq
    if window > 0:
        mask = jnp.logical_and(mask, ik > iq - window)
    if k_valid > 0:
        loc = jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1) + \
            k_local_off
        mask = jnp.logical_and(mask, loc < k_valid)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = corr * l_sc[...] + p.sum(axis=1, keepdims=True)
    acc_sc[...] = corr * acc_sc[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new


def _flash_kernel(q_ref, k_ref, v_ref, qb_ref, o_ref, m_sc, l_sc, acc_sc, *,
                  scale: float, window: int, blk_q: int, blk_k: int,
                  n_kv: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    # q positions are global: qb (SMEM scalar) is the offset of q row 0
    # in the full sequence — 0 unsharded, shard_index * shard_len under
    # the sequence-parallel shard_map wrapper (k/v stay full-length).
    q_off = qi * blk_q + qb_ref[0]
    k_off = ki * blk_k

    @pl.when(ki == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc[...], NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc[...])
        acc_sc[...] = jnp.zeros_like(acc_sc[...])

    # causal/window block skip (static grid, dynamic predicate)
    needed = k_off <= q_off + blk_q - 1
    if window > 0:
        needed = jnp.logical_and(needed,
                                 k_off + blk_k - 1 > q_off - window)

    @pl.when(needed)
    def _compute():
        _block_update(q_ref, k_ref, v_ref, m_sc, l_sc, acc_sc, q_off, k_off,
                      scale=scale, window=window, blk_q=blk_q, blk_k=blk_k)

    @pl.when(ki == n_kv - 1)
    def _emit():
        o_ref[0, :, 0, :] = (acc_sc[...] /
                             jnp.maximum(l_sc[...], 1e-30)
                             ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "blk_q", "blk_k",
                                             "interpret"))
def flash_attention_fwd(q, k, v, *, window: int = 0, blk_q: int = 256,
                        blk_k: int = 256, interpret: bool = False,
                        q_base=None):
    """q: (B, Sq, H, D); k/v: (B, Sk, G, D) with H % G == 0 -> (B, Sq, H, D).

    ``q_base`` (traced int32 scalar, default 0) is the GLOBAL position of
    q row 0: the causal/window mask compares ``q_base + local_row``
    against the k positions.  The sequence-parallel shard_map wrapper
    (``sharded_flash_attention``) passes each shard's offset here so
    every device masks against true sequence coordinates; Sq may then be
    a shard of the full Sk."""
    b, sq0, h, d = q.shape
    sk0 = k.shape[1]
    g = k.shape[2]
    r = h // g
    blk_q = min(blk_q, sq0)
    blk_k = min(blk_k, sk0)
    pad_q = (-sq0) % blk_q
    pad_k = (-sk0) % blk_k
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sq, sk = qp.shape[1], kp.shape[1]
    n_q, n_kv = sq // blk_q, sk // blk_k
    qb = jnp.zeros((1,), jnp.int32) if q_base is None else \
        jnp.asarray(q_base, jnp.int32).reshape((1,))

    kernel = functools.partial(
        _flash_kernel, scale=d ** -0.5, window=window,
        blk_q=blk_q, blk_k=blk_k, n_kv=n_kv)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, blk_q, 1, d), lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
            pl.BlockSpec((1, blk_k, 1, d),
                         lambda bi, hi, qi, ki, r=r: (bi, ki, hi // r, 0)),
            pl.BlockSpec((1, blk_k, 1, d),
                         lambda bi, hi, qi, ki, r=r: (bi, ki, hi // r, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # q_base scalar
        ],
        out_specs=pl.BlockSpec((1, blk_q, 1, d),
                               lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running max
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((blk_q, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp, qb)
    return out[:, :sq0]


def _flash_carry_kernel(q_ref, k_ref, v_ref, qb_ref, kb_ref,
                        m_in_ref, l_in_ref, acc_in_ref,
                        m_out_ref, l_out_ref, acc_out_ref,
                        m_sc, l_sc, acc_sc, *,
                        scale: float, window: int, blk_q: int, blk_k: int,
                        n_kv: int, k_valid: int):
    """Block-RESUMABLE flash step: identical inner loop to _flash_kernel,
    but the (m, l, acc) softmax state enters as inputs and leaves as
    outputs (un-normalized) instead of being zero-initialized and
    normalized in place.  The ring schedule chains N of these launches,
    one per K/V shard, carrying the state across steps exactly as the
    base kernel carries it across k-blocks.  ``kb`` (SMEM scalar) is the
    GLOBAL position of this k shard's row 0 — the per-step ``k_base``
    twin of ``q_base``; ``k_valid`` (static) is the shard's true length,
    so zero-pad rows never alias the next shard's positions."""
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    q_off = qi * blk_q + qb_ref[0]
    k_off = ki * blk_k + kb_ref[0]

    @pl.when(ki == 0)
    def _load_carry():
        m_sc[...] = m_in_ref[0, :, 0, :]
        l_sc[...] = l_in_ref[0, :, 0, :]
        acc_sc[...] = acc_in_ref[0, :, 0, :]

    needed = jnp.logical_and(k_off <= q_off + blk_q - 1,
                             ki * blk_k < k_valid)
    if window > 0:
        needed = jnp.logical_and(needed,
                                 k_off + blk_k - 1 > q_off - window)

    @pl.when(needed)
    def _compute():
        _block_update(q_ref, k_ref, v_ref, m_sc, l_sc, acc_sc, q_off, k_off,
                      scale=scale, window=window, blk_q=blk_q, blk_k=blk_k,
                      k_local_off=ki * blk_k, k_valid=k_valid)

    @pl.when(ki == n_kv - 1)
    def _emit_carry():
        m_out_ref[0, :, 0, :] = m_sc[...]
        l_out_ref[0, :, 0, :] = l_sc[...]
        acc_out_ref[0, :, 0, :] = acc_sc[...]


@functools.partial(jax.jit, static_argnames=("window", "blk_q", "blk_k",
                                             "interpret"))
def flash_attention_step(q, k, v, carry, *, q_base, k_base,
                         window: int = 0, blk_q: int = 256,
                         blk_k: int = 256, interpret: bool = False):
    """One ring step: fold the K/V block ``k/v`` (global row 0 at
    ``k_base``) into the carried online-softmax state for ``q``.

    q: (B, Sq, H, D) with Sq % blk_q == 0 (the ring wrapper pads once);
    k/v: (B, Sk, G, D), padded here to blk_k with pad rows masked out.
    ``carry`` is (m, l, acc) of shapes ((B, Sq, H, 1), (B, Sq, H, 1),
    (B, Sq, H, D)) fp32, or None to start a fresh accumulation.  Returns
    the updated carry; finalize with ``acc / max(l, tiny)``."""
    b, sq, h, d = q.shape
    sk0 = k.shape[1]
    g = k.shape[2]
    r = h // g
    blk_q = min(blk_q, sq)
    if sq % blk_q:
        raise ValueError(f"Sq {sq} must divide by blk_q {blk_q} so the "
                         f"carry keeps one block shape across ring steps")
    blk_k = min(blk_k, sk0)
    pad_k = (-sk0) % blk_k
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    n_q, n_kv = sq // blk_q, kp.shape[1] // blk_k
    if carry is None:
        carry = (jnp.full((b, sq, h, 1), NEG_INF, jnp.float32),
                 jnp.zeros((b, sq, h, 1), jnp.float32),
                 jnp.zeros((b, sq, h, d), jnp.float32))
    m0, l0, acc0 = carry
    qb = jnp.asarray(q_base, jnp.int32).reshape((1,))
    kb = jnp.asarray(k_base, jnp.int32).reshape((1,))

    kernel = functools.partial(
        _flash_carry_kernel, scale=d ** -0.5, window=window,
        blk_q=blk_q, blk_k=blk_k, n_kv=n_kv, k_valid=sk0)
    state_spec = pl.BlockSpec((1, blk_q, 1, 1),
                              lambda bi, hi, qi, ki: (bi, qi, hi, 0))
    acc_spec = pl.BlockSpec((1, blk_q, 1, d),
                            lambda bi, hi, qi, ki: (bi, qi, hi, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, blk_q, 1, d), lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
            pl.BlockSpec((1, blk_k, 1, d),
                         lambda bi, hi, qi, ki, r=r: (bi, ki, hi // r, 0)),
            pl.BlockSpec((1, blk_k, 1, d),
                         lambda bi, hi, qi, ki, r=r: (bi, ki, hi // r, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),   # q_base scalar
            pl.BlockSpec(memory_space=pltpu.SMEM),   # k_base scalar
            state_spec, state_spec, acc_spec,
        ],
        out_specs=(state_spec, state_spec, acc_spec),
        out_shape=(jax.ShapeDtypeStruct((b, sq, h, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, sq, h, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, sq, h, d), jnp.float32)),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running max
            pltpu.VMEM((blk_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((blk_q, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, kp, vp, qb, kb, m0, l0, acc0)


def _ref_bwd_fn(q, k, v, window, chunk):
    """Pure-JAX flash-equivalent used for the recompute backward."""
    from repro.models.attention import _chunked_grouped
    b, s, h, d = q.shape
    g = k.shape[2]
    q5 = q.reshape(b, s, g, h // g, d)
    out = _chunked_grouped(q5, k, v, window=window, chunk=chunk)
    return out.reshape(b, s, h, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, window: int = 0, block: int = 256,
                    interpret: bool = False):
    return flash_attention_fwd(q, k, v, window=window, blk_q=block,
                               blk_k=block, interpret=interpret)


def _fa_fwd(q, k, v, window, block, interpret):
    out = flash_attention_fwd(q, k, v, window=window, blk_q=block,
                              blk_k=block, interpret=interpret)
    return out, (q, k, v)


def _fa_bwd(window, block, interpret, res, g_out):
    q, k, v = res
    _, vjp = jax.vjp(lambda q_, k_, v_: _ref_bwd_fn(q_, k_, v_, window,
                                                    block), q, k, v)
    return vjp(g_out)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# sequence-parallel shard_map wrapper (the production-mesh path)
# ---------------------------------------------------------------------------

def axes_size(mesh, axes) -> int:
    """Product of the mesh axis sizes in ``axes`` (() -> 1).  Alias of
    ``repro.launch.mesh.axis_size`` — the shared helper the attention
    routing predicate consults (kept importable from here for the
    wrapper call sites and tests)."""
    from repro.launch.mesh import axis_size
    return axis_size(mesh, axes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def sharded_flash_attention(q, k, v, window: int, block: int,
                            interpret: bool, mesh, seq_axes: tuple,
                            batch_axes: tuple):
    """Flash attention under tensor/sequence parallelism: pallas_call is
    not GSPMD-partitionable, so the kernel runs per shard inside a
    shard_map — q/out sharded on S over ``seq_axes`` (Megatron-SP), k/v
    replicated over them (GSPMD inserts the all-gather), everything
    sharded on B over ``batch_axes``.  Each shard passes its global
    ``q_base = shard_index * local_len`` into the kernel so causal and
    window masks compare true sequence coordinates.

    Works for ANY head count (llama4's 40, starcoder2's 36,
    recurrentgemma's 10 — none divide the 16-wide model axis, which is
    why head-sharding is not the lever here); requires S % prod(seq_axes)
    == 0, B % prod(batch_axes) == 0 (caller degrades axes that don't
    divide).  Backward = recompute through the pure-JAX chunked path
    (flash semantics — no probs saved), which GSPMD shards on its own.
    """
    return _sfa_fwd_impl(q, k, v, window, block, interpret, mesh,
                         seq_axes, batch_axes)


def _sfa_fwd_impl(q, k, v, window, block, interpret, mesh, seq_axes,
                  batch_axes):
    from jax.sharding import PartitionSpec as P

    local = q.shape[1] // axes_size(mesh, seq_axes)
    bspec = tuple(batch_axes) if batch_axes else None
    sspec = tuple(seq_axes)

    def body(qs, ks, vs):
        base = 0
        for a in seq_axes:
            base = base * mesh.shape[a] + jax.lax.axis_index(a)
        return flash_attention_fwd(
            qs, ks, vs, window=window, blk_q=block, blk_k=block,
            interpret=interpret, q_base=base * local)

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, sspec, None, None),
                  P(bspec, None, None, None),
                  P(bspec, None, None, None)),
        out_specs=P(bspec, sspec, None, None),
        check_vma=False,
    )
    return f(q, k, v)


def _sfa_fwd(q, k, v, window, block, interpret, mesh, seq_axes,
             batch_axes):
    out = _sfa_fwd_impl(q, k, v, window, block, interpret, mesh,
                        seq_axes, batch_axes)
    return out, (q, k, v)


def _sfa_bwd(window, block, interpret, mesh, seq_axes, batch_axes, res,
             g_out):
    return _fa_bwd(window, block, interpret, res, g_out)


sharded_flash_attention.defvjp(_sfa_fwd, _sfa_bwd)


# ---------------------------------------------------------------------------
# ring-scheduled K/V wrapper (compute-overlapped collectives)
# ---------------------------------------------------------------------------

# Below this k/v length the all-gather wrapper wins: a ring of tiny
# shards pays N collective latencies for K/V that would have fit
# per-device anyway.  models/attention.py routes on cfg.attn_ring_min_sk,
# which defaults to this.
RING_MIN_SK = 4096


def use_ring(s_k: int, n_shards: int, *, threshold: int | None = None) -> bool:
    """The ring-vs-all-gather routing predicate: ring only when there is
    a real ring (> 1 shard), K/V divides over it, and the per-device K/V
    saving (~N x) is worth N pipelined collective steps."""
    t = RING_MIN_SK if threshold is None else threshold
    return n_shards > 1 and s_k >= t and s_k % n_shards == 0


def _ring_name(seq_axes):
    return seq_axes[0] if len(seq_axes) == 1 else tuple(seq_axes)


def _shard_index(mesh, seq_axes):
    idx = 0
    for a in seq_axes:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _ring_fwd_impl(q, k, v, window, block, interpret, mesh, seq_axes,
                   batch_axes):
    """shard_map body: q AND k/v sequence-sharded over ``seq_axes`` (the
    all-gather wrapper replicates k/v — that is the memory term the ring
    deletes).  Per ring step s the device consumes the K/V shard it
    currently holds (global offset ``k_base``) while ppermute already
    rotates that shard to the next neighbor for step s+1 — the permute
    carries no data dependency on the step's kernel, so the compiler
    overlaps the collective with the flash inner loop (double-buffered:
    at most two K/V shards resident).  Returns (out, lse); lse feeds the
    reverse-ring backward."""
    from jax.sharding import PartitionSpec as P

    n = axes_size(mesh, seq_axes)
    sq_local = q.shape[1] // n
    sk_local = k.shape[1] // n
    bspec = tuple(batch_axes) if batch_axes else None
    sspec = tuple(seq_axes)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(qs, ks, vs):
        my = _shard_index(mesh, seq_axes)
        b, sql, h, d = qs.shape
        blk_q = min(block, sql)
        pad_q = (-sql) % blk_q
        qp = jnp.pad(qs, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        carry = None
        kv = (ks, vs)
        for s in range(n):
            if s < n - 1:
                # issue the rotation BEFORE consuming the resident shard:
                # no data dependency on this step's kernel, so the
                # transfer for step s+1 overlaps the flash loop of step s
                kv_next = tuple(
                    jax.lax.ppermute(t, _ring_name(seq_axes), perm)
                    for t in kv)
            # after s forward rotations the resident shard is the one
            # that started (my - s) mod n hops upstream
            k_base = jnp.mod(my - s, n) * sk_local
            carry = flash_attention_step(
                qp, kv[0], kv[1], carry, q_base=my * sql, k_base=k_base,
                window=window, blk_q=blk_q, blk_k=block,
                interpret=interpret)
            if s < n - 1:
                kv = kv_next
        m, l, acc = carry
        out = (acc / jnp.maximum(l, 1e-30)).astype(qs.dtype)[:, :sql]
        # per-row logsumexp for the backward recompute; rows no k block
        # ever touched (l == 0) pin lse to 0 so exp(NEG_INF - 0) -> 0
        lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)), 0.0)
        return out, lse[:, :sql, :, 0]

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, sspec, None, None),
                  P(bspec, sspec, None, None),
                  P(bspec, sspec, None, None)),
        out_specs=(P(bspec, sspec, None, None), P(bspec, sspec, None)),
        check_vma=False,
    )
    return f(q, k, v)


def _ring_bwd_impl(q, k, v, out, lse, g_out, window, block, interpret,
                   mesh, seq_axes, batch_axes):
    """Reverse ring with recompute (flash semantics — no probs saved):
    q/out/lse/dout stay put; (k, v, dk, dv) rotate the OPPOSITE direction
    so after n hops the accumulated dk/dv land back on their home shard.
    Per step each device recomputes its q-block x resident-k-block probs
    from lse and adds its contribution to the traveling dk/dv."""
    from jax.sharding import PartitionSpec as P

    n = axes_size(mesh, seq_axes)
    sk_local = k.shape[1] // n
    bspec = tuple(batch_axes) if batch_axes else None
    sspec = tuple(seq_axes)
    perm_rev = [(i, (i - 1) % n) for i in range(n)]

    def body(qs, ks, vs, os_, lses, gs):
        my = _shard_index(mesh, seq_axes)
        b, sql, h, d = qs.shape
        g = ks.shape[2]
        r = h // g
        scale = d ** -0.5
        q5 = qs.reshape(b, sql, g, r, d).astype(jnp.float32)
        go5 = gs.reshape(b, sql, g, r, d).astype(jnp.float32)
        o5 = os_.reshape(b, sql, g, r, d).astype(jnp.float32)
        # (b, s, g, r) -> (b, g, r, s, 1) to broadcast over score tiles
        lse_t = jnp.transpose(lses.reshape(b, sql, g, r),
                              (0, 2, 3, 1))[..., None]
        delta = jnp.transpose(jnp.sum(go5 * o5, axis=-1),
                              (0, 2, 3, 1))[..., None]
        iq = my * sql + jnp.arange(sql)
        dq = jnp.zeros_like(q5)
        ring = (ks.astype(jnp.float32), vs.astype(jnp.float32),
                jnp.zeros((b, sk_local, g, d), jnp.float32),
                jnp.zeros((b, sk_local, g, d), jnp.float32))
        for s in range(n):
            kf, vf, dk, dv = ring
            ik = jnp.mod(my + s, n) * sk_local + jnp.arange(sk_local)
            mask = ik[None, :] <= iq[:, None]
            if window > 0:
                mask = jnp.logical_and(mask,
                                       ik[None, :] > iq[:, None] - window)
            s_blk = jnp.einsum("bqgrd,bkgd->bgrqk", q5, kf,
                               preferred_element_type=jnp.float32) * scale
            s_blk = jnp.where(mask[None, None, None], s_blk, NEG_INF)
            p = jnp.exp(s_blk - lse_t)
            dv = dv + jnp.einsum("bgrqk,bqgrd->bkgd", p, go5,
                                 preferred_element_type=jnp.float32)
            dp = jnp.einsum("bqgrd,bkgd->bgrqk", go5, vf,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta)
            dq = dq + jnp.einsum("bgrqk,bkgd->bqgrd", ds, kf,
                                 preferred_element_type=jnp.float32) * scale
            dk = dk + jnp.einsum("bgrqk,bqgrd->bkgd", ds, q5,
                                 preferred_element_type=jnp.float32) * scale
            # n reverse rotations total so dk/dv end the loop back home;
            # the last hop moves ONLY them — kf/vf are dead after step n-1
            live = (kf, vf, dk, dv) if s < n - 1 else (dk, dv)
            ring = tuple(jax.lax.ppermute(t, _ring_name(seq_axes), perm_rev)
                         for t in live)
        dk, dv = ring[-2:]
        return (dq.reshape(b, sql, h, d).astype(qs.dtype),
                dk.astype(ks.dtype), dv.astype(vs.dtype))

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, sspec, None, None),) * 3
        + (P(bspec, sspec, None, None), P(bspec, sspec, None),
           P(bspec, sspec, None, None)),
        out_specs=(P(bspec, sspec, None, None),) * 3,
        check_vma=False,
    )
    return f(q, k, v, out, lse, g_out)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def ring_flash_attention(q, k, v, window: int, block: int,
                         interpret: bool, mesh, seq_axes: tuple,
                         batch_axes: tuple):
    """Ring-scheduled flash attention: same contract as
    ``sharded_flash_attention`` (q (B, Sq, H, D), k/v (B, Sk, G, D),
    any head count, Sq and Sk each divisible by the seq-axes product),
    but K/V stay sequence-SHARDED — per-device peak K/V memory is
    O(Sk/N) (x2 for the double buffer) instead of O(Sk), and the
    all-gather serialized ahead of compute becomes N ppermute steps
    pipelined against the flash inner loop (DESIGN.md §12)."""
    out, _ = _ring_fwd_impl(q, k, v, window, block, interpret, mesh,
                            seq_axes, batch_axes)
    return out


def _ring_fwd(q, k, v, window, block, interpret, mesh, seq_axes,
              batch_axes):
    out, lse = _ring_fwd_impl(q, k, v, window, block, interpret, mesh,
                              seq_axes, batch_axes)
    return out, (q, k, v, out, lse)


def _ring_bwd(window, block, interpret, mesh, seq_axes, batch_axes, res,
              g_out):
    q, k, v, out, lse = res
    return _ring_bwd_impl(q, k, v, out, lse, g_out, window, block,
                          interpret, mesh, seq_axes, batch_axes)


ring_flash_attention.defvjp(_ring_fwd, _ring_bwd)


# ---------------------------------------------------------------------------
# analysis sites (repro.analysis / tools/kernel_lint.py)
# ---------------------------------------------------------------------------
# The shard_map'd attention schedules, registered for the collective
# lint: bound axis names, true-permutation ppermutes (the forward AND
# reverse rings), no double reductions.  Sized to whatever device count
# the host exposes, so the 1-dev and forced-8-dev CI runs both audit a
# real mesh.

def _analysis_attn_mesh():
    import numpy as np
    from jax.sharding import Mesh
    devs = np.array(jax.devices())
    return Mesh(devs.reshape(1, -1), ("data", "model"))


@registry.register_numerics_site("flash.accumulators")
def _numerics_site_flash_accumulators():
    # The accumulation contract under bf16 inputs: m/l/acc scratch stays
    # float32, both dots pin preferred_element_type=f32, and the ONLY
    # narrowing is the final intended f32 -> bf16 store (blessed here so
    # any other downcast that sneaks into the kernel still fails).
    q = jax.ShapeDtypeStruct((1, 64, 2, 16), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 64, 2, 16), jnp.bfloat16)

    def fn(q, k, v):
        return flash_attention_fwd(q, k, v, window=0, blk_q=32, blk_k=32,
                                   interpret=True)
    return {"fn": fn, "args": (q, kv, kv),
            "allow_narrow": ("float32->bfloat16",),
            "checks": ("dtype_flow", "determinism")}


@registry.register_collective_site("attention.flash_allgather")
def _collective_site_allgather():
    mesh = _analysis_attn_mesh()
    n = mesh.shape["model"]
    q = jax.ShapeDtypeStruct((1, 8 * n, 2, 8), jnp.float32)

    def fn(q, k, v):
        return sharded_flash_attention(q, k, v, 0, 4, True, mesh,
                                       ("model",), ())
    return {"fn": fn, "args": (q, q, q), "expected_psums": 0}


@registry.register_collective_site("attention.flash_ring")
def _collective_site_ring():
    mesh = _analysis_attn_mesh()
    n = mesh.shape["model"]
    q = jax.ShapeDtypeStruct((1, 8 * n, 2, 8), jnp.float32)

    def fn(q, k, v):
        # grad drives the reverse-ring backward through the custom_vjp
        def loss(q, k, v):
            return ring_flash_attention(q, k, v, 0, 4, True, mesh,
                                        ("model",), ()).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    return {"fn": fn, "args": (q, q, q), "expected_psums": 0}
