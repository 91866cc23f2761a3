"""The one featurization subsystem: CWS sampling -> b-bit encoding ->
embedding-bag indices, as a single dispatchable pipeline.

The paper's end-to-end recipe is a three-stage pipeline, but downstream
learners only ever consume the final bit-truncated feature indices
(b-bit minwise hashing's central observation).  ``FeaturePipeline``
therefore exposes the fused artifact directly:

    pipe = FeaturePipeline.create(key, dim, FeatureSpec(k=512, b_i=8))
    idx  = pipe.features(x)          # (n, k) int32 into pipe.num_features

backed by the registry-dispatched fused kernel (``ops.cws``: Mosaic on
TPU, pure-JAX reference on CPU, Pallas interpreter for kernel-parity
testing).  The staged composition (hash -> encode -> offsets) survives in
two sanctioned places only: the registry's ``reference`` implementation
and ``staged_reference`` below (the test oracle).

Scale features (DESIGN.md §6):
  * row-chunked streaming — ``features`` processes ``row_chunk`` rows per
    kernel launch so peak memory is O(row_chunk * max(D, k)), independent
    of n;
  * buffer donation — each streamed chunk buffer is donated to its launch
    (XLA reuses it for the output; no transient duplication);
  * data-axis sharding — pass ``mesh=`` (see repro.launch.mesh) to
    shard_map the launch over the ``data`` axis: rows split across
    devices, CWS parameters replicated.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cws import (CWSParams, make_cws_params, cws_hash_reference,
                            cws_hash_regen)
from repro.core.hashing import (encode, feature_indices, hashed_dim,
                                check_packed_bits, pack_codes, packed_width,
                                unpack_codes)
from repro.core.regen import key_words
from repro.kernels import ops, registry
from repro.kernels.cws_hash import launch_dims_per_trip, loop_share
from repro.launch.mesh import data_axis_size

Array = jax.Array

# Host span around one chunk's launch in the profiler's trace
# (``jax.profiler``), with the chunk's ``rows``: inert unless a profile is
# being taken, and then on the same clock as the device ops.
LAUNCH_SPAN = "repro.featurize.launch"


@dataclasses.dataclass(frozen=True)
class FeatureSpec:
    """What the downstream learner sees: k hashes, 2^{b_i+b_t} buckets each.

    ``b_i = 0`` keeps i* in full (the paper's "0-bit" refers to t*);
    ``b_t = 0`` discards t* entirely — the paper's proposed scheme, and the
    one the fused kernel serves with zero t* traffic.

    ``packed = True`` switches the pipeline's output format to bit-packed
    codes: ``features``/``launch_chunk``/``feature_chunks`` emit
    ``(n, ceil(k*b/32))`` uint32 words (b = b_i + b_t in {1, 2, 4, 8})
    instead of (n, k) int32 indices — 32/b x less feature traffic, fed
    directly to ``linear_model.bag_logits_packed``.  Requires b_i >= 1
    (packing is a bucketed-code format) — enforced at pipeline
    construction."""
    num_hashes: int
    b_i: int
    b_t: int = 0
    packed: bool = False

    @property
    def width(self) -> int:
        return 1 << (self.b_i + self.b_t)

    @property
    def bits(self) -> int:
        """Code bit width b = b_i + b_t (the packed formats' b)."""
        return self.b_i + self.b_t

    @property
    def packed_words(self) -> int:
        """uint32 words per row in packed mode: ceil(k*b/32)."""
        return packed_width(self.num_hashes, self.bits)

    @property
    def num_features(self) -> int:
        return hashed_dim(self.num_hashes, self.b_i, self.b_t)


class FeaturePipeline:
    """CWS featurization bound to one (params, spec) pair — or, in
    PARAM-FREE mode, to one (PRNG key, spec) pair.

    ``impl`` pins a registry implementation name (``pallas``,
    ``pallas-interpret``, ``reference``); None dispatches by backend
    capability.  ``blocks`` pins (bn, bk, bd); None consults the autotune
    table/heuristic per launch shape.

    Param-free mode (``create_regen``) stores only two uint32 key words
    instead of the 3·D·k fp32 parameter matrices: every launch regenerates
    its parameter tiles in-kernel from the counter spec (DESIGN.md §7), so
    parameter HBM traffic is zero and a fresh-parameter Monte-Carlo rep
    (fig45/fig6 style) is just ``pipe.with_key(new_key)`` — no
    materialization, no new device buffers.
    """

    def __init__(self, params: Optional[CWSParams], spec: FeatureSpec, *,
                 impl: Optional[str] = None,
                 blocks: Optional[Tuple[int, int, int]] = None,
                 row_chunk: int = 8192,
                 regen_key: Optional[Array] = None,
                 dim: Optional[int] = None):
        if params is None:
            if regen_key is None or dim is None:
                raise ValueError(
                    "param-free mode needs regen_key and dim "
                    "(use FeaturePipeline.create_regen)")
            k0, k1 = key_words(regen_key)
            self._key_words = jnp.stack([k0, k1])
            self.dim = dim
        elif regen_key is not None:
            raise ValueError("pass either params or regen_key, not both")
        else:
            if spec.num_hashes > params.num_hashes:
                raise ValueError(
                    f"spec asks for {spec.num_hashes} hashes but params "
                    f"carry only {params.num_hashes}")
            self._key_words = None
            self.dim = params.dim
        self.params = params
        self.spec = spec
        if spec.packed:
            # loud at construction, not first launch: packed output is a
            # bucketed-code format (b_i >= 1) at a word-tiling b
            self._require_bucketed("FeatureSpec(packed=True)")
            check_packed_bits(spec.bits)
        self.impl = impl
        self.blocks = blocks
        self.row_chunk = row_chunk
        self._donating_chunk_fn = None
        self._scoring_fn = None        # fused featurize+score, non-donating
        self._sharded_fns = {}         # (mesh, donate) -> jitted shard_map
        self._sliced_state = None      # cache: k-prefix slice of params
        self._sliced_from = None

    @classmethod
    def create(cls, key: Array, dim: int, spec: FeatureSpec,
               **kw) -> "FeaturePipeline":
        return cls(make_cws_params(key, dim, spec.num_hashes), spec, **kw)

    @classmethod
    def create_regen(cls, key: Array, dim: int, spec: FeatureSpec,
                     **kw) -> "FeaturePipeline":
        """Param-free pipeline: stores only ``key`` (two uint32 words)."""
        return cls(None, spec, regen_key=key, dim=dim, **kw)

    def with_key(self, key: Array) -> "FeaturePipeline":
        """A fresh-parameter replica of a param-free pipeline (Monte-Carlo
        reps draw a new key instead of new parameter matrices)."""
        if not self.param_free:
            raise ValueError("with_key is for param-free pipelines; "
                             "stored-param pipelines rebuild via create()")
        return FeaturePipeline(None, self.spec, impl=self.impl,
                               blocks=self.blocks, row_chunk=self.row_chunk,
                               regen_key=key, dim=self.dim)

    @property
    def param_free(self) -> bool:
        return self.params is None

    @property
    def num_features(self) -> int:
        return self.spec.num_features

    def fingerprint(self) -> dict:
        """Identity of the feature space AND the exact random parameters
        behind it, as a JSON-able dict: the FeatureSpec fields, the input
        dim, the mode, and a content digest (crc32) of the launch state —
        the two key words in param-free mode, the (sliced) CWS matrices
        otherwise.  The streamed trainer stamps this into every
        checkpoint so a resume against a DIFFERENT pipeline (other key,
        other spec, other dim) fails loudly instead of silently training
        on garbage indices."""
        import zlib
        if self.param_free:
            data = np.asarray(self._key_words).tobytes()
        else:
            s = self._state()
            data = b"".join(np.asarray(a).tobytes()
                            for a in (s.r, s.log_c, s.beta))
        return {"spec": dataclasses.asdict(self.spec),
                "dim": int(self.dim),
                "param_free": bool(self.param_free),
                "digest": f"{zlib.crc32(data) & 0xFFFFFFFF:08x}"}

    # -- single-launch building block ----------------------------------

    def _launch(self, x: Array) -> Array:
        return self._cws(x, self._state(), self.emit)

    def _cws(self, x: Array, state, emit: str):
        """One kernel launch on ``state`` (CWSParams or key words) that
        emits ``emit``, at the pipeline's blocks and impl."""
        bn, bk, bd = self._blocks_for(x.shape[0], x.shape[1], emit)
        return ops.cws(x, state, emit=emit, num_hashes=self.spec.num_hashes,
                       b_i=self.spec.b_i, b_t=self.spec.b_t, bn=bn, bk=bk,
                       bd=bd, impl=self._resolved_impl())

    def _state(self):
        """The replicated launch state: the (sliced) CWSParams matrices,
        or just the two uint32 key words in param-free mode.  The
        k-prefix slice is cached (keyed on params identity) so per-batch
        launch_chunk calls don't re-slice three (D, k) matrices every
        training step."""
        if self.param_free:
            return self._key_words
        if self.spec.num_hashes == self.params.num_hashes:
            return self.params
        if self._sliced_from is not self.params:
            self._sliced_from = self.params
            self._sliced_state = self.params.slice_hashes(
                0, self.spec.num_hashes)
        return self._sliced_state

    # -- public API ----------------------------------------------------

    def chunk_rows(self, mesh=None) -> int:
        """The ONE streaming chunk shape for a (pipeline, mesh) config:
        ``row_chunk`` unsharded, ``lcm(row_chunk, ndev)`` under a mesh —
        every full chunk splits evenly over the ``data`` axis AND keeps
        the unsharded chunk size as a divisor, so exactly one padded
        chunk shape compiles per config (no per-chunk re-pad to ndev)."""
        if mesh is None:
            return self.row_chunk
        return math.lcm(self.row_chunk, data_axis_size(mesh))

    def launch_chunk(self, xc: Array, *, mesh=None) -> Array:
        """ONE donated kernel launch: xc (m, D) nonneg -> (m, k) int32
        embedding-bag indices.

        The building block behind ``features`` streaming and the streamed
        minibatch trainer (repro.training.linear_trainer): the caller owns
        the batching.  Each distinct m compiles once, so keep m fixed
        across calls (pad ragged tails — all-zero pad rows land in bucket
        0 and slice off cleanly).  On TPU the chunk buffer is donated to
        the launch: hand over a buffer you are done with (a fresh batch
        gather, a slice), never a live input array.

        With ``mesh`` the launch is shard_mapped over the ``data`` axis
        (rows split across devices, hash state replicated); m must divide
        by the axis size so every shard sees the same local shape."""
        self._require_bucketed("launch_chunk")
        if mesh is None:
            return self._chunk_fn()(xc, self._state())
        ndev = data_axis_size(mesh)
        if xc.shape[0] % ndev:
            raise ValueError(
                f"launch_chunk under mesh= needs rows divisible by the "
                f"data axis ({ndev}); got {xc.shape[0]} — pad the chunk "
                f"(chunk_rows(mesh) gives the streaming shape)")
        return self._sharded_chunk_fn(mesh)(xc, self._state())

    def feature_chunks(self, x: Array, *, launch=None, mesh=None):
        """Iterator form of ``features``: yields ``(lo, hi, idx[lo:hi])``
        per ``chunk_rows(mesh)`` rows, so a consumer (the streaming
        trainer, a chunked evaluator) can walk n >> chunk rows without
        ever holding the full (n, k) index matrix.

        A ragged final chunk is padded up to the chunk shape and the pad
        rows sliced off (all-zero rows map to sentinel -> bucket 0, then
        are discarded), so streaming compiles EXACTLY ONE chunk shape —
        no recompile on the tail, sharded or not.  ``launch`` overrides
        the per-chunk callable (tests); default is the donating jitted
        chunk fn, shard_mapped over ``data`` when ``mesh`` is given."""
        self._require_bucketed("feature_chunks")
        n = x.shape[0]
        rows = self.chunk_rows(mesh)
        ndev = 1 if mesh is None else data_axis_size(mesh)
        fn = launch or (self.launch_chunk if mesh is None else
                        functools.partial(self.launch_chunk, mesh=mesh))
        on_device = isinstance(x, jax.Array)
        for lo in range(0, n, rows):
            hi = min(lo + rows, n)
            m = hi - lo
            # streamed ragged tails pad to the full chunk shape (the
            # single-compile invariant); a lone short chunk (n <= rows)
            # pads only to the data-axis multiple it must split into
            target = (rows if (m < rows and n > rows)
                      else m + ((-m) % ndev))
            with jax.profiler.TraceAnnotation(
                    LAUNCH_SPAN, rows=m,
                    cws_loop_share=self.loop_share(target // ndev),
                    cws_dims_per_trip=self.dims_per_trip(target // ndev)):
                # host-resident rows (numpy/memmap) slice on the host, so
                # only the chunk ever crosses to the device
                chunk = (jax.lax.slice_in_dim(x, lo, hi, axis=0)
                         if on_device else jnp.asarray(x[lo:hi]))
                if target > m:
                    chunk = jnp.pad(chunk, ((0, target - m), (0, 0)))
                    out = fn(chunk)[:m]
                elif mesh is not None and launch is None and n <= rows:
                    # lone whole-array chunk: the full-range slice may
                    # alias the caller's live x on some backends — same
                    # policy as _features_sharded, never donate it
                    out = self._sharded_chunk_fn(
                        mesh, donate=False)(chunk, self._state())
                else:
                    out = fn(chunk)
            yield lo, hi, out

    def features(self, x: Array, *, mesh=None) -> Array:
        """x (n, D) nonneg -> embedding-bag indices (n, k) int32 into
        ``num_features`` — or, with ``spec.packed``, bit-packed codes
        (n, ``spec.packed_words``) uint32.  Streams in
        ``chunk_rows(mesh)`` row chunks; with a ``mesh`` every launch is
        shard_mapped over its ``data`` axis.  Each launch's host work
        (slice, pad, enqueue) is one ``LAUNCH_SPAN`` in a profile, with
        the chunk's ``rows`` and the launch's ``cws_loop_share`` and
        ``cws_dims_per_trip``."""
        self._require_bucketed("features")
        n = x.shape[0]
        if n == 0:   # empty stream chunk: nothing to launch
            if self.spec.packed:
                return jnp.zeros((0, self.spec.packed_words), jnp.uint32)
            return jnp.zeros((0, self.spec.num_hashes), jnp.int32)
        if n <= self.chunk_rows(mesh):
            ndev = 1 if mesh is None else data_axis_size(mesh)
            with jax.profiler.TraceAnnotation(
                    LAUNCH_SPAN, rows=n,
                    cws_loop_share=self.loop_share(-(-n // ndev)),
                    cws_dims_per_trip=self.dims_per_trip(-(-n // ndev))):
                return self._launch(x) if mesh is None else \
                    self._features_sharded(x, mesh)
        return self._features_streamed(x, mesh=mesh)

    def hashes(self, x: Array):
        """Staged stage-1 escape hatch for estimator sweeps that reuse one
        hash pass across many (b_i, b_t) encodings: (i*, t*) each (n, k)."""
        if x.shape[0] == 0:
            z = jnp.zeros((0, self.spec.num_hashes), jnp.int32)
            return z, z
        return self._cws(x, self._state(), "hash")

    def features_from_hashes(self, i_star: Array, t_star: Array) -> Array:
        """Stage 2+3 on precomputed hashes (columns may be pre-sliced to a
        k prefix; offsets follow the column count).  In packed mode the
        codes bit-pack instead of expanding to global indices — the same
        output format as ``features``."""
        self._require_bucketed("features_from_hashes")
        codes = encode(i_star, t_star, b_i=self.spec.b_i, b_t=self.spec.b_t)
        if self.spec.packed:
            return pack_codes(codes, b=self.spec.bits)
        return feature_indices(codes, b_i=self.spec.b_i, b_t=self.spec.b_t)

    def unpack_features(self, packed: Array) -> Array:
        """Packed words -> the (n, k) int32 GLOBAL bag indices the
        unpacked pipeline would have emitted (decode oracle; also the
        bridge to index-consuming evaluators).  Bit-exact inverse of the
        packed emit."""
        if not self.spec.packed:
            raise ValueError("unpack_features needs a packed=True spec")
        codes = unpack_codes(packed, self.spec.num_hashes, b=self.spec.bits)
        offs = jnp.arange(self.spec.num_hashes, dtype=jnp.int32) * \
            self.spec.width
        return (offs + codes).astype(jnp.int32)

    def codes(self, x: Array) -> Array:
        """Per-hash codes WITHOUT feature offsets (collision estimators);
        sentinel rows keep -1."""
        i_star, t_star = self.hashes(x)
        return encode(i_star, t_star, b_i=self.spec.b_i, b_t=self.spec.b_t)

    def staged_reference(self, x: Array) -> Array:
        """The unchunked staged oracle — tests compare ``features`` to this.
        In param-free mode the oracle is the counter-spec regen path."""
        if self.param_free:
            i_star, t_star = cws_hash_regen(x, self._key_words,
                                            self.spec.num_hashes)
        else:
            i_star, t_star = cws_hash_reference(x, self._state())
        return self.features_from_hashes(i_star, t_star)

    def _require_bucketed(self, method: str) -> None:
        """Embedding-bag expansion needs b_i >= 1: with b_i = 0 the i* part
        is kept in full, so codes are unbounded by 2^{b_i+b_t} and flat
        indices would silently collide/clip past ``num_features``.  b_i = 0
        specs are for collision estimators — use ``codes``/``hashes``."""
        if self.spec.b_i == 0:
            raise ValueError(
                f"{method} requires b_i >= 1 (b_i = 0 keeps i* in full, so "
                f"indices are not bounded by num_features = "
                f"{self.spec.num_features}); use .codes()/.hashes() for "
                f"b_i = 0 estimator specs")

    # -- streaming / sharding internals --------------------------------

    def _chunk_fn(self):
        """Jitted per-chunk launch with the chunk buffer donated (on TPU):
        streaming never holds chunk + output beyond one launch.  On CPU the
        int32 output can never alias the fp32 chunk, so donation would only
        warn."""
        if self._donating_chunk_fn is None:
            self._donating_chunk_fn = jax.jit(
                lambda xc, state: self._launch_with(xc, state),
                donate_argnums=registry.donate_argnums(0))
        return self._donating_chunk_fn

    def scoring_chunk_fn(self):
        """The ONLINE-SERVING launch: one cached jitted executable fusing
        the featurization kernel with the embedding-bag logits head
        matched to the spec's output format (``bag_logits``, or
        ``bag_logits_packed`` for ``packed`` specs) —
        ``fn(xc, pipe._state(), table) -> (m, C) float32`` logits.

        NON-donating, unlike ``_chunk_fn``: the serving gateway re-pads
        caller request rows into buffers it still owns when slicing
        responses back out, and the (F, C) weight table must stay live
        across every request.  Each distinct m compiles one executable
        (inspect via ``_cache_size()``), which is exactly the per-bucket
        discipline repro.serving.BucketRunner keys its warmup off."""
        self._require_bucketed("scoring_chunk_fn")
        if self._scoring_fn is None:
            from repro.core.linear_model import bag_logits, bag_logits_packed
            if self.spec.packed:
                head = functools.partial(bag_logits_packed,
                                         num_hashes=self.spec.num_hashes,
                                         b=self.spec.bits)
            else:
                head = bag_logits
            self._scoring_fn = jax.jit(
                lambda xc, state, table: head(
                    table, self._launch_with(xc, state)))
        return self._scoring_fn

    @property
    def source(self) -> str:
        """Where the CWS parameters come from: ``"stored"`` matrices, or
        ``"regen"``erated in the kernel from two key words."""
        return "regen" if self.param_free else "stored"

    @property
    def emit(self) -> str:
        """What a feature launch emits: ``"packed"`` words or int32
        bag ``"index"``es (``hashes`` emits ``"hash"``)."""
        return "packed" if self.spec.packed else "index"

    def _blocks_for(self, rows: int, dim: int,
                    emit: str) -> Tuple[int, int, int]:
        """(bn, bk, bd) of one kernel launch over (rows, dim) that emits
        ``emit``."""
        return self.blocks or registry.choose_blocks(
            rows, dim, self.spec.num_hashes,
            op=registry.cws_family(self.source, emit))

    def loop_share(self, rows: int) -> float:
        """Real D over the padded D, for a launch of ``rows`` rows on one
        device: the share of the padded dimension loop that the kernel
        runs (``cws_loop_share`` of the launch and fit set-up spans)."""
        return loop_share(self.dim,
                          self._blocks_for(rows, self.dim, self.emit)[2])

    def dims_per_trip(self, rows: int) -> int:
        """The dimensions each trip of the kernel's accumulation loop
        folds in the main D steps of a launch of ``rows`` rows on one
        device (``cws_dims_per_trip`` of the launch and fit set-up
        spans)."""
        bn, bk, bd = self._blocks_for(rows, self.dim, self.emit)
        return launch_dims_per_trip(
            rows, self.dim, self.spec.num_hashes, emit=self.emit,
            b_i=self.spec.b_i, b_t=self.spec.b_t, bn=bn, bk=bk, bd=bd)

    def _launch_with(self, x: Array, state) -> Array:
        """One kernel launch on explicit state (CWSParams or key words)."""
        return self._cws(x, state, self.emit)

    def _resolved_impl(self) -> str:
        return self.impl or registry.auto_impl("cws")

    def state_pspec(self):
        """PartitionSpec for the replicated launch state: the (2,) key
        words in param-free mode, each (D, k) CWSParams matrix otherwise.
        Shared with the streamed trainer's shard_map in_specs."""
        from jax.sharding import PartitionSpec as P
        return P(None) if self.param_free else P(None, None)

    def _sharded_chunk_fn(self, mesh, *, donate: bool = True):
        """Jitted shard_map'd per-chunk launch over the mesh's ``data``
        axis, cached per (mesh, donate): rows split across devices, hash
        state replicated, each shard running the same kernel body as the
        unsharded chunk fn.  ``donate=True`` (the streaming path, whose
        chunks are fresh slice/pad buffers) donates the chunk per shard
        on TPU; ``donate=False`` serves whole-array launches where the
        buffer may alias the CALLER's live x (zero-pad pass-through)."""
        key = (mesh, bool(donate))
        fn = self._sharded_fns.get(key)
        if fn is None:
            body = jax.shard_map(
                lambda xs, ps: self._launch_with(xs, ps),
                mesh=mesh,
                in_specs=(self._rows_pspec(), self.state_pspec()),
                out_specs=self._rows_pspec(),
                check_vma=False,
            )
            donate_argnums = registry.donate_argnums(0) if donate else ()
            fn = jax.jit(body, donate_argnums=donate_argnums)
            self._sharded_fns[key] = fn
        return fn

    def _rows_pspec(self):
        from jax.sharding import PartitionSpec as P
        return P("data", None)

    def _features_streamed(self, x: Array, *, launch=None,
                           mesh=None) -> Array:
        """Chunked launches keep peak memory at O(chunk * max(D, k)) on
        every path; the ragged tail is padded inside feature_chunks so
        only one chunk shape ever compiles, sharded or not."""
        return jnp.concatenate(
            [out for _, _, out in self.feature_chunks(x, launch=launch,
                                                      mesh=mesh)],
            axis=0)

    def _features_sharded(self, x: Array, mesh) -> Array:
        """One whole-array launch (n <= chunk_rows) shard_mapped over
        ``data``: pad once to the axis multiple — with n < ndev some
        shards are ALL pad rows, which featurize as all-zero rows ->
        sentinel -> bucket 0 and slice off.  Never donating here: with
        zero pad ``jnp.pad`` may pass x straight through, and donating
        the caller's live array (or slicing [:n] out of its reclaimed
        buffer) would invalidate it."""
        ndev = data_axis_size(mesh)
        n = x.shape[0]
        pad = (-n) % ndev
        xp = jnp.pad(x, ((0, pad), (0, 0)))   # all-zero pad rows -> bucket 0
        fn = self._sharded_chunk_fn(mesh, donate=False)
        return fn(xp, self._state())[:n]


# ---------------------------------------------------------------------------
# analysis sites (repro.analysis / tools/kernel_lint.py)
# ---------------------------------------------------------------------------
# The pipeline's donating entry points, registered for the donation-safety
# lint: builders construct a tiny pipeline UNDER registry.force_donation()
# so the traced jaxprs carry the TPU-shaped donated_invars on any host.
# "pipeline.features_streamed" walks the caller path that shipped the
# PR 4 alias bug; "pipeline.features_sharded" pins its fix (the
# non-donating twin on whole-array launches).

def _analysis_pipe(*, packed: bool = False) -> "FeaturePipeline":
    spec = FeatureSpec(num_hashes=16, b_i=4, b_t=2 if packed else 0,
                       packed=packed)
    return FeaturePipeline.create_regen(jax.random.PRNGKey(0), 24, spec,
                                        row_chunk=8)


@registry.register_donation_site("pipeline.launch_chunk")
def _donation_site_launch_chunk():
    with registry.force_donation():
        pipe = _analysis_pipe()
        fn = pipe._chunk_fn()
    chunk = jax.ShapeDtypeStruct((8, 24), jnp.float32)
    return {"fn": lambda c, s: fn(c, s), "args": (chunk, pipe._state()),
            "donate_argnums": (0,)}


@registry.register_donation_site("pipeline.features_streamed")
def _donation_site_features_streamed():
    with registry.force_donation():
        pipe = _analysis_pipe()
        pipe._chunk_fn()            # the donating jit the stream launches
    x = jax.ShapeDtypeStruct((27, 24), jnp.float32)   # ragged tail chunk
    return {"fn": lambda x: pipe._features_streamed(x), "args": (x,),
            "donate_argnums": ()}


@registry.register_donation_site("pipeline.features_sharded")
def _donation_site_features_sharded():
    from repro.launch.mesh import make_data_mesh
    mesh = make_data_mesh()
    with registry.force_donation():
        pipe = _analysis_pipe()
        pipe._sharded_chunk_fn(mesh, donate=False)
    x = jax.ShapeDtypeStruct((7, 24), jnp.float32)    # pad may be zero
    return {"fn": lambda x: pipe._features_sharded(x, mesh),
            "args": (x,), "donate_argnums": ()}


@registry.register_collective_site("pipeline.sharded_chunk")
def _collective_site_sharded_chunk():
    from repro.launch.mesh import make_data_mesh
    mesh = make_data_mesh()
    ndev = data_axis_size(mesh)
    pipe = _analysis_pipe()
    fn = pipe._sharded_chunk_fn(mesh, donate=False)
    x = jax.ShapeDtypeStruct((8 * ndev, 24), jnp.float32)
    # featurization is embarrassingly parallel over rows: the shard_map
    # must contain NO cross-device reduction
    return {"fn": lambda x, s: fn(x, s), "args": (x, pipe._state()),
            "expected_psums": 0}
