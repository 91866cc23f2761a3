"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.minmax_paper import CONFIG
from repro.core import hashing
from repro.core.cws import (CWSParams, make_cws_params,
                            cws_hash as cws_hash_core)
from repro.core.regen import regen_params
from repro.analysis.launches import extract_launches
from repro.kernels import cws_hash as ch
from repro.kernels import ops, registry
from repro.kernels.ref import cws_hash_ref, minmax_gram_ref, min_sum_ref


def rand_nonneg(key, shape, sparsity=0.4, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    mag = jnp.exp(jax.random.normal(k1, shape))
    mask = jax.random.bernoulli(k2, 1 - sparsity, shape)
    return (mag * mask).astype(dtype)


def last_block(d, bd):
    """First column of the last D block (``bd`` clamped to D)."""
    bd = min(bd, d)
    return -(-d // bd) * bd - bd


def with_edge_rows(x, bd):
    """x with row 0 all zero (the sentinel) and row 1 nonzero only in
    its last D block, the one whose loop stops at the real D."""
    last = last_block(x.shape[1], bd)
    return x.at[0].set(0.0).at[1, :last].set(0.0).at[1, last:].add(0.5)


# D against bd at the last D step: a tail of 1, a tail of bd - 1, bd
# dividing D (no tail branch), and D under bd (bd clamps to D)
TAIL_SHAPES = [
    # (n, D, k, bn, bk, bd)
    (9, 33, 20, 8, 8, 16),
    (9, 47, 20, 8, 8, 16),
    (9, 48, 20, 8, 8, 16),
    (9, 12, 20, 8, 8, 32),
]

CWS_SHAPES = [
    # (n, D, k, bn, bk, bd)
    (4, 8, 4, 4, 4, 8),
    (16, 32, 16, 8, 8, 16),
    (33, 50, 21, 8, 8, 16),     # non-divisible everywhere
    (7, 128, 64, 8, 32, 32),
    (64, 300, 33, 32, 16, 128),
    (128, 64, 128, 128, 128, 64),
] + TAIL_SHAPES


class TestCWSPallas:
    @pytest.mark.parametrize("n,d,k,bn,bk,bd", CWS_SHAPES)
    def test_matches_oracle(self, n, d, k, bn, bk, bd):
        x = rand_nonneg(jax.random.PRNGKey(n * 1000 + d), (n, d))
        x = with_edge_rows(x, bd)
        p = make_cws_params(jax.random.PRNGKey(d * 7 + k), d, k)
        i_ref, t_ref = cws_hash_ref(x, p.r, p.log_c, p.beta)
        i_pl, t_pl = ops.cws(x, p, emit="hash", bn=bn, bk=bk, bd=bd,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_pl))
        np.testing.assert_array_equal(np.asarray(t_ref), np.asarray(t_pl))
        assert (np.asarray(i_pl[0]) == -1).all()
        assert (np.asarray(i_pl[1]) >= last_block(d, bd)).all()

    def test_matches_core_chunked(self):
        x = rand_nonneg(jax.random.PRNGKey(0), (40, 70))
        p = make_cws_params(jax.random.PRNGKey(1), 70, 30)
        i_core, t_core = cws_hash_core(x, p, row_block=16, hash_block=8)
        i_pl, t_pl = ops.cws(x, p, emit="hash", bn=16, bk=8, bd=32,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(i_core), np.asarray(i_pl))
        np.testing.assert_array_equal(np.asarray(t_core), np.asarray(t_pl))

    def test_zero_rows_sentinel(self):
        x = jnp.zeros((8, 16))
        p = make_cws_params(jax.random.PRNGKey(2), 16, 8)
        i_pl, t_pl = ops.cws(x, p, emit="hash", bn=4, bk=4, bd=8,
                             interpret=True)
        assert (np.asarray(i_pl) == -1).all()
        assert (np.asarray(t_pl) == 0).all()

    def test_mixed_sparsity_row(self):
        # one dense row, one zero row, one single-entry row
        x = jnp.zeros((3, 12)).at[0].set(1.5).at[2, 5].set(3.0)
        p = make_cws_params(jax.random.PRNGKey(3), 12, 16)
        i_ref, t_ref = cws_hash_ref(x, p.r, p.log_c, p.beta)
        i_pl, t_pl = ops.cws(x, p, emit="hash", bn=2, bk=8, bd=4,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_pl))
        assert (np.asarray(i_pl[2]) == 5).all()   # only one active dim

    @pytest.mark.parametrize("in_dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
    def test_input_dtypes(self, in_dtype):
        # data may arrive in low precision; hashing math is fp32 internally
        x = rand_nonneg(jax.random.PRNGKey(4), (12, 24), dtype=in_dtype)
        p = make_cws_params(jax.random.PRNGKey(5), 24, 8)
        i_ref, t_ref = cws_hash_ref(x, p.r, p.log_c, p.beta)
        i_pl, t_pl = ops.cws(x, p, emit="hash", bn=4, bk=4, bd=8,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(i_ref), np.asarray(i_pl))


@pytest.mark.parametrize("d,bd,tail,share", [
    (784, 512, 272, 0.765625),      # mnist-stored: 784 of 1,024
    (254, 128, 126, 0.9921875),     # webspam-regen-packed: 254 of 256
    (256, 256, 256, 1.0),           # minmax_paper: bd divides D
    (256, 512, 256, 1.0),           # bd clamps to D
    (33, 16, 1, 33 / 48),
    (47, 16, 15, 47 / 48),
    (1, 128, 1, 1.0),
])
def test_tail_trip_count_and_loop_share(d, bd, tail, share):
    assert ch.tail_dims(d, bd) == tail
    assert ch.loop_share(d, bd) == share


# (n, D, bn, bd) of each case, with its trips at the U = 16 that a
# (bn, 16) tile takes: every variant, with and without t* tracked, at a
# step of 8 trips and a tail of 35 (2 trips and a remainder of 3) at
# bn = 128; then one or two variants at the train cell's tail of 272 (17
# trips), the featurize cell's tail of 126 (7 trips and 14) at bn = 128
# and at the serving bucket's bn = 8, and tails of 16 at bn = 1, 7 (below
# U: one trip of 7) and 1
TRIP_CASES = [
    (source, emit, b_t, 128, 163, 128, 128)
    for source, emit in ch.KERNEL_NAMES for b_t in (0, 2)
] + [
    ("stored", "index", 0, 128, 784, 128, 512),
    ("stored", "hash", 0, 128, 784, 128, 512),
    ("regen", "packed", 0, 128, 254, 128, 128),
    ("stored", "index", 2, 128, 254, 128, 128),
    ("stored", "index", 0, 8, 254, 8, 128),
    ("regen", "index", 0, 1, 48, 1, 32),
    ("stored", "hash", 0, 8, 39, 8, 32),
    ("stored", "packed", 2, 128, 33, 128, 32),
]


def _plain_reference(x, p, emit, b_i, b_t):
    """The naive oracle's (i*, t*) through the b-bit encode and, for the
    index and packed emits, the offsets or the packing."""
    i_star, t_star = cws_hash_ref(x, p.r, p.log_c, p.beta)
    if emit == "hash":
        return i_star, t_star
    codes = hashing.encode(i_star, t_star, b_i=b_i, b_t=b_t)
    if emit == "packed":
        return hashing.pack_codes(codes, b=b_i + b_t)
    return hashing.feature_indices(codes, b_i=b_i, b_t=b_t)


@pytest.mark.parametrize("source,emit,b_t,n,d,bn,bd", TRIP_CASES)
def test_variant_bit_identical_at_every_trip_count(source, emit, b_t, n, d,
                                                   bn, bd):
    """Every (source, emit) variant, with t* tracked (b_t > 0, or the hash
    emit) and without, equals the plain oracle and core.cws bit for bit,
    whether or not U divides the tail's dimensions.  Row 0 is all zero
    (the sentinel), row 1 nonzero only in the last D block, and one
    entry is +inf (log u not finite: it never wins).  With stored
    parameters, four dimensions repeat an earlier one: 3 repeats 2 (one
    trip), 16 repeats 15 (two trips), D - 2 repeats D - 3 (the tail's
    last trip, or its remainder) and the last repeats 0 (another D
    step): exact ties, which the strict ``<`` must break to the lower
    index whatever trip each dimension falls in."""
    k, bk = 24, 16
    b_i = 8 - b_t if emit == "packed" else 4
    x = rand_nonneg(jax.random.PRNGKey(d), (n, d))
    if source == "stored":
        p = make_cws_params(jax.random.PRNGKey(1), d, k)
        for src, dst in [(2, 3), (15, 16), (d - 3, d - 2), (0, d - 1)]:
            x = x.at[:, dst].set(x[:, src])
            p = CWSParams(*(m.at[dst].set(m[src])
                            for m in (p.r, p.log_c, p.beta)))
        state = p
    else:
        state = jax.random.PRNGKey(5)
        p = regen_params(state, d, k)
    x = with_edge_rows(x, bd).at[n - 1, d // 2].set(jnp.inf)
    kw = dict(emit=emit, num_hashes=None if source == "stored" else k,
              b_i=b_i, b_t=b_t, bn=bn, bk=bk, bd=bd)
    got = ops.cws(x, state, impl="pallas-interpret", **kw)
    for want in (_plain_reference(x, p, emit, b_i, b_t),
                 ops.cws(x, state, impl="reference", **kw)):
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("carries", [2, 3])
@pytest.mark.parametrize("bn,bk", [(1, 128), (8, 128), (128, 128),
                                   (256, 128), (128, 256), (8, 16)])
def test_dims_per_trip_fits_the_trip_and_the_vreg_budget(bn, bk, carries):
    """U never exceeds the trip count, is the trip count below its
    nominal value, and keeps the trip within the budget it states."""
    nominal = ch.dims_per_trip(bn, bk, carries, 1 << 20)
    assert ch.trip_vregs(bn, bk, carries, nominal) <= ch.TRIP_VREGS
    for trips in range(1, 2 * nominal + 3):
        u = ch.dims_per_trip(bn, bk, carries, trips)
        assert 1 <= u <= trips
        assert u == min(trips, nominal)


def _loop_trips(jaxpr, dims=None, branches=0):
    """(real dimensions of every accumulation loop, number of branches)
    in ``jaxpr`` and the jaxprs inside it, the kernel body's included.

    Each dimension a loop folds is one ``floor``: a loop covers its
    trips times the floors of its body, plus the floors of the static
    trip that follows it in the same block."""
    dims = [] if dims is None else dims
    if any(eqn.primitive.name == "scan" for eqn in jaxpr.eqns):
        dims.append(_folds(jaxpr))
    for eqn in jaxpr.eqns:
        assert eqn.primitive.name != "while", "a loop with no static trips"
        branches += eqn.primitive.name == "cond"
        if eqn.primitive.name == "scan":
            continue
        for sub in _subjaxprs(eqn):
            _, branches = _loop_trips(sub, dims, branches)
    return dims, branches


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for sub in v if isinstance(v, (tuple, list)) else (v,):
            sub = getattr(sub, "jaxpr", sub)
            if isinstance(sub, jax.extend.core.Jaxpr):
                yield sub


def _folds(jaxpr):
    """Dimensions folded in ``jaxpr`` outside its branches: its floors,
    and each loop's trips times its body's."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "floor":
            n += 1
        elif eqn.primitive.name == "scan":
            n += eqn.params["length"] * sum(_folds(sub)
                                            for sub in _subjaxprs(eqn))
    return n


# each kernel's name, less "_pallas" -> its (parameter source, emit)
VARIANTS = {name[:-len("_pallas")]: variant
            for variant, name in ch.KERNEL_NAMES.items()}


def _kernel_call(name, n, d, k, b):
    """(wrapper, arg shapes) of one CWS kernel with choose_blocks'
    blocks at (n, D, k)."""
    source, emit = VARIANTS[name]
    bn, bk, bd = registry.choose_blocks(
        n, d, k, op=registry.cws_family(source, emit))
    blocks = dict(emit=emit, b_i=b, bn=bn, bk=bk, bd=bd)
    x = jax.ShapeDtypeStruct((n, d), jnp.float32)
    if source == "regen":
        return (lambda x, key: ch.cws_pallas(x, key, k, **blocks),
                [x, jax.ShapeDtypeStruct((2,), jnp.uint32)])
    p = jax.ShapeDtypeStruct((d, k), jnp.float32)
    return (lambda x, r, c, be: ch.cws_pallas(x, CWSParams(r, c, be),
                                              **blocks)), [x, p, p, p]


@pytest.mark.parametrize("d,trips", [
    (CONFIG.dim, [256]),        # minmax_paper: bd = 256 divides D
    (784, [512, 272]),          # mnist-stored: a tail of 272
])
@pytest.mark.parametrize("name", [
    "cws_hash", "cws_encode", "cws_encode_packed", "cws_hash_rng",
    "cws_encode_rng", "cws_encode_rng_packed",
])
def test_kernel_loops_over_the_real_dimensions(name, d, trips):
    """Where bd divides D, each D step's loop covers bd dimensions and the
    body branches only for its init and emit, as before the tail; else
    the last D step's loop covers the tail's real dimensions under a
    branch of its own.  A loop covers its trips times the U dimensions a
    trip folds, plus the static remainder."""
    fn, shapes = _kernel_call(name, 512, d, CONFIG.num_hashes, CONFIG.b_i)
    jaxpr = jax.make_jaxpr(fn)(*shapes).jaxpr
    got, branches = _loop_trips(jaxpr)
    assert got == trips
    assert branches == 2 * len(trips)


# (n, D) -> choose_blocks' (bn, bk, bd) at k = 1,024 and the grid they
# give: the train cell's step, the featurize cell's chunk, and the
# serving ladder's two smallest buckets.  Every CWS family gives the same.
CELL_LAUNCHES = [
    (512, 784, (128, 128, 512), (4, 8, 2)),
    (8192, 254, (128, 128, 128), (64, 8, 2)),
    (1, 784, (1, 128, 512), (1, 8, 2)),
    (8, 784, (8, 128, 512), (1, 8, 2)),
]


@pytest.mark.parametrize("name", list(VARIANTS))
def test_variant_blocks_and_launch_shapes(name):
    """Each (source, emit) variant keeps its block choice, its kernel's
    name, and its grid, block and scratch shapes at the cells' widths."""
    source, emit = VARIANTS[name]
    fam = registry.cws_family(source, emit)
    # the one measured-table entry these shapes reach is the stored
    # unpacked family's; the others take the heuristic
    assert registry.choose_blocks(4096, 1024, 1024, op=fam) == (
        (256, 128, 512) if fam == "cws" else (128, 128, 1024))
    for n, d, (bn, bk, bd), grid in CELL_LAUNCHES:
        assert registry.choose_blocks(n, d, 1024, op=fam) == (bn, bk, bd)
        fn, shapes = _kernel_call(name, n, d, 1024, 8)
        (launch,) = extract_launches(fn, *shapes)
        assert launch.name == f"{name}_pallas"
        assert launch.grid == grid
        params = [(bd, bk)] * 3 if source == "stored" else [(2,)]
        assert [o.block_shape for o in launch.inputs] == [(bn, bd)] + params
        outs = {"hash": [(bn, bk)] * 2, "index": [(bn, bk)],
                "packed": [(1, bn, bk * 8 // 32)]}[emit]
        assert [o.block_shape for o in launch.outputs] == outs
        f32, i32 = "float32", "int32"
        scratch = ([((bd, bn), f32)]
                   + [((bd, bk), f32)] * 3 * (source == "regen")
                   + [((bn, bk), f32), ((bn, bk), i32)]
                   + [((bn, bk), f32)] * (emit == "hash"))
        assert [(o.shape, jnp.dtype(o.dtype).name)
                for o in launch.scratch] == scratch


GRAM_SHAPES = [
    (4, 4, 8, 4, 4, 8),
    (16, 8, 32, 8, 8, 16),
    (33, 17, 50, 8, 8, 16),
    (64, 64, 128, 32, 32, 64),
    (10, 128, 77, 8, 64, 32),
]


class TestMinMaxGramPallas:
    @pytest.mark.parametrize("m,n,d,bm,bn,bd", GRAM_SHAPES)
    def test_matches_oracle(self, m, n, d, bm, bn, bd):
        x = rand_nonneg(jax.random.PRNGKey(m * 31 + d), (m, d))
        y = rand_nonneg(jax.random.PRNGKey(n * 17 + d), (n, d))
        g_ref = minmax_gram_ref(x, y)
        g_pl = ops.minmax_gram(x, y, bm=bm, bn=bn, bd=bd, interpret=True)
        np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_pl),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("m,n,d,bm,bn,bd", GRAM_SHAPES[:3])
    def test_min_sum_matches(self, m, n, d, bm, bn, bd):
        x = rand_nonneg(jax.random.PRNGKey(1), (m, d))
        y = rand_nonneg(jax.random.PRNGKey(2), (n, d))
        np.testing.assert_allclose(np.asarray(min_sum_ref(x, y)),
                                   np.asarray(ops.min_sum(x, y, bm=bm, bn=bn,
                                                          bd=bd, interpret=True)),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = rand_nonneg(jax.random.PRNGKey(3), (9, 33), dtype=dtype)
        y = rand_nonneg(jax.random.PRNGKey(4), (7, 33), dtype=dtype)
        g_ref = minmax_gram_ref(x, y)  # ref upcasts to fp32 the same way
        g_pl = ops.minmax_gram(x, y, bm=4, bn=4, bd=16, interpret=True)
        np.testing.assert_allclose(np.asarray(g_ref), np.asarray(g_pl),
                                   rtol=1e-5, atol=1e-6)

    def test_diag_one_selfgram(self):
        x = rand_nonneg(jax.random.PRNGKey(5), (20, 40), sparsity=0.2) + 0.01
        g = np.asarray(ops.minmax_gram(x, x, bm=8, bn=8, bd=16, interpret=True))
        np.testing.assert_allclose(np.diag(g), 1.0, atol=1e-5)
