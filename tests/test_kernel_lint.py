"""The kernel-contract analyzer vs a fixture zoo of deliberately-broken
kernels — every check class must catch its seeded bug with an actionable
message — plus the green path: the real registry passes the full suite,
and the packed VMEM models (fixed this PR) are pinned exact against the
footprints the kernels declare.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis import (audit_collectives, audit_completeness,
                            audit_coverage, audit_determinism,
                            audit_donation, audit_dtype_flow,
                            audit_family_vmem, audit_intervals,
                            audit_trio_signatures, check_permutation,
                            compile_guard, extract_launches,
                            probe_footprints, run_suite, unknown_ival)
from repro.kernels import ops, registry  # noqa: F401  (probe registration)


def _messages(findings):
    return "\n".join(f.message for f in findings)


def _fixture_call(in_map, out_map, grid=(2,), x_shape=(8, 8),
                  out_shape=(8, 8), block=(4, 8)):
    """A minimal interpret-mode pallas_call with injectable index maps."""
    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def fn(x):
        return pl.pallas_call(
            kernel, grid=grid,
            in_specs=[pl.BlockSpec(block, in_map)],
            out_specs=pl.BlockSpec(block, out_map),
            out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
            interpret=True)(x)
    return fn


# -- coverage ----------------------------------------------------------


class TestCoverageFixtures:
    def test_oob_input_index_map_fires(self):
        fn = _fixture_call(in_map=lambda i: (i + 1, 0),
                           out_map=lambda i: (i, 0))
        (launch,) = extract_launches(fn, jnp.ones((8, 8)))
        findings = audit_coverage(launch, target="fx")
        assert any("outside the padded block grid" in f.message
                   for f in findings), _messages(findings)

    def test_double_written_output_block_fires(self):
        # j is the inner grid axis; an out map ignoring i revisits every
        # block NON-consecutively -> two visit-runs per block
        fn = _fixture_call(in_map=lambda i, j: (i, 0),
                           out_map=lambda i, j: (j, 0), grid=(2, 2))
        (launch,) = extract_launches(fn, jnp.ones((8, 8)))
        findings = audit_coverage(launch, target="fx")
        assert any("separate visit-runs" in f.message for f in findings), \
            _messages(findings)

    def test_never_written_output_block_fires(self):
        fn = _fixture_call(in_map=lambda i: (i, 0),
                           out_map=lambda i: (0, 0))
        (launch,) = extract_launches(fn, jnp.ones((8, 8)))
        findings = audit_coverage(launch, target="fx")
        assert any("never written" in f.message for f in findings), \
            _messages(findings)

    def test_consecutive_revisits_are_one_write(self):
        # accumulate-then-emit shape: out map ignores the INNER axis, so
        # revisits collapse to a single visit-run — no finding
        fn = _fixture_call(in_map=lambda i, s: (i, 0),
                           out_map=lambda i, s: (i, 0), grid=(2, 3))
        (launch,) = extract_launches(fn, jnp.ones((8, 8)))
        assert audit_coverage(launch, target="fx") == []

    def test_real_kernels_covered(self):
        for fam in registry.model_families():
            blocks = registry.choose_blocks(48, 96, 160, op=fam)
            for rec in probe_footprints(fam, blocks):
                findings = audit_coverage(rec["launch"], target=fam)
                assert findings == [], _messages(findings)


# -- vmem --------------------------------------------------------------


class TestVmemFixtures:
    def test_optimistic_model_fires(self):
        findings = audit_family_vmem(
            "cws", blocks_list=[(8, 128, 128)], model=lambda *b: 10)
        assert any("optimistic model overbooks VMEM" in f.message
                   for f in findings), _messages(findings)

    def test_budget_violation_fires(self):
        findings = audit_family_vmem(
            "cws", blocks_list=[(8, 128, 128)], budget=1000)
        assert any("exceeds the 1000 B budget" in f.message
                   for f in findings), _messages(findings)

    def test_stale_model_drift_fires(self):
        findings = audit_family_vmem(
            "cws", blocks_list=[(8, 128, 128)],
            model=lambda b1, b2, bd: 10 ** 9)
        assert any("drift forbids legal block choices" in f.message
                   for f in findings), _messages(findings)

    def test_unprobed_family_fires(self):
        findings = audit_family_vmem("no_such_family")
        assert any("no registered LaunchProbe" in f.message
                   for f in findings), _messages(findings)

    def test_all_family_models_pass(self):
        stats = {}
        for fam in registry.model_families():
            findings = audit_family_vmem(fam, stats=stats)
            assert findings == [], _messages(findings)

    def test_models_pinned_exact_on_worst_member(self):
        # The regression pin for the PR 6 packed families (and everyone
        # else): _VMEM_MODELS equals the worst member's declared
        # BlockSpec+scratch footprint EXACTLY at every audited block
        # choice.  A model edit or a kernel scratch change that breaks
        # this must also update the other side.
        stats = {}
        for fam in registry.model_families():
            audit_family_vmem(fam, stats=stats)
            assert stats[fam]["max_model_over_actual"] == 1.0, (fam, stats)


# -- donation ----------------------------------------------------------


class TestDonationFixtures:
    def test_donated_and_returned_fires(self):
        findings = audit_donation(
            lambda x: jnp.reshape(x, (-1,)), (jnp.ones((4, 4)),),
            donate_argnums=(0,), name="fx")
        assert any("aliases donated input" in f.message
                   for f in findings), _messages(findings)

    def test_donated_caller_live_buffer_fires(self):
        # the PR 4 shape: a statically-zero jnp.pad passes the caller's
        # live x straight through to a donating jit
        inner = jax.jit(lambda b: b * 2.0, donate_argnums=(0,))

        def caller(x):
            y = jnp.pad(x, ((0, 0), (0, 0)))
            return inner(y), x.sum()

        findings = audit_donation(caller, (jnp.ones((4, 4)),), name="fx")
        assert any("other live uses" in f.message or
                   "aliases a caller buffer" in f.message
                   for f in findings), _messages(findings)

    def test_donated_and_returned_by_caller_fires(self):
        inner = jax.jit(lambda b: b * 2.0, donate_argnums=(0,))

        def caller(x):
            y = x * 3.0
            return y, inner(y)

        findings = audit_donation(caller, (jnp.ones((4, 4)),), name="fx")
        assert any("caller also RETURNS" in f.message
                   for f in findings), _messages(findings)

    def test_donated_closure_constant_fires(self):
        inner = jax.jit(lambda b: b * 2.0, donate_argnums=(0,))
        w = jnp.ones((4, 4))

        def caller(x):
            return inner(w) + x

        findings = audit_donation(caller, (jnp.ones((4, 4)),), name="fx")
        assert any("closure constant" in f.message
                   for f in findings), _messages(findings)

    def test_copy_breaks_the_alias_chain(self):
        findings = audit_donation(
            lambda x: jnp.copy(x), (jnp.ones((4, 4)),),
            donate_argnums=(0,), name="fx")
        assert findings == [], _messages(findings)

    def test_nonzero_pad_is_fresh_memory(self):
        inner = jax.jit(lambda b: b * 2.0, donate_argnums=(0,))

        def caller(x):
            y = jnp.pad(x, ((0, 1), (0, 0)))   # real pad: new buffer
            return inner(y), x.sum()

        findings = audit_donation(caller, (jnp.ones((4, 4)),), name="fx")
        assert findings == [], _messages(findings)


# -- collectives -------------------------------------------------------


def _mesh1():
    return Mesh(np.array(jax.devices()[:1]), ("data",))


class TestCollectiveFixtures:
    def test_unbound_axis_name_fires(self):
        f = shard_map(lambda x: jax.lax.psum(x, "model"), mesh=_mesh1(),
                      in_specs=P("data"), out_specs=P("data"),
                      check_vma=False)
        findings = audit_collectives(f, (jnp.ones((4,)),), name="fx")
        assert any("unbound axis name" in f.message
                   for f in findings), _messages(findings)

    def test_non_permutation_ppermute_fires(self):
        f = shard_map(
            lambda x: jax.lax.ppermute(x, "data", [(0, 0), (1, 0)]),
            mesh=_mesh1(), in_specs=P("data"), out_specs=P("data"),
            check_vma=False)
        findings = audit_collectives(f, (jnp.ones((4,)),), name="fx")
        assert any("not a true permutation" in f.message
                   for f in findings), _messages(findings)

    def test_check_permutation_rules(self):
        assert check_permutation([(0, 1), (1, 0)], 2) == []
        assert any("duplicate destinations" in e
                   for e in check_permutation([(0, 0), (1, 0)], 2))
        assert any("cannot send twice" in e
                   for e in check_permutation([(0, 0), (0, 1)], 2))
        assert any("outside the axis size" in e
                   for e in check_permutation([(0, 3)], 2))
        assert any("unmatched shards" in e
                   for e in check_permutation([(0, 1)], 2))

    def test_double_reduction_fires(self):
        f = shard_map(
            lambda x: jax.lax.psum(jax.lax.psum(x, "data"), "data"),
            mesh=_mesh1(), in_specs=P("data"), out_specs=P(),
            check_vma=False)
        findings = audit_collectives(f, (jnp.ones((4,)),), name="fx")
        assert any("reduced twice" in f.message
                   for f in findings), _messages(findings)

    def test_blessed_point_count_fires(self):
        f = shard_map(lambda x: jax.lax.psum(x, "data"), mesh=_mesh1(),
                      in_specs=P("data"), out_specs=P(), check_vma=False)
        findings = audit_collectives(f, (jnp.ones((4,)),), name="fx",
                                     expected_psums=3)
        assert any("expected exactly 3 psum(s)" in f.message
                   for f in findings), _messages(findings)


# -- completeness ------------------------------------------------------


class TestCompletenessFixtures:
    def test_partial_op_family_fires(self):
        try:
            @registry.register("lint_demo_op", "pallas", requires=("tpu",))
            def _demo(x, *, bn):
                return x

            findings = audit_completeness(["lint_demo_op"])
            msgs = _messages(findings)
            assert "missing ['pallas-interpret', 'reference']" in msgs
            assert "no _VMEM_MODELS entry" in msgs
        finally:
            registry._REGISTRY.pop("lint_demo_op", None)

    def test_signature_drift_fires(self):
        try:
            @registry.register("lint_demo_op", "pallas-interpret")
            def _demo(x, *, bn):
                return x

            @registry.register("lint_demo_op", "reference")
            def _demo_ref(x, *, bk):        # drifted kwarg name
                return x

            findings = audit_completeness(["lint_demo_op"])
            assert any("disagree on signatures" in f.message
                       for f in findings), _messages(findings)
        finally:
            registry._REGISTRY.pop("lint_demo_op", None)

    def test_real_registry_complete(self):
        findings = audit_completeness()
        assert findings == [], _messages(findings)


# -- compile_guard -----------------------------------------------------


class TestCompileGuard:
    def test_single_compile_passes(self):
        f = jax.jit(lambda x: x * 2)
        with compile_guard() as g:
            g.watch(f)
            f(jnp.ones(3))
            f(jnp.ones(3) + 1)       # same shape: no retrace

    def test_retrace_fails(self):
        f = jax.jit(lambda x: x * 2)
        with pytest.raises(AssertionError, match="re-traced"):
            with compile_guard() as g:
                g.watch(f)
                f(jnp.ones(3))
                f(jnp.ones(4))       # new shape: second compile

    def test_expect_overrides(self):
        f = jax.jit(lambda x: x * 2)
        with compile_guard() as g:
            g.watch(f, expect=2)
            f(jnp.ones(3))
            f(jnp.ones(4))

    def test_non_jitted_rejected(self):
        with compile_guard() as g:
            with pytest.raises(TypeError, match="_cache_size"):
                g.watch(lambda x: x)

    def test_inner_exception_propagates_unjudged(self):
        f = jax.jit(lambda x: x * 2)
        with pytest.raises(ValueError, match="boom"):
            with compile_guard() as g:
                g.watch(f, expect=99)    # would fail verify — must not mask
                raise ValueError("boom")


# -- numerics: dtype_flow ----------------------------------------------


class TestDtypeFlowFixtures:
    def test_implicit_narrowing_fires(self):
        fn = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
        x = jax.ShapeDtypeStruct((4, 4), jnp.float32)
        findings = audit_dtype_flow(fn, (x,), name="fx")
        assert any("float32->bfloat16" in f.message for f in findings), \
            _messages(findings)

    def test_blessed_narrowing_is_clean(self):
        fn = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
        x = jax.ShapeDtypeStruct((4, 4), jnp.float32)
        assert not audit_dtype_flow(fn, (x,), name="fx",
                                    allow_narrow=("float32->bfloat16",))

    def test_bf16_dot_without_pinned_accumulator_fires(self):
        fn = lambda a, b: jnp.dot(a, b)  # noqa: E731
        a = jax.ShapeDtypeStruct((4, 8), jnp.bfloat16)
        b = jax.ShapeDtypeStruct((8, 4), jnp.bfloat16)
        findings = audit_dtype_flow(fn, (a, b), name="fx")
        assert any("preferred_element_type" in f.message
                   for f in findings), _messages(findings)
        # pinning the accumulation to f32 is the fix
        fixed = lambda a, b: jnp.dot(  # noqa: E731
            a, b, preferred_element_type=jnp.float32)
        assert not audit_dtype_flow(fixed, (a, b), name="fx")

    def test_sub_f32_scan_carry_fires(self):
        def fn(x):
            def body(c, xi):
                return (c + xi).astype(jnp.bfloat16), ()
            c, _ = jax.lax.scan(body, jnp.zeros((), jnp.bfloat16), x)
            return c
        x = jax.ShapeDtypeStruct((8,), jnp.bfloat16)
        findings = audit_dtype_flow(fn, (x,), name="fx",
                                    allow_narrow=("float32->bfloat16",))
        assert any("carry" in f.message and "bfloat16" in f.message
                   for f in findings), _messages(findings)

    def test_sub_f32_pallas_scratch_fires(self):
        from jax.experimental.pallas import tpu as pltpu

        def kernel(x_ref, o_ref, acc):
            acc[...] = x_ref[...].astype(jnp.bfloat16)
            o_ref[...] = acc[...].astype(jnp.float32)

        def fn(x):
            return pl.pallas_call(
                kernel, grid=(2,),
                in_specs=[pl.BlockSpec((4, 8), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((4, 8), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
                scratch_shapes=[pltpu.VMEM((4, 8), jnp.bfloat16)],
                interpret=True)(x)
        x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
        findings = audit_dtype_flow(fn, (x,), name="fx",
                                    allow_narrow=("float32->bfloat16",))
        assert any("scratch" in f.message and "bfloat16" in f.message
                   for f in findings), _messages(findings)


# -- numerics: int_range -----------------------------------------------


class TestIntervalFixtures:
    def test_out_of_range_shift_fires(self):
        fn = lambda x: x << 35  # noqa: E731
        x = unknown_ival((4,), jnp.uint32)
        findings = audit_intervals(fn, (x,), name="fx")
        assert any("shift" in f.message and "35" in f.message
                   for f in findings), _messages(findings)

    def test_wrapping_int32_arithmetic_fires(self):
        fn = lambda a, b: a + b  # noqa: E731
        a = unknown_ival((4,), jnp.int32)
        b = unknown_ival((4,), jnp.int32)
        findings = audit_intervals(fn, (a, b), name="fx")
        assert any("wrap int32" in f.message for f in findings), \
            _messages(findings)
        # threefry-style wraparound is blessed per site, not globally
        assert not audit_intervals(fn, (a, b), name="fx",
                                   allow_wrap=True)

    def test_out_of_table_gather_fires(self):
        fn = lambda t, idx: t[idx]  # noqa: E731
        t = jax.ShapeDtypeStruct((8,), jnp.float32)
        idx = unknown_ival((4,), jnp.int32, lo=0, hi=100)
        findings = audit_intervals(fn, (t, idx), name="fx")
        assert any("gather" in f.message for f in findings), \
            _messages(findings)
        # a provably in-table index is clean
        ok = unknown_ival((4,), jnp.int32, lo=0, hi=7)
        assert not audit_intervals(fn, (t, ok), name="fx")

    def test_inexact_int_to_float_fires_exact_constant_does_not(self):
        fn = lambda x: x.astype(jnp.float32)  # noqa: E731
        x = unknown_ival((4,), jnp.int32)    # full range > 2^24
        findings = audit_intervals(fn, (x,), name="fx")
        assert any("2^24" in f.message for f in findings), \
            _messages(findings)
        # a known power-of-two constant round-trips exactly (the
        # jnp.clip(..., 2^30) pattern in the emit kernel)
        big = lambda: jnp.int32(1 << 30).astype(jnp.float32)  # noqa: E731
        assert not audit_intervals(big, (), name="fx")

    def test_interval_proof_of_packed_shift_chain(self):
        # the real unpack_codes contract, in miniature: lax.div/rem keep
        # word index and shift amount provably in range at any k
        from repro.core.hashing import unpack_codes
        packed = jax.ShapeDtypeStruct((2, 3), jnp.uint32)
        assert not audit_intervals(
            lambda p: unpack_codes(p, 9, b=8), (packed,), name="fx")


# -- numerics: determinism ---------------------------------------------


class TestDeterminismFixtures:
    def test_float_scatter_add_fires(self):
        def fn(x, idx):
            return jnp.zeros((8,), jnp.float32).at[idx].add(x)
        x = jax.ShapeDtypeStruct((16,), jnp.float32)
        idx = jax.ShapeDtypeStruct((16,), jnp.int32)
        findings = audit_determinism(fn, (x, idx), name="fx")
        assert any("scatter" in f.message for f in findings), \
            _messages(findings)
        # per-site blessing (the trainer's grad accumulation) silences it
        assert not audit_determinism(fn, (x, idx), name="fx",
                                     allow=("scatter-add",))

    def test_int_scatter_add_is_clean(self):
        # integer addition is associative: order cannot change the sum
        def fn(x, idx):
            return jnp.zeros((8,), jnp.int32).at[idx].add(x)
        x = jax.ShapeDtypeStruct((16,), jnp.int32)
        idx = jax.ShapeDtypeStruct((16,), jnp.int32)
        assert not audit_determinism(fn, (x, idx), name="fx")

    def test_stray_collective_fires(self):
        mesh = Mesh(np.array(jax.devices()).reshape(1, -1),
                    ("data", "model"))
        def fn(x):
            return shard_map(lambda xs: jax.lax.psum(xs, "model"),
                             mesh=mesh, in_specs=P(None, "model"),
                             out_specs=P(None, None))(x)
        x = jax.ShapeDtypeStruct((4, len(jax.devices())), jnp.float32)
        findings = audit_determinism(fn, (x,), name="fx")
        assert any("psum" in f.message for f in findings), \
            _messages(findings)
        assert not audit_determinism(fn, (x,), name="fx",
                                     allow=("psum",))

    def test_dtype_mismatched_trio_fires(self):
        op = "lint_demo_op"
        try:
            registry.register(op, "reference")(
                lambda x: x.astype(jnp.float32))
            registry.register(op, "pallas-interpret")(
                lambda x: x.astype(jnp.bfloat16))   # drifted dtype
            registry.register_trio(
                op, impls=("reference", "pallas-interpret"))(
                lambda: ((jnp.ones((4, 4), jnp.float32),), {}))
            findings = audit_trio_signatures()
            mine = [f for f in findings if f.target == op]
            assert any("disagrees" in f.message for f in mine), \
                _messages(findings)
        finally:
            registry._REGISTRY.pop(op, None)
            registry._TRIO_PROBES.pop(op, None)

    def test_pallas_op_without_trio_probe_fires(self):
        op = "lint_demo_unprobed"
        try:
            registry.register(op, "pallas", requires=("tpu",))(
                lambda x: x)
            registry.register(op, "reference")(lambda x: x)
            findings = audit_trio_signatures()
            mine = [f for f in findings if f.target == op]
            assert any("trio" in f.message for f in mine), \
                _messages(findings)
        finally:
            registry._REGISTRY.pop(op, None)


# -- numerics: the packed-table int32 boundary (satellite guard) -------


class TestPackedTableBoundary:
    def test_boundary_table_traces(self):
        # k * 2^b == 2^31 exactly: the top flat index is int32 max
        from repro.core.linear_model import (LinearParams,
                                             bag_logits_packed,
                                             check_bag_table_size)
        from repro.core.hashing import packed_width
        k, b = 1 << 23, 8
        F = check_bag_table_size(k, b)
        assert F == 1 << 31
        w = jax.ShapeDtypeStruct((F, 3), jnp.float32)
        bias = jax.ShapeDtypeStruct((3,), jnp.float32)
        packed = jax.ShapeDtypeStruct((2, packed_width(k, b)), jnp.uint32)
        out = jax.eval_shape(
            lambda w, bias, p: bag_logits_packed(
                LinearParams(w, bias), p, num_hashes=k, b=b),
            w, bias, packed)
        assert out.shape == (2, 3)

    def test_over_boundary_raises(self):
        from repro.core.linear_model import check_bag_table_size
        with pytest.raises(ValueError, match="2\\^31"):
            check_bag_table_size((1 << 23) + 1, 8)
        with pytest.raises(ValueError, match="2\\^31"):
            check_bag_table_size(1 << 26, 8)


# -- the real registry, end to end -------------------------------------


class TestSuiteGreen:
    def test_full_suite_has_no_failures(self):
        report = run_suite()
        assert not report.failures, report.to_text()

    def test_matrix_covers_every_family_and_site(self):
        report = run_suite()
        for fam in registry.model_families():
            assert report.matrix[fam]["vmem"] == "pass"
            assert report.matrix[fam]["coverage"] == "pass"
        for site in registry.donation_sites():
            assert report.matrix[site.name]["donation"] == "pass"
        for site in registry.collective_sites():
            assert report.matrix[site.name]["collectives"] == "pass"
        for site in registry.numerics_sites():
            row = report.matrix[site.name]
            numerics = [c for c in ("dtype_flow", "int_range",
                                    "determinism")
                        if row.get(c, "n/a") != "n/a"]
            assert numerics, f"{site.name} ran no numerics checks"
            for c in numerics:
                assert row[c] == "pass", (site.name, c)

    def test_launch_extraction_structure(self):
        # structural sanity on a real kernel: grid, operands, scratch
        fam_blocks = (8, 128, 128)
        (rec,) = [r for r in probe_footprints("cws_rng", fam_blocks)
                  if r["launch"].name == "cws_hash_rng_pallas"]
        launch = rec["launch"]
        assert len(launch.grid) == 3
        assert len(launch.outputs) == 2          # i*, t*
        assert len(launch.scratch) == 7          # log u + 3 param + 3 accum
        smem = [o for o in launch.inputs if o.memory_space == "smem"]
        assert len(smem) == 1                    # the regen key words
