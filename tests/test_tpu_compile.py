"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

Interpret mode runs a kernel body on the CPU but never asks Mosaic (the
TPU kernel compiler) whether it can lower it.  These tests do: each
kernel is lowered for a described ``v5e:2x2`` topology (no chip
attached) at the ``minmax_paper`` widths with ``choose_blocks``' blocks
(and at the benchmark cells' D, which ``bd`` does not divide),
compiled, and must come back holding a Mosaic kernel
(``tpu_custom_call``).  A kernel Mosaic refuses fails here instead of on
the chip.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU compiler's library, so the
one worker that runs this file loads it and every other worker collects
the same tests without touching it.  The persistent compilation cache is
off around these compiles (an entry written for a described chip cannot
be read back without one).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.minmax_paper import CONFIG
from repro.core.cws import CWSParams
from repro.kernels import cws_hash as ch
from repro.kernels import registry
from repro.kernels.minmax_gram import min_sum_pallas

N = 512                                  # rows per launch
D, K, B_I = CONFIG.dim, CONFIG.num_hashes, CONFIG.b_i


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:          # no TPU compiler in this install
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# each kernel's name, less "_pallas" -> its (parameter source, emit)
VARIANTS = {name[:-len("_pallas")]: variant
            for variant, name in ch.KERNEL_NAMES.items()}


def _case(name, b=B_I, n=N, d=D):
    """(fn, arg shapes) for one kernel at the config widths (D = ``d``
    where given)."""
    if name == "min_sum":
        bm, bn, bd = registry.choose_blocks(n, D, n, op="min_sum")
        x = ((n, D), jnp.float32)
        return (lambda a, c: min_sum_pallas(a, c, bm=bm, bn=bn,
                                            bd=bd)), [x, x]
    source, emit = VARIANTS[name]
    bn, bk, bd = registry.choose_blocks(
        n, d, K, op=registry.cws_family(source, emit))
    blocks = dict(emit=emit, b_i=b, bn=bn, bk=bk, bd=bd)
    x = ((n, d), jnp.float32)
    if source == "regen":
        return (lambda x, key: ch.cws_pallas(x, key, K, **blocks)), [
            x, ((2,), jnp.uint32)]
    p = ((d, K), jnp.float32)
    return (lambda x, r, c, be: ch.cws_pallas(
        x, CWSParams(r, c, be), **blocks)), [x, p, p, p]


@pytest.mark.parametrize("name,b", [
    ("cws_hash", B_I), ("cws_encode", B_I),
    ("cws_hash_rng", B_I), ("cws_encode_rng", B_I),
    ("cws_encode_packed", 4), ("cws_encode_packed", 8),
    ("cws_encode_rng_packed", 4), ("cws_encode_rng_packed", 8),
    ("min_sum", 0),
])
def test_kernel_compiles_for_v5e(one_chip, name, b):
    fn, shapes = _case(name, b)
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


@pytest.mark.parametrize("name,n,d", [
    ("cws_encode", N, 784),                 # mnist-stored: bd 512, tail 272
    ("cws_encode_rng_packed", 8192, 254),   # webspam: bd 128, tail 126
])
def test_tail_loop_compiles_for_v5e_at_the_cells_widths(one_chip, name, n,
                                                        d):
    """At D that choose_blocks' bd does not divide, the last D step's
    loop stops at the real D under a branch of its own; Mosaic must
    lower it."""
    fn, shapes = _case(name, 8, n=n, d=d)
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


@pytest.mark.parametrize("rows", registry.DEFAULT_SERVE_BUCKETS)
def test_serving_bucket_blocks_compile_for_v5e(one_chip, rows):
    """The serving ladder's small buckets give choose_blocks row blocks
    below the (8, 128) tile (down to one row); the fused encode kernel
    must still lower at each."""
    fn, shapes = _case("cws_encode", n=rows)
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


@pytest.mark.parametrize("name", [
    "cws_hash", "cws_encode", "cws_hash_rng", "cws_encode_rng",
    "cws_encode_packed", "cws_encode_rng_packed",
])
def test_kernel_keeps_its_name_in_the_tpu_lowering(name):
    """Each Mosaic kernel is named after its (source, emit) variant,
    whatever the kernel body's Python function is called; profiles and
    their readers match these names.  Lowering for the TPU needs no chip
    and no topology."""
    fn, shapes = _case(name)
    args = [jax.ShapeDtypeStruct(s, dt) for s, dt in shapes]
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    assert re.findall(r'kernel_name = "(\w+)"', text) == [f"{name}_pallas"]


@pytest.mark.parametrize("packed", [False, True])
def test_trainer_update_has_no_scatter_sort_or_gather_for_v5e(one_chip,
                                                              packed):
    """The streamed trainer's jitted ``update`` at the train cell's size
    (512 rows, k = 1,024, b = 8, 10 classes): the bag head is a one-hot
    contraction, so the compiled step holds no scatter, no index sort
    and no gather of table rows, and the one-hot is built inside the dot
    fusions
    (scratch far under one (512, F) bf16 block, 268 MB)."""
    import types

    from repro.core.linear_model import TrainCfg, init_bag, make_linear_tx
    from repro.pipeline import FeatureSpec
    from repro.training import linear_trainer as lt

    spec = FeatureSpec(num_hashes=K, b_i=B_I, packed=packed)
    cfg = TrainCfg(n_classes=10, steps=544, lr=0.05, l2=1e-5,
                   batch_size=N)
    tx = make_linear_tx(cfg)
    with registry.force_donation():
        step = lt._make_update_step(
            cfg, tx, 1, lt._bag_logits_fn(types.SimpleNamespace(spec=spec)))
    p0 = init_bag(jax.random.PRNGKey(0), spec.num_features, 10)

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    feats = ((N, spec.packed_words), jnp.uint32) if packed else \
        ((N, K), jnp.int32)
    args = (jax.tree_util.tree_map(sds, p0),
            jax.tree_util.tree_map(sds, jax.eval_shape(tx.init, p0)),
            jax.ShapeDtypeStruct(*feats, sharding=one_chip),
            jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    compiled = step.lower(*args).compile()
    ops = re.findall(r"= (\w+)\[\S* (\w[\w-]*)\(", compiled.as_text())
    assert not {op for _, op in ops} & {"scatter", "sort"}
    # (a packed spec's unpack gathers its uint32 words, not table rows)
    assert not [t for t, op in ops if op == "gather" and t[0] in "fb"]
    onehot_block = N * spec.num_features * 2
    assert compiled.memory_analysis().temp_size_in_bytes < onehot_block // 16
