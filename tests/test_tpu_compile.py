"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

Interpret mode runs a kernel body on the CPU but never asks Mosaic (the
TPU kernel compiler) whether it can lower it.  These tests do: each
kernel is lowered for a described ``v5e:2x2`` topology (no chip
attached) at the ``minmax_paper`` widths with ``choose_blocks``' blocks,
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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.minmax_paper import CONFIG
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


def _stored(n, fam):
    bn, bk, bd = registry.choose_blocks(n, D, K, op=fam)
    x, p = ((n, D), jnp.float32), ((D, K), jnp.float32)
    return (bn, bk, bd), [x, p, p, p]


def _regen(n, fam):
    bn, bk, bd = registry.choose_blocks(n, D, K, op=fam)
    return (bn, bk, bd), [((n, D), jnp.float32), ((2,), jnp.uint32)]


def _case(name, b=B_I, n=N):
    """(fn, arg shapes) for one kernel family at the config widths."""
    if name == "cws_hash":
        (bn, bk, bd), shapes = _stored(n, "cws")
        return (lambda x, r, c, be: ch.cws_hash_pallas(
            x, r, c, be, bn=bn, bk=bk, bd=bd)), shapes
    if name == "cws_encode":
        (bn, bk, bd), shapes = _stored(n, "cws")
        return (lambda x, r, c, be: ch.cws_encode_pallas(
            x, r, c, be, b_i=b, bn=bn, bk=bk, bd=bd)), shapes
    if name == "cws_encode_packed":
        (bn, bk, bd), shapes = _stored(n, "cws_packed")
        return (lambda x, r, c, be: ch.cws_encode_packed_pallas(
            x, r, c, be, b_i=b, bn=bn, bk=bk, bd=bd)), shapes
    if name == "cws_hash_rng":
        (bn, bk, bd), shapes = _regen(n, "cws_rng")
        return (lambda x, key: ch.cws_hash_rng_pallas(
            x, key, K, bn=bn, bk=bk, bd=bd)), shapes
    if name == "cws_encode_rng":
        (bn, bk, bd), shapes = _regen(n, "cws_rng")
        return (lambda x, key: ch.cws_encode_rng_pallas(
            x, key, K, b_i=b, bn=bn, bk=bk, bd=bd)), shapes
    if name == "cws_encode_rng_packed":
        (bn, bk, bd), shapes = _regen(n, "cws_rng_packed")
        return (lambda x, key: ch.cws_encode_rng_packed_pallas(
            x, key, K, b_i=b, bn=bn, bk=bk, bd=bd)), shapes
    if name == "min_sum":
        bm, bn, bd = registry.choose_blocks(n, D, n, op="min_sum")
        x = ((n, D), jnp.float32)
        return (lambda a, c: min_sum_pallas(a, c, bm=bm, bn=bn,
                                            bd=bd)), [x, x]
    raise KeyError(name)


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


@pytest.mark.parametrize("rows", registry.DEFAULT_SERVE_BUCKETS)
def test_serving_bucket_blocks_compile_for_v5e(one_chip, rows):
    """The serving ladder's small buckets give choose_blocks row blocks
    below the (8, 128) tile (down to one row); the fused encode kernel
    must still lower at each."""
    fn, shapes = _case("cws_encode", n=rows)
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)
