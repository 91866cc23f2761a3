"""Production mesh construction (single-pod 16x16, multi-pod 2x16x16).

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (required so smoke tests see 1 CPU device while
the dry-run sees 512 forced host devices).

Every mesh here has ``Auto`` axes: shardings are propagated by the
compiler, as the data-parallel paths (shard_map launches, eager gathers
on sharded batches) are written for.  ``jax.make_mesh`` otherwise
defaults to ``Explicit`` axes, under which an eager ``jnp.take`` on a
row-sharded index batch is refused.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, **kw):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         **kw)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist, as a (data=N, model=1) mesh — used by the
    CPU examples and the single-host training driver."""
    n = len(jax.devices())
    return _mesh((n, 1), ("data", "model"))


def make_data_mesh(ndev: int | None = None):
    """A pure data-parallel (data=ndev, model=1) mesh over the first
    ``ndev`` devices — the shape the streamed trainer shard_maps over.
    ``None`` takes every device (same as make_local_mesh)."""
    n = len(jax.devices()) if ndev is None else ndev
    if n > len(jax.devices()):
        raise ValueError(f"asked for {n} devices but only "
                         f"{len(jax.devices())} exist")
    return _mesh((n, 1), ("data", "model"), devices=jax.devices()[:n])


def data_axis_size(mesh) -> int:
    """Number of devices along the ``data`` axis — the shard count for
    every data-parallel launch (featurize chunks, minibatch grads)."""
    if "data" not in mesh.shape:
        raise ValueError(
            f"mesh axes {tuple(mesh.shape)} carry no 'data' axis; "
            f"data-parallel paths shard over 'data' (see make_*_mesh)")
    return mesh.shape["data"]


def axis_size(mesh, axes) -> int:
    """Product of the named mesh axis sizes.  ``axes`` is a name, a tuple
    of names, or None/() -> 1.  THE one spot that turns an axis-name
    spec into a shard count — shared by the attention routing
    (ring-vs-all-gather threshold) and the flash shard_map wrappers."""
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size
