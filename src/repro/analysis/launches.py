"""Extract ``pl.pallas_call`` launch structure from traced jaxprs.

``extract_launches(fn, *args)`` traces ``fn`` (args may be
ShapeDtypeStructs — nothing executes or compiles) and walks the jaxpr
recursively through pjit/scan/shard_map/custom-vjp bodies, collecting one
:class:`PallasLaunch` per ``pallas_call`` equation.  Each launch records
the grid, every operand's block shape / padded operand shape / dtype /
memory space, a *callable* index map recovered from the BlockSpec's
``index_map_jaxpr`` (evaluable on concrete grid points), and the scratch
shapes declared by the kernel body.  This is the shared substrate for the
VMEM audit (repro.analysis.vmem) and the emit-coverage check
(repro.analysis.coverage).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Tuple

import jax
from jax.extend import core as jex_core

__all__ = ["OperandInfo", "PallasLaunch", "extract_launches",
           "launches_of_jaxpr"]


def _memory_space(aval_or_ms) -> str:
    """Normalize a Pallas memory-space annotation to 'vmem'/'smem'/'any'.
    Blocked operands default to VMEM when unannotated."""
    s = str(aval_or_ms).lower()
    if "smem" in s:
        return "smem"
    if "any" in s:
        return "any"
    return "vmem"


@dataclasses.dataclass(frozen=True)
class OperandInfo:
    role: str                         # "in" | "out" | "scratch"
    name: str                         # BlockSpec origin / positional label
    shape: Tuple[int, ...]            # padded operand shape (HBM view)
    block_shape: Optional[Tuple[int, ...]]   # None => whole-operand block
    dtype: object
    memory_space: str                 # "vmem" | "smem" | "any"
    index_map: Optional[Callable]     # grid point -> block indices

    @property
    def block_bytes(self) -> int:
        shape = self.block_shape if self.block_shape is not None else self.shape
        return math.prod(shape) * jax.dtypes.canonicalize_dtype(
            self.dtype).itemsize

    def block_grid(self) -> Tuple[int, ...]:
        """Number of blocks along each operand axis (padded shape / block)."""
        if self.block_shape is None:
            return tuple(1 for _ in self.shape)
        return tuple(-(-s // b) for s, b in zip(self.shape, self.block_shape))


@dataclasses.dataclass(frozen=True)
class PallasLaunch:
    name: str
    grid: Tuple[int, ...]
    inputs: Tuple[OperandInfo, ...]
    outputs: Tuple[OperandInfo, ...]
    scratch: Tuple[OperandInfo, ...]

    @property
    def operands(self) -> Tuple[OperandInfo, ...]:
        return self.inputs + self.outputs + self.scratch

    def vmem_bytes(self) -> int:
        """Single-buffered per-step working set: one copy of every VMEM
        operand block plus declared scratch.  SMEM operands (scalar
        prefetch like the regen key) are excluded — they do not draw from
        the VMEM budget the registry models."""
        return sum(o.block_bytes for o in self.operands
                   if o.memory_space != "smem")


def _index_map_fn(block_mapping) -> Optional[Callable]:
    cj = getattr(block_mapping, "index_map_jaxpr", None)
    if cj is None:
        return None

    def run(*grid_point):
        out = jax.core.eval_jaxpr(cj.jaxpr, cj.consts, *grid_point)
        return tuple(int(v) for v in out)
    return run


def _block_dim(b) -> int:
    """Block extent of one dim: a plain int, or a ``pl.Blocked`` /
    ``pl.Element`` wrapper carrying ``block_size``; a squeezed dim is 1."""
    if isinstance(b, int):
        return b
    return int(getattr(b, "block_size", 1))


def _launch_of_eqn(eqn) -> PallasLaunch:
    gm = eqn.params["grid_mapping"]
    grid = tuple(int(g) for g in gm.grid)
    n_in, n_out = gm.num_inputs, gm.num_outputs
    n_scratch = gm.num_scratch_operands

    def operand(role, bm, padded):
        block = tuple(_block_dim(b) for b in bm.block_shape)
        return OperandInfo(
            role=role,
            name=str(getattr(bm, "origin", "") or role),
            shape=tuple(int(s) for s in padded.shape),
            block_shape=block,
            dtype=padded.dtype,
            memory_space=_memory_space(bm.block_aval),
            index_map=_index_map_fn(bm),
        )

    bms = list(gm.block_mappings)
    in_shapes = list(gm.in_shapes)
    out_shapes = list(gm.out_shapes)
    inputs = tuple(operand("in", bm, sd)
                   for bm, sd in zip(bms[:n_in], in_shapes))
    outputs = tuple(operand("out", bm, sd)
                    for bm, sd in zip(bms[n_in:n_in + n_out], out_shapes))

    # Scratch shapes live on the kernel body's trailing invars.
    body = eqn.params["jaxpr"]
    invars = body.jaxpr.invars if hasattr(body, "jaxpr") else body.invars
    scratch = []
    for v in invars[len(invars) - n_scratch:] if n_scratch else []:
        aval = v.aval
        scratch.append(OperandInfo(
            role="scratch", name="scratch",
            shape=tuple(int(s) for s in aval.shape),
            block_shape=tuple(int(s) for s in aval.shape),
            dtype=aval.dtype,
            memory_space=_memory_space(getattr(aval, "memory_space", "vmem")),
            index_map=None,
        ))
    name = str(eqn.params.get("name_and_src_info", "")) or "pallas_call"
    return PallasLaunch(name=name.split(" ")[0], grid=grid,
                        inputs=inputs, outputs=outputs,
                        scratch=tuple(scratch))


def _walk(jaxpr, out, seen) -> None:
    if id(jaxpr) in seen:
        return
    seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(_launch_of_eqn(eqn))
            continue
        for val in eqn.params.values():
            if isinstance(val, jex_core.ClosedJaxpr):
                _walk(val.jaxpr, out, seen)
            elif isinstance(val, jex_core.Jaxpr):
                _walk(val, out, seen)
            elif isinstance(val, (tuple, list)):
                for item in val:
                    if isinstance(item, jex_core.ClosedJaxpr):
                        _walk(item.jaxpr, out, seen)
                    elif isinstance(item, jex_core.Jaxpr):
                        _walk(item, out, seen)


def launches_of_jaxpr(closed_jaxpr) -> Tuple[PallasLaunch, ...]:
    out: list = []
    _walk(closed_jaxpr.jaxpr, out, set())
    return tuple(out)


def extract_launches(fn, *args, **kwargs) -> Tuple[PallasLaunch, ...]:
    """Trace ``fn(*args, **kwargs)`` and return every pallas_call launch
    reachable from its jaxpr.  Args may be jax.ShapeDtypeStruct."""
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return launches_of_jaxpr(closed)
