"""Determinism audit: reproducibility contracts over traced jaxprs.

Two halves, both ``check="determinism"``:

``audit_determinism(fn, args)`` walks the traced jaxpr and flags
primitives that can break the repo's bit-identical guarantees
(streamed == full-batch, resume == uninterrupted, pallas ==
reference):

  * backend-dependent RNG (``rng_bit_generator``/``rng_uniform``) —
    output bits differ across TPU/CPU, unlike the counter-based
    threefry the repo hand-rolls;
  * order-sensitive float scatter-accumulation (``scatter-add`` and
    friends on inexact operands) — associativity is not guaranteed in
    general; sites where XLA's deterministic lowering is relied on
    (the embedding-bag backward) must bless it explicitly via
    ``allow=("scatter-add",)`` so the reliance is recorded;
  * cross-device reductions (psum/all-reduce/all-gather/ppermute)
    outside the blessed collective sites — those sites carry their own
    axis/psum-count contract (repro.analysis.collectives); any other
    site reducing across devices must either move under that contract
    or bless the primitive by name.

Integer scatter-adds are exempt: integer addition is associative, so
ordering cannot change the result.

``audit_trio_signatures()`` checks, for every registered
:class:`~repro.kernels.registry.TrioProbe`, that each impl of the trio
(pallas / pallas-interpret / reference) accepts the same probe
arguments and produces byte-for-byte identical output
shape/dtype trees under ``jax.eval_shape`` — the signature-level half
of the bit-identical trio guarantee (the value-level half lives in the
equivalence tests).  Ops that register a pallas impl but no trio probe
are themselves findings, completeness-style, so the catalog cannot
silently rot.
"""
from __future__ import annotations

import functools
from typing import Iterable, List, Optional

import jax
import numpy as np
from jax.extend import core as jex_core

from ..kernels import registry
from .report import Finding

__all__ = ["audit_determinism", "audit_trio_signatures",
           "NONDETERMINISTIC_PRIMS", "ORDER_SENSITIVE_SCATTERS",
           "COLLECTIVE_PRIMS"]

NONDETERMINISTIC_PRIMS = ("rng_bit_generator", "rng_uniform")
ORDER_SENSITIVE_SCATTERS = ("scatter-add", "scatter_add", "scatter-mul",
                            "scatter_mul")
# "psum2" and "psum_invariant" (shard_map with replication checks on)
# are internal spellings of psum; they canonicalize to "psum" for both
# detection and per-site blessing
COLLECTIVE_PRIMS = ("psum", "psum2", "psum_invariant", "all_gather",
                    "all_to_all", "ppermute", "reduce_scatter", "pmax",
                    "pmin")
_CANONICAL = {"psum2": "psum", "psum_invariant": "psum"}


def _is_float(dt) -> bool:
    return jax.numpy.issubdtype(jax.dtypes.canonicalize_dtype(dt),
                                jax.numpy.floating)


def _walk(jaxpr, visit, seen) -> None:
    if id(jaxpr) in seen:
        return
    seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        visit(eqn)
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                _walk(sub, visit, seen)


def _subjaxprs(val):
    if isinstance(val, jex_core.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jex_core.Jaxpr):
        yield val
    elif isinstance(val, (tuple, list)):
        for item in val:
            yield from _subjaxprs(item)


def audit_determinism(fn, args, *, name: str = "fn",
                      allow: Iterable[str] = ()) -> List[Finding]:
    """Trace ``fn(*args)`` and flag reproducibility hazards.

    ``allow`` blesses primitives by name (e.g. ``("scatter-add",)``
    where XLA's deterministic scatter lowering is a recorded
    dependency, or ``("psum",)`` for a site that is its own collective
    contract)."""
    from .intervals import trace_args
    allow = tuple(allow)
    closed = jax.make_jaxpr(fn)(*trace_args(args))
    findings: List[Finding] = []
    seen_msgs = set()

    def emit(message, **details):
        if message in seen_msgs:
            return
        seen_msgs.add(message)
        findings.append(Finding(check="determinism", target=name,
                                message=message, details=details))

    def visit(eqn):
        pname = eqn.primitive.name
        canonical = _CANONICAL.get(pname, pname)
        if pname in allow or canonical in allow:
            return
        if pname in NONDETERMINISTIC_PRIMS:
            emit(f"{pname}: backend-dependent RNG — output bits differ "
                 f"across TPU/CPU backends, breaking pallas/reference "
                 f"parity; use the counter-based threefry in "
                 f"core/regen.py instead", prim=pname)
        elif pname in ORDER_SENSITIVE_SCATTERS:
            operand_dt = eqn.invars[0].aval.dtype
            if _is_float(operand_dt):
                emit(f"{pname} on {np.dtype(operand_dt).name}: float "
                     f"scatter-accumulation is order-sensitive in "
                     f"general; if this site relies on XLA's "
                     f"deterministic lowering (embedding-bag backward), "
                     f"record it with allow=('scatter-add',)",
                     prim=pname, dtype=np.dtype(operand_dt).name)
        elif pname in COLLECTIVE_PRIMS:
            emit(f"{pname}: cross-device reduction outside the blessed "
                 f"collective sites — register the caller via "
                 f"register_collective_site (axis/psum contract) or "
                 f"bless {pname!r} explicitly on this numerics site",
                 prim=pname)

    _walk(closed.jaxpr, visit, set())
    return findings


def _sig_of(tree) -> list:
    return [(tuple(leaf.shape), np.dtype(
        jax.dtypes.canonicalize_dtype(leaf.dtype)).name)
        for leaf in jax.tree_util.tree_leaves(tree)]


def audit_trio_signatures(
        families: Optional[Iterable[str]] = None) -> List[Finding]:
    """Signature-agreement check across each registered impl trio."""
    findings: List[Finding] = []
    fams = tuple(families) if families else None

    def in_scope(op: str) -> bool:
        if fams is None:
            return True
        return registry.family(op) in fams or op in fams

    probed = set()
    for probe in registry.trio_probes():
        probed.add(probe.op)
        if not in_scope(probe.op):
            continue
        args, kwargs = probe.build()
        sigs = {}
        for impl_name in probe.impls:
            try:
                impl = registry.lookup(probe.op, impl_name)
            except KeyError:
                findings.append(Finding(
                    check="determinism", target=probe.op,
                    message=f"trio probe names impl {impl_name!r} but "
                            f"the registry has no such impl for "
                            f"{probe.op!r} — register it or fix the "
                            f"probe's impls tuple",
                    details={"impl": impl_name}))
                continue
            try:
                out = jax.eval_shape(
                    functools.partial(impl.fn, **kwargs), *args)
            except Exception as e:  # trace failure is itself a finding
                findings.append(Finding(
                    check="determinism", target=probe.op,
                    message=f"impl {impl_name!r} failed to trace on the "
                            f"trio probe args: {type(e).__name__}: {e}",
                    details={"impl": impl_name}))
                continue
            sigs[impl_name] = _sig_of(out)
        if len(sigs) >= 2:
            ref_name = probe.impls[0] if probe.impls[0] in sigs \
                else sorted(sigs)[0]
            ref = sigs[ref_name]
            for impl_name, sig in sigs.items():
                if sig != ref:
                    findings.append(Finding(
                        check="determinism", target=probe.op,
                        message=f"impl {impl_name!r} output signature "
                                f"{sig} disagrees with {ref_name!r} "
                                f"{ref} — the trio must agree on "
                                f"shape/dtype at the jaxpr level for "
                                f"bit-identical parity to be possible",
                        details={"impl": impl_name,
                                 "sig": [list(s) for s in sig],
                                 "ref": [list(s) for s in ref]}))

    for op in registry.registered_ops():
        if not in_scope(op):
            continue
        impls = registry.impl_names(op)
        if "pallas" in impls and op not in probed:
            findings.append(Finding(
                check="determinism", target=op,
                message=f"op {op!r} has a pallas impl but no trio "
                        f"probe — register_trio({op!r}, build=...) in "
                        f"kernels/ops.py so the signature contract "
                        f"covers it",
                details={"impls": sorted(impls)}))
    return findings
