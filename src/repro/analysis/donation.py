"""Donation-safety check: the PR 4 alias bug as a lint rule.

Two rules over a traced jaxpr (trace under ``registry.force_donation()``
so the TPU-shaped ``donated_invars`` exist on any host):

  **(a) donated-and-returned** — for an entry point declaring
  ``donate_argnums``, no output may alias a donated input through a
  chain of view ops (reshape/transpose/zero-pad/full-slice/same-dtype
  convert).  XLA reuses donated buffers; an aliased return hands the
  caller freed memory.  ``jnp.copy`` (the ``copy`` primitive) is the
  sanctioned break in the chain.

  **(b) donated caller-live buffer** — walking a *caller*'s jaxpr, every
  operand a nested jit donates must be a dead transfer: its alias roots
  may not be closure constants, may not appear in the caller's outputs,
  and may not have any use besides the donating call.  The shipped PR 4
  bug was exactly this shape: ``jnp.pad`` with a statically-zero pad
  config passes the caller's live ``x`` straight through to a donating
  launch.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

import jax
from jax.extend import core as jex_core

from .report import Finding

__all__ = ["audit_donation", "alias_roots"]

_MAX_DEPTH = 8

_CALL_PRIMS = ("jit", "pjit", "closed_call", "core_call", "remat", "remat2",
               "custom_jvp_call", "custom_vjp_call")


def _is_var(v) -> bool:
    return not isinstance(v, jex_core.Literal)


def _subjaxpr(params) -> Optional[object]:
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        cj = params.get(key)
        if cj is not None:
            return cj
    return None


def _view_sources(eqn, outvar, depth: int) -> List[object]:
    """Input atoms ``outvar`` may alias through this equation; [] when the
    op materializes fresh memory (or explicitly copies)."""
    p = eqn.primitive.name
    if p in ("reshape", "squeeze", "expand_dims", "transpose", "rev"):
        return [eqn.invars[0]]
    if p == "convert_element_type":
        if eqn.invars[0].aval.dtype == outvar.aval.dtype:
            return [eqn.invars[0]]
        return []
    if p == "broadcast_in_dim":
        if tuple(eqn.invars[0].aval.shape) == tuple(outvar.aval.shape):
            return [eqn.invars[0]]
        return []
    if p == "pad":
        cfg = eqn.params.get("padding_config", ())
        if all(lo == 0 and hi == 0 and inner == 0 for lo, hi, inner in cfg):
            return [eqn.invars[0]]
        return []
    if p == "slice":
        aval = eqn.invars[0].aval
        start = eqn.params.get("start_indices", ())
        limit = eqn.params.get("limit_indices", ())
        strides = eqn.params.get("strides")
        if (all(s == 0 for s in start)
                and tuple(limit) == tuple(aval.shape)
                and (strides is None or all(s == 1 for s in strides))):
            return [eqn.invars[0]]
        return []
    if p in _CALL_PRIMS and depth > 0:
        cj = _subjaxpr(eqn.params)
        if cj is None:
            return []
        inner = cj.jaxpr if isinstance(cj, jex_core.ClosedJaxpr) else cj
        try:
            pos = eqn.outvars.index(outvar)
        except ValueError:
            return []
        inner_out = inner.outvars[pos]
        if not _is_var(inner_out):
            return []
        out = []
        for root in alias_roots(inner, inner_out, depth - 1):
            if root in inner.invars:
                outer = eqn.invars[inner.invars.index(root)]
                if _is_var(outer):
                    out.append(outer)
            # inner constvars / fresh producers do not alias caller memory
        return out
    return []


def _producers(jaxpr) -> Dict[object, object]:
    return {ov: eqn for eqn in jaxpr.eqns for ov in eqn.outvars}


def alias_roots(jaxpr, var, depth: int = _MAX_DEPTH) -> Set[object]:
    """The set of vars in ``jaxpr`` that ``var`` may share a buffer with:
    invars/constvars, or outputs of fresh-memory-producing equations,
    reached through view chains (recursing through nested jits)."""
    prod = _producers(jaxpr)
    roots: Set[object] = set()
    stack = [var]
    seen: Set[int] = set()
    while stack:
        v = stack.pop()
        if not _is_var(v) or id(v) in seen:
            continue
        seen.add(id(v))
        eqn = prod.get(v)
        if eqn is None:            # invar or constvar at this level
            roots.add(v)
            continue
        srcs = _view_sources(eqn, v, depth)
        if srcs:
            stack.extend(srcs)
        else:
            roots.add(v)           # materialized fresh here
    return roots


def _donated_leaf_indices(args, donate_argnums) -> List[int]:
    """Python-level donate_argnums -> flat jaxpr invar indices."""
    counts = [len(jax.tree_util.tree_leaves(a)) for a in args]
    offsets = [sum(counts[:i]) for i in range(len(counts))]
    out: List[int] = []
    for argnum in donate_argnums:
        out.extend(range(offsets[argnum], offsets[argnum] + counts[argnum]))
    return out


def _use_counts(jaxpr) -> Dict[object, int]:
    uses: Dict[object, int] = {}
    for eqn in jaxpr.eqns:
        for v in eqn.invars:
            if _is_var(v):
                uses[v] = uses.get(v, 0) + 1
    for v in jaxpr.outvars:
        if _is_var(v):
            uses[v] = uses.get(v, 0) + 1
    return uses


def _audit_caller_level(jaxpr, name: str, depth: int,
                        findings: List[Finding]) -> None:
    uses = _use_counts(jaxpr)
    constvars = set(jaxpr.constvars)
    outvars = {v for v in jaxpr.outvars if _is_var(v)}
    for eqn in jaxpr.eqns:
        donated = eqn.params.get("donated_invars")
        sub = _subjaxpr(eqn.params) if eqn.primitive.name in _CALL_PRIMS \
            else None
        if donated and any(donated):
            callee = eqn.params.get("name", eqn.primitive.name)
            for pos, don in enumerate(donated):
                if not don or not _is_var(eqn.invars[pos]):
                    continue
                operand = eqn.invars[pos]
                for root in alias_roots(jaxpr, operand, depth):
                    if root in constvars:
                        findings.append(Finding(
                            check="donation", target=name,
                            message=(f"call {callee!r} donates operand "
                                     f"{pos}, which aliases a closure "
                                     f"constant of the caller — a captured "
                                     f"array would be freed under the "
                                     f"caller's feet; pass a fresh buffer "
                                     f"or jnp.copy it")))
                    elif root in outvars:
                        findings.append(Finding(
                            check="donation", target=name,
                            message=(f"call {callee!r} donates operand "
                                     f"{pos}, which aliases a value the "
                                     f"caller also RETURNS — the returned "
                                     f"buffer is freed by the donation; "
                                     f"jnp.copy one of the two")))
                    elif uses.get(root, 0) > 1:
                        findings.append(Finding(
                            check="donation", target=name,
                            message=(f"call {callee!r} donates operand "
                                     f"{pos}, which aliases a caller "
                                     f"buffer with other live uses (e.g. "
                                     f"a zero-pad/reshape pass-through of "
                                     f"an argument used again later — the "
                                     f"PR 4 bug shape); slice/copy a dead "
                                     f"buffer into the donating call or "
                                     f"use a non-donating twin")))
        # recurse into nested bodies so donation inside shard_map/scan
        # callers is audited at its own level
        if sub is None:
            for val in eqn.params.values():
                if isinstance(val, (jex_core.Jaxpr, jex_core.ClosedJaxpr)):
                    sub = val
                    break
        if sub is not None and depth > 0:
            inner = sub.jaxpr if isinstance(sub, jex_core.ClosedJaxpr) \
                else sub
            _audit_caller_level(inner, name, depth - 1, findings)


def audit_donation(fn, args, *, donate_argnums: Tuple[int, ...] = (),
                   name: str = "donation-site") -> List[Finding]:
    """Trace ``fn(*args)`` and apply rules (a) and (b).

    ``donate_argnums`` declares the entry point's own donation for rule
    (a); rule (b) always scans for nested donating jits (build the jits
    under ``registry.force_donation()`` for a faithful TPU-shaped trace).
    """
    findings: List[Finding] = []
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as e:          # a site that cannot trace is a finding
        return [Finding(
            check="donation", target=name,
            message=f"entry point failed to trace: {type(e).__name__}: {e}")]
    jaxpr = closed.jaxpr
    donated_idx = _donated_leaf_indices(args, donate_argnums)
    donated_vars = {jaxpr.invars[i] for i in donated_idx}
    if donated_vars:
        for opos, ov in enumerate(jaxpr.outvars):
            if not _is_var(ov):
                continue
            hit = alias_roots(jaxpr, ov) & donated_vars
            if hit:
                argpos = jaxpr.invars.index(next(iter(hit)))
                findings.append(Finding(
                    check="donation", target=name,
                    message=(f"output {opos} aliases donated input "
                             f"{argpos} through a view chain — the caller "
                             f"receives a freed buffer on TPU; return "
                             f"jnp.copy(...) or drop the argnum from "
                             f"donate_argnums"),
                    details={"output": opos, "donated_input": argpos}))
    _audit_caller_level(jaxpr, name, _MAX_DEPTH, findings)
    return findings
