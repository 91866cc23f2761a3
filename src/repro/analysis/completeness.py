"""Registry completeness check: the gate every new op family must pass.

For every registered op:

  * an op with a ``pallas`` impl must also register ``pallas-interpret``
    and ``reference`` (the correctness ladder the tests climb), and every
    block family it launches (``registry.launch_families``) must have a
    `_VMEM_MODELS` entry and register at least one LaunchProbe so the
    VMEM/coverage checks can actually see its BlockSpecs;
  * schedule families (ops dispatched by named schedule rather than
    backend, e.g. ``attention``) must register ``reference`` plus their
    expected schedule set;
  * all impls of one op must agree on parameter names and kinds — a
    drifted signature breaks registry dispatch silently.
"""
from __future__ import annotations

import inspect
from typing import Iterable, List, Optional

from ..kernels import registry
from .report import Finding

__all__ = ["audit_completeness", "EXPECTED_SCHEDULES"]

# Ops dispatched by named schedule instead of the pallas/interpret/
# reference backend trio, with the schedules each must expose.
EXPECTED_SCHEDULES = {
    "attention": {"reference", "flash", "flash_allgather", "flash_ring"},
}


def _signature_params(fn):
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return None
    return tuple((p.name, p.kind) for p in sig.parameters.values())


def _family_findings(op: str, fam: str) -> List[Finding]:
    """One block family that ``op`` launches: it needs a VMEM model and a
    LaunchProbe."""
    if not registry.has_vmem_model(fam):
        return [Finding(
            check="completeness", target=op,
            message=(f"op {op!r} (family {fam!r}) has no "
                     f"_VMEM_MODELS entry — choose_blocks/"
                     f"block_candidates cannot budget its tiles; "
                     f"add the model (and, for an op named apart "
                     f"from its family, a _FAMILY_ALIASES entry) "
                     f"in kernels/registry.py"))]
    if not registry.family_probes(fam):
        return [Finding(
            check="completeness", target=op,
            message=(f"family {fam!r} registers no LaunchProbe — "
                     f"the VMEM/coverage audits cannot inspect its "
                     f"BlockSpecs; add registry.register_probe"
                     f"({fam!r}, op=...) in kernels/ops.py"))]
    return []


def audit_completeness(ops: Optional[Iterable[str]] = None
                       ) -> List[Finding]:
    findings: List[Finding] = []
    for op in (ops or registry.registered_ops()):
        impls = set(registry.impl_names(op))
        if op in EXPECTED_SCHEDULES:
            missing = EXPECTED_SCHEDULES[op] - impls
            if missing:
                findings.append(Finding(
                    check="completeness", target=op,
                    message=(f"schedule family {op!r} is missing "
                             f"{sorted(missing)} (has {sorted(impls)}) — "
                             f"register the schedule or update "
                             f"EXPECTED_SCHEDULES")))
        elif "pallas" in impls:
            missing = {"pallas", "pallas-interpret", "reference"} - impls
            if missing:
                findings.append(Finding(
                    check="completeness", target=op,
                    message=(f"op {op!r} has a pallas impl but is missing "
                             f"{sorted(missing)} — every kernel op needs "
                             f"the interpret twin (kernel-parity tests) "
                             f"and the pure-JAX reference (the oracle)")))
            for fam in registry.launch_families(op):
                findings.extend(_family_findings(op, fam))
        elif "reference" not in impls:
            findings.append(Finding(
                check="completeness", target=op,
                message=(f"op {op!r} registers {sorted(impls)} but no "
                         f"reference impl — nothing to test against")))

        sigs = {}
        for impl in sorted(impls):
            params = _signature_params(registry.lookup(op, impl).fn)
            if params is not None:
                sigs.setdefault(params, []).append(impl)
        if len(sigs) > 1:
            groups = [f"{names} -> ({', '.join(p[0] for p in params)})"
                      for params, names in sorted(
                          sigs.items(), key=lambda kv: kv[1])]
            findings.append(Finding(
                check="completeness", target=op,
                message=(f"impls of {op!r} disagree on signatures: "
                         f"{'; '.join(groups)} — registry dispatch "
                         f"passes one kwarg set to all of them")))
    return findings
