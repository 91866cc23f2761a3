"""The full kernel-contract suite: one call, one Report.

``run_suite()`` imports every module that self-registers probes and
analysis sites (kernels/ops, pipeline/featurize, training/linear_trainer,
kernels/flash_attention, plus the core numeric modules), then runs all
eight checks:

  completeness  — registry surface per op (impl trio, model, alias, probe)
  vmem          — _VMEM_MODELS vs declared BlockSpec+scratch footprints
  coverage      — index-map bounds + write-exactly-once per output block
  donation      — donated-and-returned / donated-caller-live (PR 4 rule)
  collectives   — bound axes, true-permutation ppermutes, blessed psums
  dtype_flow    — no implicit float narrowing, pinned dot accumulation,
                  f32 loop carries and pallas scratch (DESIGN.md §15)
  int_range     — interval proofs: shifts in [0,31], wrap only where
                  blessed, exact int<->float converts, in-table gathers
  determinism   — no backend-RNG / unblessed float scatters or stray
                  collectives; trio impls agree on jaxpr signatures

tools/kernel_lint.py is the CLI front end; CI runs it ``--all --strict``
on 1 and 8 devices so a new op family missing any contract fails the
build.
"""
from __future__ import annotations

import importlib
from typing import Iterable, Optional

from ..kernels import registry
from .collectives import audit_collectives
from .completeness import audit_completeness
from .coverage import audit_coverage
from .donation import audit_donation
from .dtype_flow import audit_dtype_flow, scratch_findings
from .intervals import audit_intervals
from .numerics import audit_determinism, audit_trio_signatures
from .report import CHECKS, Finding, Report
from .vmem import audit_family_vmem, audit_vmem, probe_footprints

__all__ = ["run_suite", "register_builtin_sites", "NUMERICS_CHECKS"]

NUMERICS_CHECKS = ("dtype_flow", "int_range", "determinism")

_SITE_MODULES = (
    "repro.kernels.ops",
    "repro.pipeline.featurize",
    "repro.training.linear_trainer",
    "repro.kernels.flash_attention",
    # core numeric modules self-register interval/dtype sites
    "repro.core.regen",
    "repro.core.hashing",
    "repro.core.linear_model",
    "repro.kernels.cws_hash",
)


def register_builtin_sites() -> None:
    """Import every module that self-registers probes/sites."""
    for mod in _SITE_MODULES:
        importlib.import_module(mod)


def _in_scope(op: str, fams) -> bool:
    """Whether ``op`` is named or launches one of the families ``fams``."""
    return op in fams or bool(set(registry.launch_families(op)) & set(fams))


def _coverage_blocks(fam: str):
    """One ragged-tail block choice per family: the heuristic pick at the
    representative shape — small grids, so coverage enumerates fully."""
    return registry.choose_blocks(48, 96, 160, op=fam)


def run_suite(families: Optional[Iterable[str]] = None, *,
              checks: Iterable[str] = CHECKS,
              exhaustive: bool = False) -> Report:
    register_builtin_sites()
    checks = tuple(checks)
    rep = Report()
    fams = tuple(families) if families else registry.model_families()

    if "completeness" in checks:
        found = audit_completeness()
        rep.extend(found)
        for op in registry.registered_ops():
            if families and not _in_scope(op, fams):
                continue
            rep.mark(op, "completeness", found)

    if "vmem" in checks:
        stats: dict = {}
        found = audit_vmem(fams, exhaustive=exhaustive, stats=stats)
        rep.extend(found)
        rep.stats["vmem"] = stats
        for fam in fams:
            rep.mark(fam, "vmem", found)

    if "coverage" in checks:
        for fam in fams:
            found = []
            for rec in probe_footprints(fam, _coverage_blocks(fam)):
                found.extend(audit_coverage(rec["launch"], target=fam))
            rep.extend(found)
            rep.mark(fam, "coverage", found)

    if "donation" in checks:
        for site in registry.donation_sites():
            case = site.build()
            found = audit_donation(case["fn"], case["args"],
                                   donate_argnums=case.get(
                                       "donate_argnums", ()),
                                   name=site.name)
            rep.extend(found)
            rep.mark(site.name, "donation", found)

    if "collectives" in checks:
        for site in registry.collective_sites():
            case = site.build()
            found = audit_collectives(
                case["fn"], case["args"], name=site.name,
                expected_psums=case.get("expected_psums"),
                expected_axes=case.get("expected_axes"))
            rep.extend(found)
            rep.mark(site.name, "collectives", found)

    # --- numerics checks over the registered numerics sites ---------------
    if any(c in checks for c in NUMERICS_CHECKS):
        for site in registry.numerics_sites():
            case = site.build()
            wanted = tuple(case.get("checks", NUMERICS_CHECKS))
            if "dtype_flow" in checks and "dtype_flow" in wanted:
                found = audit_dtype_flow(
                    case["fn"], case["args"], name=site.name,
                    allow_narrow=case.get("allow_narrow", ()))
                rep.extend(found)
                rep.mark(site.name, "dtype_flow", found)
            if "int_range" in checks and "int_range" in wanted:
                found = audit_intervals(
                    case["fn"], case["args"], name=site.name,
                    allow_wrap=case.get("allow_wrap", False))
                rep.extend(found)
                rep.mark(site.name, "int_range", found)
            if "determinism" in checks and "determinism" in wanted:
                found = audit_determinism(
                    case["fn"], case["args"], name=site.name,
                    allow=case.get("allow", ()))
                rep.extend(found)
                rep.mark(site.name, "determinism", found)

    # dtype_flow additionally audits every family probe's launch scratch
    # (the f32-accumulator contract) without retracing any call site
    if "dtype_flow" in checks:
        for fam in fams:
            found = []
            for rec in probe_footprints(fam, _coverage_blocks(fam)):
                found.extend(scratch_findings(rec["launch"], target=fam))
            rep.extend(found)
            rep.mark(fam, "dtype_flow", found)

    # determinism additionally requires every pallas-bearing op's trio to
    # agree on jaxpr signatures (and to HAVE a trio probe at all)
    if "determinism" in checks:
        found = audit_trio_signatures(families)
        rep.extend(found)
        for op in registry.registered_ops():
            if families and not _in_scope(op, fams):
                continue
            rep.mark(op, "determinism", found)

    return rep
