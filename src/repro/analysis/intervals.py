"""Integer-range abstract interpretation over jaxprs (DESIGN.md §15).

``audit_intervals(fn, args)`` traces ``fn`` (args may be
ShapeDtypeStructs, concrete arrays, or :class:`IVal` range seeds —
nothing executes) and walks the jaxpr propagating a per-value interval
``[lo, hi]`` (elementwise numpy float64 bounds where cheap, scalar
summaries otherwise).  The domain is deliberately small — the numeric
hot paths this repo ships (threefry rounds, shift/or word packing,
per-hash offset arithmetic, embedding-bag gathers) are loops of a ~30
primitive vocabulary — and the checks are the contracts DESIGN.md §15
catalogs:

  * every shift amount provably lands in ``[0, bitwidth-1]``;
  * integer add/sub/mul/shift never wraps its dtype, except at sites
    that declare ``allow_wrap`` (threefry WANTS mod-2^32 adds);
  * integer->float conversions are exact (the operand range fits the
    target mantissa — the ``bits >> 8`` uniform contract);
  * float->int conversions are dominated by a clamp into the target
    range;
  * narrowing integer conversions cannot drop value bits;
  * gather indices provably stay inside the gathered table.

Float arithmetic is tracked only monotonically (clamp/min/max/floor);
anything else widens to ±inf, which is sound for every check above.
NaN is not modeled — a NaN reaching a float->int cast is undefined on
both sides of the abstraction.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.extend import core as jex_core

from .report import Finding

__all__ = ["IVal", "unknown_ival", "audit_intervals", "trace_args"]

# Above this many elements, iota/constant bounds collapse to scalar
# [min, max] summaries so auditing 2^23-hash boundary shapes stays O(1)
# in memory.
_ELEMENTWISE_LIMIT = 1 << 20

_F32_EXACT = float(1 << 24)      # ints with |v| <= 2^mant convert exactly
_MANTISSA = {"float64": 53, "float32": 24, "bfloat16": 8, "float16": 11}


def _is_int(dt) -> bool:
    return np.issubdtype(np.dtype(dt), np.integer)


def _is_float(dt) -> bool:
    d = jax.dtypes.canonicalize_dtype(dt)
    return jax.numpy.issubdtype(d, jax.numpy.floating)


def _dtype_range(dt) -> Tuple[float, float]:
    d = np.dtype(jax.dtypes.canonicalize_dtype(dt))
    if d == np.bool_:
        return 0.0, 1.0
    if np.issubdtype(d, np.integer):
        info = np.iinfo(d)
        return float(info.min), float(info.max)
    return -np.inf, np.inf


def _bitwidth(dt) -> int:
    return np.dtype(jax.dtypes.canonicalize_dtype(dt)).itemsize * 8


@dataclasses.dataclass(frozen=True)
class IVal:
    """Abstract value: shape/dtype plus elementwise [lo, hi] bounds.

    ``lo``/``hi`` are numpy float64 arrays broadcastable to ``shape``
    (often 0-d summaries); ``lo == hi`` everywhere means the value is
    known exactly.  float64 endpoints are exact for every integer this
    repo computes (< 2^53)."""
    shape: Tuple[int, ...]
    dtype: object
    lo: np.ndarray
    hi: np.ndarray

    @property
    def known(self) -> bool:
        return bool(np.all(self.lo == self.hi))

    def summary(self) -> Tuple[float, float]:
        return float(np.min(self.lo)), float(np.max(self.hi))


def _mk(shape, dtype, lo, hi) -> IVal:
    lo = np.asarray(lo, np.float64)
    hi = np.asarray(hi, np.float64)
    # inf - inf style endpoints: widen, never NaN
    lo = np.where(np.isnan(lo), -np.inf, lo)
    hi = np.where(np.isnan(hi), np.inf, hi)
    return IVal(tuple(shape), dtype, lo, hi)


def _top(shape, dtype) -> IVal:
    lo, hi = _dtype_range(dtype)
    return _mk(shape, dtype, lo, hi)


def _const(x) -> IVal:
    arr = np.asarray(x)
    if arr.dtype == np.bool_:
        arr = arr.astype(np.float64)
    if arr.size > _ELEMENTWISE_LIMIT:
        v = arr.astype(np.float64, copy=False)
        return _mk(arr.shape, np.asarray(x).dtype, v.min(), v.max())
    v = arr.astype(np.float64)
    return _mk(arr.shape, np.asarray(x).dtype, v, v)


def unknown_ival(shape, dtype, lo=None, hi=None) -> IVal:
    """An input seed: any value of ``dtype`` within [lo, hi] (defaults
    to the full dtype range / ±inf for floats)."""
    dlo, dhi = _dtype_range(dtype)
    return _mk(tuple(shape), dtype,
               dlo if lo is None else lo, dhi if hi is None else hi)


def _is_ival(x) -> bool:
    return isinstance(x, IVal)


def trace_args(args) -> tuple:
    """IVal seeds -> ShapeDtypeStructs, through arbitrary pytrees
    (NamedTuple params etc.); everything else passes through.  Shared by
    the numerics checks so one site ``args`` tuple serves the interval,
    dtype-flow, and determinism audits."""
    return tuple(jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if isinstance(a, IVal) else a, args, is_leaf=_is_ival))


def _seed(a) -> IVal:
    if isinstance(a, IVal):
        return a
    if isinstance(a, jax.ShapeDtypeStruct):
        return _top(a.shape, a.dtype)
    return _const(a)


def _mask_below(hi: np.ndarray) -> np.ndarray:
    """Smallest all-ones mask >= hi, elementwise (hi nonneg, < 2^63)."""
    h = np.clip(np.nan_to_num(np.asarray(hi, np.float64),
                              posinf=float(2 ** 63 - 1)),
                0, float(2 ** 63 - 1)).astype(np.uint64)
    for s in (1, 2, 4, 8, 16, 32):
        h = h | (h >> np.uint64(s))
    return h


class _Interp:
    """One interval-interpretation run; findings dedupe by message."""

    def __init__(self, *, name: str, allow_wrap: bool = False):
        self.name = name
        self.allow_wrap = allow_wrap
        self.findings: List[Finding] = []
        self._seen = set()

    # -- findings ------------------------------------------------------

    def emit(self, message: str, **details) -> None:
        if message in self._seen:
            return
        self._seen.add(message)
        self.findings.append(Finding(
            check="int_range", target=self.name, message=message,
            details=details))

    # -- core loop -----------------------------------------------------

    def run(self, jaxpr, consts, in_vals: List[IVal]) -> List[IVal]:
        env: Dict[object, IVal] = {}

        def read(atom) -> IVal:
            if isinstance(atom, jex_core.Literal):
                return _const(atom.val)
            return env.get(atom) or _top(atom.aval.shape, atom.aval.dtype)

        for var, c in zip(jaxpr.constvars, consts):
            env[var] = _const(c)
        for var, val in zip(jaxpr.invars, in_vals):
            env[var] = val
        for eqn in jaxpr.eqns:
            ins = [read(x) for x in eqn.invars]
            outs = self.eqn(eqn, ins)
            for var, val in zip(eqn.outvars, outs):
                env[var] = val
        return [read(x) for x in jaxpr.outvars]

    def run_closed(self, closed, in_vals) -> List[IVal]:
        return self.run(closed.jaxpr, closed.consts, in_vals)

    def _tops(self, eqn) -> List[IVal]:
        return [_top(v.aval.shape, v.aval.dtype) for v in eqn.outvars]

    def eqn(self, eqn, ins: List[IVal]) -> List[IVal]:
        name = eqn.primitive.name
        handler = getattr(self, "p_" + name.replace("-", "_"), None)
        if handler is not None:
            out = handler(eqn, ins)
            return out if isinstance(out, list) else [out]
        if name in ("jit", "pjit", "closed_call", "core_call", "remat_call",
                    "checkpoint", "custom_jvp_call", "custom_vjp_call",
                    "custom_vjp_call_jaxpr"):
            sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr") \
                or eqn.params.get("fun_jaxpr")
            if sub is not None:
                if hasattr(sub, "consts"):
                    return self.run_closed(sub, ins[:len(sub.in_avals)])
                return self.run(sub, (), ins)
            return self._tops(eqn)
        if name == "cond":
            branches = eqn.params["branches"]
            outs = [self.run_closed(br, ins[1:]) for br in branches]
            return [self._join([o[i] for o in outs])
                    for i in range(len(outs[0]))]
        if name in ("scan", "while"):
            # Run the body once on TOP carries so findings inside loops
            # still fire; outputs widen to TOP (a fixpoint would buy
            # nothing for the contracts checked here).
            sub = eqn.params.get("jaxpr") or eqn.params.get("body_jaxpr")
            if sub is not None:
                body = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                self.run(body, getattr(sub, "consts", ()),
                         [_top(v.aval.shape, v.aval.dtype)
                          for v in body.invars])
            return self._tops(eqn)
        # unknown primitive: sound TOP of the output avals
        return self._tops(eqn)

    @staticmethod
    def _join(vals: List[IVal]) -> IVal:
        lo = vals[0].lo
        hi = vals[0].hi
        for v in vals[1:]:
            lo = np.minimum(lo, v.lo)
            hi = np.maximum(hi, v.hi)
        return _mk(vals[0].shape, vals[0].dtype, lo, hi)

    # -- int overflow policy -------------------------------------------

    def _wrap_check(self, eqn, shape, dtype, lo, hi, what: str) -> IVal:
        if not _is_int(dtype):
            return _mk(shape, dtype, lo, hi)
        dlo, dhi = _dtype_range(dtype)
        if np.any(hi > dhi) or np.any(lo < dlo):
            if not self.allow_wrap:
                slo, shi = float(np.min(lo)), float(np.max(hi))
                self.emit(
                    f"{what}: result range [{slo:.0f}, {shi:.0f}] can wrap "
                    f"{np.dtype(dtype).name} [{dlo:.0f}, {dhi:.0f}] — prove "
                    f"the operands smaller or declare allow_wrap at this "
                    f"site if modular arithmetic is intended",
                    lo=slo, hi=shi, dtype=np.dtype(dtype).name)
            return _top(shape, dtype)
        return _mk(shape, dtype, lo, hi)

    # -- elementwise arithmetic ----------------------------------------

    def p_add(self, eqn, ins):
        a, b = ins
        return self._wrap_check(eqn, eqn.outvars[0].aval.shape, a.dtype,
                                a.lo + b.lo, a.hi + b.hi, "add")

    def p_sub(self, eqn, ins):
        a, b = ins
        return self._wrap_check(eqn, eqn.outvars[0].aval.shape, a.dtype,
                                a.lo - b.hi, a.hi - b.lo, "sub")

    def p_mul(self, eqn, ins):
        a, b = ins
        with np.errstate(invalid="ignore"):
            cands = np.stack(np.broadcast_arrays(
                a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi))
        lo = np.nanmin(np.where(np.isnan(cands), np.inf, cands), axis=0)
        hi = np.nanmax(np.where(np.isnan(cands), -np.inf, cands), axis=0)
        return self._wrap_check(eqn, eqn.outvars[0].aval.shape, a.dtype,
                                lo, hi, "mul")

    def p_dot_general(self, eqn, ins):
        """Monotone bound for nonnegative operands without batch dims
        (the packed emit's 0/1-selection matmul): the product of the
        lower bounds and of the upper bounds; anything else is TOP."""
        a, b = ins
        out = eqn.outvars[0].aval
        (ca, cb), (ba, bb) = eqn.params["dimension_numbers"]
        if ba or bb or np.any(a.lo < 0) or np.any(b.lo < 0):
            return _top(out.shape, out.dtype)

        def contract(x, y):
            return np.tensordot(np.broadcast_to(x, a.shape),
                                np.broadcast_to(y, b.shape),
                                axes=(tuple(ca), tuple(cb)))
        with np.errstate(invalid="ignore"):
            lo, hi = contract(a.lo, b.lo), contract(a.hi, b.hi)
        return self._wrap_check(eqn, out.shape, out.dtype, lo, hi,
                                "dot_general")

    def p_neg(self, eqn, ins):
        (a,) = ins
        return self._wrap_check(eqn, a.shape, a.dtype, -a.hi, -a.lo, "neg")

    def p_div(self, eqn, ins):
        a, b = ins
        shape = eqn.outvars[0].aval.shape
        if _is_int(a.dtype):
            if np.all(a.lo >= 0) and np.all(b.lo >= 1):
                return _mk(shape, a.dtype, np.floor(a.lo / b.hi),
                           np.floor(a.hi / b.lo))
            return _top(shape, a.dtype)
        return _top(shape, a.dtype)

    def p_rem(self, eqn, ins):
        a, b = ins
        shape = eqn.outvars[0].aval.shape
        if _is_int(a.dtype) and a.known and b.known \
                and np.all(np.abs(b.lo) >= 1):
            with np.errstate(invalid="ignore"):
                v = np.fmod(a.lo, b.lo)   # lax.rem is truncated (C-style)
            return self._structural(_mk(a.shape, a.dtype, v, v), shape,
                                    lambda x: np.broadcast_to(x, shape))
        if _is_int(a.dtype) and np.all(b.lo >= 1):
            hi = np.minimum(np.broadcast_to(a.hi, shape) if a.hi.shape
                            else a.hi, b.hi - 1)
            if np.all(a.lo >= 0):
                return _mk(shape, a.dtype, 0.0, np.maximum(hi, 0.0))
            return _mk(shape, a.dtype, -(np.max(b.hi) - 1), np.max(b.hi) - 1)
        return _top(shape, a.dtype)

    def p_sign(self, eqn, ins):
        (a,) = ins
        if a.known:
            return _mk(a.shape, a.dtype, np.sign(a.lo), np.sign(a.lo))
        lo = np.where(a.lo > 0, 1.0, np.where(a.lo >= 0, 0.0, -1.0))
        hi = np.where(a.hi < 0, -1.0, np.where(a.hi <= 0, 0.0, 1.0))
        return _mk(a.shape, a.dtype, lo, hi)

    def p_max(self, eqn, ins):
        a, b = ins
        return _mk(eqn.outvars[0].aval.shape, a.dtype,
                   np.maximum(a.lo, b.lo), np.maximum(a.hi, b.hi))

    def p_min(self, eqn, ins):
        a, b = ins
        return _mk(eqn.outvars[0].aval.shape, a.dtype,
                   np.minimum(a.lo, b.lo), np.minimum(a.hi, b.hi))

    def p_clamp(self, eqn, ins):
        lo_b, x, hi_b = ins
        return _mk(x.shape, x.dtype,
                   np.clip(x.lo, lo_b.lo, hi_b.hi),
                   np.clip(x.hi, lo_b.lo, hi_b.hi))

    def p_floor(self, eqn, ins):
        (a,) = ins
        return _mk(a.shape, a.dtype, np.floor(a.lo), np.floor(a.hi))

    def p_ceil(self, eqn, ins):
        (a,) = ins
        return _mk(a.shape, a.dtype, np.ceil(a.lo), np.ceil(a.hi))

    def p_abs(self, eqn, ins):
        (a,) = ins
        lo = np.where((a.lo <= 0) & (a.hi >= 0), 0.0,
                      np.minimum(np.abs(a.lo), np.abs(a.hi)))
        return _mk(a.shape, a.dtype, lo,
                   np.maximum(np.abs(a.lo), np.abs(a.hi)))

    def p_stop_gradient(self, eqn, ins):
        return ins[0]

    def p_copy(self, eqn, ins):
        return ins[0]

    # -- bitwise / shifts ----------------------------------------------

    def _check_shift_amount(self, s: IVal, width: int, what: str) -> None:
        slo, shi = s.summary()
        if slo < 0 or shi > width - 1:
            self.emit(
                f"{what}: shift amount range [{slo:.0f}, {shi:.0f}] "
                f"escapes [0, {width - 1}] — an out-of-range shift on a "
                f"{width}-bit lane is undefined on TPU; mask the shift "
                f"or prove its bound",
                shift_lo=slo, shift_hi=shi, width=width)

    def p_shift_left(self, eqn, ins):
        a, s = ins
        width = _bitwidth(a.dtype)
        self._check_shift_amount(s, width, "shift_left")
        shape = eqn.outvars[0].aval.shape
        if np.all(a.lo >= 0) and np.all(s.lo >= 0):
            hi = a.hi * np.exp2(np.minimum(s.hi, width))
            lo = a.lo * np.exp2(s.lo)
            return self._wrap_check(eqn, shape, a.dtype, lo, hi,
                                    "shift_left")
        return _top(shape, a.dtype)

    def p_shift_right_logical(self, eqn, ins):
        a, s = ins
        width = _bitwidth(a.dtype)
        self._check_shift_amount(s, width, "shift_right_logical")
        shape = eqn.outvars[0].aval.shape
        if np.all(a.lo >= 0):
            return _mk(shape, a.dtype, np.floor(a.lo / np.exp2(s.hi)),
                       np.floor(a.hi / np.exp2(s.lo)))
        # logical shift reinterprets negative ints as their unsigned bits
        return _mk(shape, a.dtype, 0.0, float(2 ** width - 1)
                   if width < 64 else float(2 ** 63 - 1))

    def p_shift_right_arithmetic(self, eqn, ins):
        a, s = ins
        self._check_shift_amount(s, _bitwidth(a.dtype),
                                 "shift_right_arithmetic")
        return _mk(eqn.outvars[0].aval.shape, a.dtype,
                   np.floor(a.lo / np.exp2(s.lo)),
                   np.floor(a.hi / np.exp2(s.lo)))

    def p_and(self, eqn, ins):
        a, b = ins
        shape = eqn.outvars[0].aval.shape
        masks = [_mask_below(v.hi) for v in (a, b) if np.all(v.lo >= 0)]
        if masks:
            hi = masks[0]
            for m in masks[1:]:
                hi = np.minimum(hi, m)
            return _mk(shape, a.dtype, 0.0, hi.astype(np.float64))
        return _top(shape, a.dtype)

    def p_or(self, eqn, ins):
        a, b = ins
        shape = eqn.outvars[0].aval.shape
        if np.all(a.lo >= 0) and np.all(b.lo >= 0):
            hi = (_mask_below(a.hi) | _mask_below(b.hi)).astype(np.float64)
            return _mk(shape, a.dtype, np.maximum(a.lo, b.lo), hi)
        return _top(shape, a.dtype)

    def p_xor(self, eqn, ins):
        a, b = ins
        shape = eqn.outvars[0].aval.shape
        if np.all(a.lo >= 0) and np.all(b.lo >= 0):
            hi = (_mask_below(a.hi) | _mask_below(b.hi)).astype(np.float64)
            return _mk(shape, a.dtype, 0.0, hi)
        return _top(shape, a.dtype)

    def p_not(self, eqn, ins):
        return _top(eqn.outvars[0].aval.shape, ins[0].dtype)

    # -- conversions ---------------------------------------------------

    def p_convert_element_type(self, eqn, ins):
        (a,) = ins
        dst = eqn.params["new_dtype"]
        lo, hi = a.summary()
        if _is_int(a.dtype) and _is_float(dst):
            mant = _MANTISSA.get(np.dtype(
                jax.dtypes.canonicalize_dtype(dst)).name, 53)
            bound = float(1 << mant)
            # known values escape the mantissa bound if they round-trip
            # exactly (powers of two like a 2^30 clip constant do)
            exact = a.known and np.all(
                a.lo.astype(np.dtype(jax.dtypes.canonicalize_dtype(dst)))
                .astype(np.float64) == a.lo)
            if (hi > bound or lo < -bound) and not exact:
                self.emit(
                    f"convert {np.dtype(a.dtype).name}->"
                    f"{np.dtype(dst).name}: operand range "
                    f"[{lo:.0f}, {hi:.0f}] exceeds the exactly-"
                    f"representable ±2^{mant} — the promotion silently "
                    f"rounds; shift the integer below 2^{mant} first "
                    f"(the bits >> 8 uniform contract)",
                    lo=lo, hi=hi, mantissa=mant)
        elif _is_float(a.dtype) and _is_int(dst):
            dlo, dhi = _dtype_range(dst)
            if hi > dhi or lo < dlo:
                self.emit(
                    f"convert {np.dtype(a.dtype).name}->"
                    f"{np.dtype(dst).name}: float range "
                    f"[{lo:.6g}, {hi:.6g}] is not dominated by a clamp "
                    f"into [{dlo:.0f}, {dhi:.0f}] — the cast is undefined "
                    f"out of range; jnp.clip before .astype",
                    lo=lo, hi=hi)
            return _mk(a.shape, dst, np.clip(a.lo, dlo, dhi),
                       np.clip(a.hi, dlo, dhi))
        elif _is_int(a.dtype) and _is_int(dst):
            dlo, dhi = _dtype_range(dst)
            if (hi > dhi or lo < dlo) and not self.allow_wrap:
                self.emit(
                    f"convert {np.dtype(a.dtype).name}->"
                    f"{np.dtype(dst).name}: operand range "
                    f"[{lo:.0f}, {hi:.0f}] does not fit "
                    f"[{dlo:.0f}, {dhi:.0f}] — the narrowing conversion "
                    f"wraps; mask the value or widen the target",
                    lo=lo, hi=hi)
            if hi > dhi or lo < dlo:
                return _top(a.shape, dst)
        return _mk(a.shape, dst, a.lo, a.hi)

    # -- structure -----------------------------------------------------

    def p_iota(self, eqn, ins):
        shape = tuple(eqn.params["shape"])
        dim = eqn.params["dimension"]
        dtype = eqn.params["dtype"]
        n = shape[dim]
        if int(np.prod(shape)) <= _ELEMENTWISE_LIMIT:
            v = np.broadcast_to(
                np.arange(n, dtype=np.float64).reshape(
                    [n if i == dim else 1 for i in range(len(shape))]),
                shape)
            return _mk(shape, dtype, v, v)
        return _mk(shape, dtype, 0.0, float(n - 1))

    def _structural(self, a: IVal, shape, fn):
        """Apply a shape-changing op to full-resolution bounds; collapse
        to a scalar summary when the bounds are already summarized."""
        if a.lo.shape == a.shape and a.hi.shape == a.shape:
            try:
                return _mk(shape, a.dtype, fn(a.lo), fn(a.hi))
            except Exception:
                pass
        lo, hi = a.summary()
        return _mk(shape, a.dtype, lo, hi)

    def p_reshape(self, eqn, ins):
        shape = tuple(eqn.outvars[0].aval.shape)
        return self._structural(ins[0], shape,
                                lambda v: np.reshape(v, shape))

    def p_squeeze(self, eqn, ins):
        shape = tuple(eqn.outvars[0].aval.shape)
        return self._structural(ins[0], shape,
                                lambda v: np.reshape(v, shape))

    def p_transpose(self, eqn, ins):
        perm = eqn.params["permutation"]
        shape = tuple(eqn.outvars[0].aval.shape)
        return self._structural(ins[0], shape,
                                lambda v: np.transpose(v, perm))

    def p_slice(self, eqn, ins):
        p = eqn.params
        idx = tuple(slice(s, l, st) for s, l, st in
                    zip(p["start_indices"], p["limit_indices"],
                        p["strides"] or [1] * len(p["start_indices"])))
        shape = tuple(eqn.outvars[0].aval.shape)
        return self._structural(ins[0], shape, lambda v: v[idx])

    def p_rev(self, eqn, ins):
        shape = tuple(eqn.outvars[0].aval.shape)
        dims = tuple(eqn.params["dimensions"])
        return self._structural(ins[0], shape, lambda v: np.flip(v, dims))

    def p_broadcast_in_dim(self, eqn, ins):
        (a,) = ins
        shape = tuple(eqn.params["shape"])
        bdims = eqn.params["broadcast_dimensions"]

        def expand(v):
            new = [1] * len(shape)
            for src, dst in enumerate(bdims):
                new[dst] = a.shape[src]
            return np.broadcast_to(np.reshape(v, new), shape)
        return self._structural(a, shape, expand)

    def p_concatenate(self, eqn, ins):
        shape = tuple(eqn.outvars[0].aval.shape)
        return _mk(shape, ins[0].dtype,
                   min(float(np.min(v.lo)) for v in ins),
                   max(float(np.max(v.hi)) for v in ins))

    def p_pad(self, eqn, ins):
        a, pv = ins
        shape = tuple(eqn.outvars[0].aval.shape)
        lo, hi = a.summary()
        plo, phi = pv.summary()
        return _mk(shape, a.dtype, min(lo, plo), max(hi, phi))

    def p_select_n(self, eqn, ins):
        pred, cases = ins[0], ins[1:]
        shape = eqn.outvars[0].aval.shape
        # elementwise-known predicate: take exactly the selected case's
        # bounds per element instead of joining all branches
        if pred.known:
            try:
                idx = np.broadcast_to(pred.lo, shape).astype(np.int64)
                los = np.stack([np.broadcast_to(c.lo, shape)
                                for c in cases])
                his = np.stack([np.broadcast_to(c.hi, shape)
                                for c in cases])
                lo = np.take_along_axis(los, idx[None], axis=0)[0]
                hi = np.take_along_axis(his, idx[None], axis=0)[0]
                return _mk(shape, cases[0].dtype, lo, hi)
            except Exception:
                pass
        joined = self._join(cases)
        return _mk(shape, cases[0].dtype, joined.lo, joined.hi)

    def p_dynamic_slice(self, eqn, ins):
        a = ins[0]
        lo, hi = a.summary()
        return _mk(eqn.outvars[0].aval.shape, a.dtype, lo, hi)

    def p_dynamic_update_slice(self, eqn, ins):
        a, upd = ins[0], ins[1]
        return _mk(eqn.outvars[0].aval.shape, a.dtype,
                   min(a.summary()[0], upd.summary()[0]),
                   max(a.summary()[1], upd.summary()[1]))

    # -- reductions ----------------------------------------------------

    def _reduce(self, eqn, ins, np_fn, wrap_what: Optional[str] = None):
        (a,) = ins
        axes = tuple(eqn.params["axes"])
        shape = tuple(eqn.outvars[0].aval.shape)
        lo = np_fn(np.broadcast_to(a.lo, a.shape), axis=axes)
        hi = np_fn(np.broadcast_to(a.hi, a.shape), axis=axes)
        if wrap_what is not None:
            return self._wrap_check(eqn, shape, a.dtype, lo, hi, wrap_what)
        return _mk(shape, a.dtype, lo, hi)

    def p_reduce_sum(self, eqn, ins):
        return self._reduce(eqn, ins, np.sum, "reduce_sum")

    def p_reduce_max(self, eqn, ins):
        return self._reduce(eqn, ins, np.max)

    def p_reduce_min(self, eqn, ins):
        return self._reduce(eqn, ins, np.min)

    def p_reduce_and(self, eqn, ins):
        return _mk(eqn.outvars[0].aval.shape, ins[0].dtype, 0.0, 1.0)

    def p_reduce_or(self, eqn, ins):
        return _mk(eqn.outvars[0].aval.shape, ins[0].dtype, 0.0, 1.0)

    # -- comparisons (bool outputs) ------------------------------------
    #
    # Interval-precise: elementwise 1 where the relation certainly holds,
    # 0 where it certainly fails, [0, 1] otherwise.  This is what lets
    # the floor-div/mod sign-correction chains jnp emits collapse — with
    # nonnegative operands their correction predicates are certainly
    # false, so select_n keeps the uncorrected quotient's bounds instead
    # of joining an infeasible q-1 branch.

    def _cmp(self, eqn, ins, certain_true, certain_false):
        a, b = ins
        shape = eqn.outvars[0].aval.shape
        try:
            t = np.broadcast_to(certain_true(a, b), shape)
            f = np.broadcast_to(certain_false(a, b), shape)
        except Exception:
            t = np.asarray(False)
            f = np.asarray(False)
        lo = np.where(t, 1.0, 0.0)
        hi = np.where(f, 0.0, 1.0)
        return _mk(shape, np.dtype(np.bool_), lo, hi)

    def p_lt(self, eqn, ins):
        return self._cmp(eqn, ins, lambda a, b: a.hi < b.lo,
                         lambda a, b: a.lo >= b.hi)

    def p_le(self, eqn, ins):
        return self._cmp(eqn, ins, lambda a, b: a.hi <= b.lo,
                         lambda a, b: a.lo > b.hi)

    def p_gt(self, eqn, ins):
        return self._cmp(eqn, ins, lambda a, b: a.lo > b.hi,
                         lambda a, b: a.hi <= b.lo)

    def p_ge(self, eqn, ins):
        return self._cmp(eqn, ins, lambda a, b: a.lo >= b.hi,
                         lambda a, b: a.hi < b.lo)

    def p_eq(self, eqn, ins):
        return self._cmp(
            eqn, ins,
            lambda a, b: (a.lo == a.hi) & (b.lo == b.hi) & (a.lo == b.lo),
            lambda a, b: (a.hi < b.lo) | (a.lo > b.hi))

    def p_ne(self, eqn, ins):
        return self._cmp(
            eqn, ins,
            lambda a, b: (a.hi < b.lo) | (a.lo > b.hi),
            lambda a, b: (a.lo == a.hi) & (b.lo == b.hi) & (a.lo == b.lo))

    def p_is_finite(self, eqn, ins):
        return _mk(eqn.outvars[0].aval.shape, np.dtype(np.bool_), 0.0, 1.0)

    # -- gather: the in-table contract ---------------------------------

    def p_gather(self, eqn, ins):
        operand, indices = ins
        dnums = eqn.params["dimension_numbers"]
        slice_sizes = eqn.params["slice_sizes"]
        ilo, ihi = indices.summary()
        for pos, d in enumerate(dnums.start_index_map):
            limit = operand.shape[d] - slice_sizes[d]
            # per-position bounds when the index vector dim is resolved
            plo, phi = ilo, ihi
            if indices.lo.shape == indices.shape and indices.shape:
                take = np.take(indices.lo, pos, axis=-1)
                plo = float(np.min(take))
                phi = float(np.max(np.take(indices.hi, pos, axis=-1)))
            if plo < 0 or phi > limit:
                self.emit(
                    f"gather: index range [{plo:.0f}, {phi:.0f}] into "
                    f"operand dim {d} (size {operand.shape[d]}, slice "
                    f"{slice_sizes[d]}) escapes [0, {limit}] — "
                    f"out-of-table gathers clamp or corrupt silently; "
                    f"clip the indices against the table or prove the "
                    f"bound (bag_logits-style)",
                    lo=plo, hi=phi, dim=d, table=operand.shape[d])
        lo, hi = operand.summary()
        return _mk(eqn.outvars[0].aval.shape, operand.dtype, lo, hi)


def audit_intervals(fn, args, *, name: str = "fn",
                    allow_wrap: bool = False) -> List[Finding]:
    """Trace ``fn(*args)`` and interval-check its integer arithmetic.

    ``args`` entries may be concrete arrays (exact), ShapeDtypeStructs
    (full dtype range), or :class:`IVal` seeds (declared range).
    ``allow_wrap=True`` blesses modular integer arithmetic (threefry)
    — shift-amount, conversion, and gather bounds are still enforced.
    """
    closed = jax.make_jaxpr(fn)(*trace_args(args))
    interp = _Interp(name=name, allow_wrap=allow_wrap)
    seeds = [_seed(a) for a in
             jax.tree_util.tree_leaves(args, is_leaf=_is_ival)]
    if len(seeds) != len(closed.jaxpr.invars):
        # flattening disagrees with the trace: aval-derived TOP seeds
        seeds = [_top(v.aval.shape, v.aval.dtype)
                 for v in closed.jaxpr.invars]
    interp.run_closed(closed, seeds)
    return interp.findings
