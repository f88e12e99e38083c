"""quantcheck layer 1: interval abstract interpretation over traced jaxprs.

Runs every :class:`~repro.analysis.trace.TracedEntry` through a sound
interval interpreter and proves (or refutes) three numerics properties over
the entry's *shape envelope* (``repro.kernels.envelope``), not just the
smoke shapes it was traced at:

  QL301 int-overflow       an integer equation's value interval leaves its
                           dtype range — e.g. an int8 x int8 matmul
                           accumulating in int16. Contractions and K-axis
                           reductions are scaled up to the envelope's
                           ``k_max`` so the proof covers every serving
                           shape, and a fitting accumulator is reported as
                           an explicit proof (info).
  QL302 grid-saturation    a clamp bound is *provably always* active: the
                           clamped operand's interval lies entirely beyond
                           one bound, so the quantization grid collapses to
                           a constant. Straddling intervals (ordinary
                           clipping) never fire.
  QL303 scale-underflow    a division's divisor interval is entirely
                           subnormal (|d| < float32 tiny) — FlexRound's
                           s1*s2*s3 product down here flushes to zero on
                           TPU and kills every gradient through the
                           reciprocal rule.

Soundness over silence: invars are seeded from the entry's declared value
ranges (``TracedEntry.ranges``), from integer dtype bounds, and from const
values; everything else is TOP and marked *unknown*. The three rules only
fire on intervals whose every input was known — an unimplemented primitive
or a widened loop carry can never produce a false positive, only a missed
proof.
"""
from __future__ import annotations

import dataclasses
import fnmatch
import math
from typing import Any, Dict, List, Optional

import numpy as np

from repro.analysis.report import Report
from repro.analysis.trace import TracedEntry
from repro.kernels.envelope import F32_TINY, ShapeEnvelope, get_envelope

NEG_INF = float("-inf")
POS_INF = float("inf")


@dataclasses.dataclass(frozen=True)
class Interval:
    """A closed real interval [lo, hi] with a knownness bit.

    ``known=False`` marks fallback bounds (unimplemented primitive, widened
    loop carry, unseeded float input); the QL30x rules never fire on them.
    """
    lo: float
    hi: float
    known: bool = True

    def __post_init__(self):
        assert self.lo <= self.hi, (self.lo, self.hi)

    @property
    def abs_max(self) -> float:
        return max(abs(self.lo), abs(self.hi))

    @property
    def finite(self) -> bool:
        return math.isfinite(self.lo) and math.isfinite(self.hi)

    def hull(self, other: "Interval") -> "Interval":
        return Interval(min(self.lo, other.lo), max(self.hi, other.hi),
                        self.known and other.known)

    def clip_to(self, lo: float, hi: float) -> "Interval":
        nlo = min(max(self.lo, lo), hi)
        nhi = max(min(self.hi, hi), lo)
        return Interval(nlo, nhi, self.known)


TOP = Interval(NEG_INF, POS_INF, known=False)


def _mul1(a: float, b: float) -> float:
    # IEEE inf * 0 is nan; the correct interval endpoint product is 0
    if a == 0.0 or b == 0.0:
        return 0.0
    return a * b


def _imul(a: Interval, b: Interval) -> Interval:
    ps = (_mul1(a.lo, b.lo), _mul1(a.lo, b.hi),
          _mul1(a.hi, b.lo), _mul1(a.hi, b.hi))
    return Interval(min(ps), max(ps), a.known and b.known)


def _idiv(a: Interval, b: Interval) -> Interval:
    # division where the divisor interval may include 0 is unbounded
    if b.lo <= 0.0 <= b.hi:
        return Interval(NEG_INF, POS_INF, a.known and b.known)
    inv = Interval(1.0 / b.hi, 1.0 / b.lo, b.known)
    return _imul(a, inv)


def _dtype_interval(dtype) -> Interval:
    try:
        d = np.dtype(dtype)
    except TypeError:
        return TOP   # extended dtypes (PRNG keys) carry no value range
    if d.kind == "b":
        return Interval(0.0, 1.0, known=True)
    if d.kind in "iu":
        info = np.iinfo(d)
        # dtype bounds are always true bounds, but only the narrow code
        # dtypes (int8/uint8/int16) carry *meaningful* range information —
        # full-range int32 counters/indices would turn every add into a
        # may-overflow false positive, so they stay unknown
        return Interval(float(info.min), float(info.max),
                        known=d.itemsize <= 2)
    return TOP


def _np_dtype(aval):
    """np.dtype of an aval, or None for extended dtypes (PRNG keys)."""
    if aval is None or not hasattr(aval, "dtype"):
        return None
    try:
        return np.dtype(aval.dtype)
    except TypeError:
        return None


def _const_interval(val) -> Interval:
    arr = np.asarray(val)
    if arr.size == 0:
        return Interval(0.0, 0.0)
    if arr.dtype.kind not in "biufc":
        return TOP
    if arr.dtype.kind == "c":
        return TOP
    lo = float(np.min(arr))
    hi = float(np.max(arr))
    if math.isnan(lo) or math.isnan(hi):
        return TOP
    return Interval(lo, hi)


def _round_iv(iv: Interval, fn) -> Interval:
    lo = fn(iv.lo) if math.isfinite(iv.lo) else iv.lo
    hi = fn(iv.hi) if math.isfinite(iv.hi) else iv.hi
    return Interval(float(lo), float(hi), iv.known)


# --------------------------------------------------------------- interpreter
class _Ctx:
    """Per-entry interpreter state: envelope, report sink, proof ledger."""

    def __init__(self, entry: TracedEntry, rep: Report):
        self.entry = entry
        self.rep = rep
        self.env: Optional[ShapeEnvelope] = (
            get_envelope(entry.envelope) if entry.envelope else None)
        self.proofs: List[str] = []
        self.fired: set = set()   # dedup (rule, prim, detail) per entry

    def where(self, eqn) -> str:
        return f"jaxpr:{self.entry.name}#{eqn.primitive.name}"

    def add_once(self, key, rule, name, severity, where, message):
        if key in self.fired:
            return
        self.fired.add(key)
        self.rep.add(rule, name, severity, where, message)


def _reduction_count(shape, axes, ctx: _Ctx) -> int:
    n = 1
    for ax in axes:
        n *= int(shape[ax])
    if ctx.env is not None:
        # prove over the envelope's largest contraction, not the smoke shape
        n = max(n, ctx.env.k_max)
    return max(n, 1)


def _scaled_sum(iv: Interval, n: int) -> Interval:
    return Interval(_mul1(float(n), iv.lo), _mul1(float(n), iv.hi), iv.known)


def _eval_eqn(eqn, ins: List[Interval], ctx: _Ctx) -> List[Interval]:
    p = eqn.primitive.name
    out_aval = eqn.outvars[0].aval if eqn.outvars else None

    if p in ("add", "add_any"):
        a, b = ins[:2]
        return [Interval(a.lo + b.lo, a.hi + b.hi, a.known and b.known)]
    if p == "sub":
        a, b = ins[:2]
        return [Interval(a.lo - b.hi, a.hi - b.lo, a.known and b.known)]
    if p == "mul":
        return [_imul(ins[0], ins[1])]
    if p == "div":
        return [_idiv(ins[0], ins[1])]
    if p == "neg":
        a = ins[0]
        return [Interval(-a.hi, -a.lo, a.known)]
    if p == "abs":
        a = ins[0]
        lo = 0.0 if a.lo <= 0.0 <= a.hi else min(abs(a.lo), abs(a.hi))
        return [Interval(lo, a.abs_max, a.known)]
    if p == "max":
        a, b = ins[:2]
        return [Interval(max(a.lo, b.lo), max(a.hi, b.hi),
                         a.known and b.known)]
    if p == "min":
        a, b = ins[:2]
        return [Interval(min(a.lo, b.lo), min(a.hi, b.hi),
                         a.known and b.known)]
    if p == "clamp":
        lo_b, x, hi_b = ins[:3]
        out = Interval(min(max(x.lo, lo_b.lo), hi_b.hi),
                       max(min(x.hi, hi_b.hi), lo_b.lo),
                       x.known and lo_b.known and hi_b.known)
        return [out]
    if p in ("round", "nearbyint"):
        return [_round_iv(ins[0], round)]
    if p == "floor":
        return [_round_iv(ins[0], math.floor)]
    if p == "ceil":
        return [_round_iv(ins[0], math.ceil)]
    if p == "sign":
        return [Interval(-1.0, 1.0, ins[0].known)]
    if p in ("stop_gradient", "copy", "device_put", "sharding_constraint",
             "reshape", "squeeze", "expand_dims", "broadcast_in_dim",
             "transpose", "rev", "slice", "dynamic_slice", "gather",
             "reduce_max", "reduce_min", "real", "optimization_barrier"):
        # value-preserving / value-subsetting ops (first operand carries it)
        return [ins[0] if ins else TOP] * max(len(eqn.outvars), 1)
    if p == "concatenate":
        out = ins[0]
        for iv in ins[1:]:
            out = out.hull(iv)
        return [out]
    if p == "select_n":
        out = ins[1]
        for iv in ins[2:]:
            out = out.hull(iv)
        return [out]
    if p == "pad":
        return [ins[0].hull(ins[1])]
    if p == "iota":
        size = max(int(np.prod(out_aval.shape)), 1) if out_aval else 1
        return [Interval(0.0, float(size - 1))]
    if p == "convert_element_type":
        a = ins[0]
        d = np.dtype(eqn.params["new_dtype"])
        if d.kind in "iu" and a.finite:
            # float -> int truncates toward zero; int -> int preserves
            a = _round_iv(a, math.trunc)
        return [a]
    if p == "integer_pow":
        y = int(eqn.params["y"])
        a = ins[0]
        if y == 2:
            lo = 0.0 if a.lo <= 0.0 <= a.hi else min(a.lo**2, a.hi**2)
            return [Interval(lo, a.abs_max**2, a.known)]
        return [TOP if not a.known else
                Interval(min(a.lo**y, a.hi**y), max(a.lo**y, a.hi**y),
                         a.known)] if y % 2 == 1 else [TOP]
    if p == "exp":
        a = ins[0]
        return [Interval(math.exp(min(a.lo, 700.0)) if a.finite else 0.0,
                         math.exp(min(a.hi, 700.0)) if a.finite else POS_INF,
                         a.known and a.finite)]
    if p in ("and", "or", "xor"):
        a, b = ins[:2]
        if a.lo >= 0.0 and b.lo >= 0.0 and a.finite and b.finite:
            hi = min(a.hi, b.hi) if p == "and" else a.hi + b.hi
            return [Interval(0.0, hi, a.known and b.known)]
        return [_dtype_interval(out_aval.dtype) if out_aval else TOP]
    if p in ("shift_right_logical", "shift_right_arithmetic"):
        a, s = ins[:2]
        if a.lo >= 0.0 and a.finite and s.known and s.lo >= 0.0:
            return [Interval(0.0, float(int(a.hi) >> int(s.lo)), a.known)]
        return [_dtype_interval(out_aval.dtype) if out_aval else TOP]
    if p == "shift_left":
        a, s = ins[:2]
        if a.lo >= 0.0 and a.finite and s.finite:
            return [Interval(0.0, float(int(a.hi) << int(s.hi)), a.known)]
        return [_dtype_interval(out_aval.dtype) if out_aval else TOP]
    if p == "reduce_sum":
        shape = eqn.invars[0].aval.shape
        n = _reduction_count(shape, eqn.params["axes"], ctx)
        return [_scaled_sum(ins[0], n)]
    if p == "dot_general":
        (lc, _), _ = eqn.params["dimension_numbers"]
        shape = eqn.invars[0].aval.shape
        n = _reduction_count(shape, lc, ctx)
        return [_scaled_sum(_imul(ins[0], ins[1]), n)]
    if p in ("eq", "ne", "lt", "le", "gt", "ge", "is_finite"):
        return [Interval(0.0, 1.0)]
    if p in ("psum", "pmean", "all_gather", "all_reduce"):
        # cross-device sum widens by the axis size; without a declared bound
        # treat as unknown-scaled
        return [Interval(min(iv.lo * 64, iv.lo), max(iv.hi * 64, iv.hi),
                         False) for iv in ins[:len(eqn.outvars)]]
    return []  # caller applies the dtype-range fallback per outvar


def _check_eqn(eqn, ins: List[Interval], outs: List[Interval],
               ctx: _Ctx) -> List[Interval]:
    """Run QL301/302/303 on one equation; returns ``outs`` with integer
    results clipped to their dtype range (overflow already reported)."""
    p = eqn.primitive.name

    # ---- QL303: provably subnormal divisor (FlexRound reciprocal rule)
    if p == "div" and len(ins) >= 2:
        d = ins[1]
        dt = _np_dtype(getattr(eqn.invars[1], "aval", None))
        if (d.known and dt is not None and dt.kind == "f" and d.finite
                and 0.0 < d.abs_max < F32_TINY):
            ctx.add_once(("QL303", p), "QL303", "scale-underflow", "error",
                         ctx.where(eqn),
                         f"divisor interval [{d.lo:.3g}, {d.hi:.3g}] is "
                         "entirely subnormal (< float32 tiny "
                         f"{F32_TINY:.3g}) — the scale product flushes to "
                         "zero on TPU and zeroes every gradient through "
                         "the reciprocal rule; check the EPS projection "
                         "on s1/s2/s3")

    # ---- QL302: clamp bound provably always active
    def _sat(xi: Interval, bound: Interval, side: str, kind: str):
        if not (xi.known and bound.known and xi.finite and bound.finite):
            return
        hit = (side == "low" and xi.hi < bound.lo) or \
              (side == "high" and xi.lo > bound.hi)
        if hit:
            ctx.add_once(("QL302", kind, side), "QL302", "grid-saturation",
                         "error", ctx.where(eqn),
                         f"{kind}: operand interval [{xi.lo:.4g}, "
                         f"{xi.hi:.4g}] lies entirely beyond the "
                         f"{side} clamp bound [{bound.lo:.4g}, "
                         f"{bound.hi:.4g}] — the quantization grid is "
                         "provably saturated to a constant (scale/zero "
                         "badly calibrated for the declared ranges)")

    if p == "max" and len(ins) == 2:
        a, b = ins
        # the point-interval side (literal/const bound) is the clamp bound
        if b.lo == b.hi:
            _sat(a, b, "low", "max")
        elif a.lo == a.hi:
            _sat(b, a, "low", "max")
    if p == "min" and len(ins) == 2:
        a, b = ins
        if b.lo == b.hi:
            _sat(a, b, "high", "min")
        elif a.lo == a.hi:
            _sat(b, a, "high", "min")
    if p == "clamp" and len(ins) == 3:
        _sat(ins[1], ins[0], "low", "clamp")
        _sat(ins[1], ins[2], "high", "clamp")

    # ---- QL301: integer interval leaves its dtype range
    clipped: List[Interval] = []
    for ov, iv in zip(eqn.outvars, outs):
        d = _np_dtype(getattr(ov, "aval", None))
        if d is None or d.kind not in "iu":
            clipped.append(iv)
            continue
        info = np.iinfo(d)
        if iv.known and iv.finite and (iv.lo < info.min or iv.hi > info.max):
            scaled = (" (envelope-scaled to k_max="
                      f"{ctx.env.k_max})" if ctx.env is not None
                      and p in ("dot_general", "reduce_sum") else "")
            ctx.add_once(("QL301", p, str(d)), "QL301", "int-overflow",
                         "error", ctx.where(eqn),
                         f"{p}: value interval [{iv.lo:.4g}, {iv.hi:.4g}]"
                         f"{scaled} exceeds {d.name} range "
                         f"[{info.min}, {info.max}] — integer overflow; "
                         "widen the accumulator "
                         "(preferred_element_type=jnp.int32)")
        elif (iv.known and iv.finite and p == "dot_general"
              and ctx.env is not None):
            ctx.proofs.append(
                f"{p}->{d.name}: accumulator interval [{iv.lo:.4g}, "
                f"{iv.hi:.4g}] fits for every K <= {ctx.env.k_max}")
        clipped.append(iv.clip_to(float(info.min), float(info.max)))
    return clipped


def _call_jaxpr(params: Dict[str, Any], key: str):
    j = params.get(key)
    if j is None:
        return None, ()
    if hasattr(j, "jaxpr"):   # ClosedJaxpr
        return j.jaxpr, tuple(j.consts)
    return j, ()


def _eval_jaxpr(jaxpr, in_ivals: List[Interval],
                const_ivals: List[Interval], ctx: _Ctx,
                depth: int = 0) -> List[Interval]:
    if depth > 24:
        return [TOP for _ in jaxpr.outvars]
    env: Dict[Any, Interval] = {}

    def write(var, iv: Interval):
        if type(var).__name__ == "DropVar":
            return
        env[var] = iv

    def read(var) -> Interval:
        if hasattr(var, "val"):    # Literal
            return _const_interval(var.val)
        if var in env:
            return env[var]
        aval = getattr(var, "aval", None)
        base = _dtype_interval(aval.dtype) if aval is not None and hasattr(
            aval, "dtype") else TOP
        return dataclasses.replace(base, known=False)

    for var, iv in zip(jaxpr.invars, in_ivals):
        write(var, iv)
    for var, iv in zip(jaxpr.constvars, const_ivals):
        write(var, iv)

    for eqn in jaxpr.eqns:
        ins = [read(v) for v in eqn.invars]
        p = eqn.primitive.name
        outs: List[Interval] = []

        if p in ("jit", "closed_call", "core_call", "remat_call", "remat",
                 "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
                 "checkpoint"):
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                sub, consts = _call_jaxpr(eqn.params, key)
                if sub is not None:
                    outs = _eval_jaxpr(sub, ins, list(consts), ctx, depth + 1)
                    break
        elif p == "shard_map":
            sub, consts = _call_jaxpr(eqn.params, "jaxpr")
            if sub is not None:
                outs = _eval_jaxpr(sub, ins, list(consts), ctx, depth + 1)
                # per-shard values reassemble across devices: keep bounds
                # but drop knownness (axis sizes not modeled)
                outs = [dataclasses.replace(o, known=False) for o in outs]
        elif p in ("scan", "while"):
            sub, consts = _call_jaxpr(
                eqn.params, "jaxpr" if p == "scan" else "body_jaxpr")
            if sub is not None:
                if p == "scan":
                    nc = eqn.params.get("num_consts", 0)
                    ncar = eqn.params.get("num_carry", 0)
                    body_in = list(ins[:nc])
                    # widen carries to their dtype fallback (fixpoint-free)
                    for var in sub.invars[nc:nc + ncar]:
                        aval = getattr(var, "aval", None)
                        base = (_dtype_interval(aval.dtype)
                                if aval is not None and hasattr(aval, "dtype")
                                else TOP)
                        body_in.append(dataclasses.replace(base, known=False))
                    # xs slices keep the stacked operand's interval
                    body_in.extend(ins[nc + ncar:])
                    body_out = _eval_jaxpr(sub, body_in, list(consts), ctx,
                                           depth + 1)
                    outs = [dataclasses.replace(o, known=False)
                            for o in body_out]
                else:
                    body_in = [dataclasses.replace(
                        read(v), known=False) for v in sub.invars]
                    _eval_jaxpr(sub, body_in, list(consts), ctx, depth + 1)
                    outs = []
        else:
            outs = _eval_eqn(eqn, ins, ctx)

        if len(outs) != len(eqn.outvars):
            outs = []
            for ov in eqn.outvars:
                aval = getattr(ov, "aval", None)
                base = _dtype_interval(aval.dtype) if aval is not None and \
                    hasattr(aval, "dtype") else TOP
                outs.append(dataclasses.replace(base, known=False))

        outs = _check_eqn(eqn, ins, outs, ctx)
        for ov, iv in zip(eqn.outvars, outs):
            write(ov, iv)

    return [read(v) for v in jaxpr.outvars]


# ------------------------------------------------------------------ public
def seed_invars(entry: TracedEntry) -> List[Interval]:
    """Initial intervals for the entry's flat invars: declared range glob
    (first match wins), else integer dtype bounds, else unknown TOP."""
    out: List[Interval] = []
    for var, label in zip(entry.closed.jaxpr.invars, entry.labels):
        iv: Optional[Interval] = None
        for glob, lo, hi in entry.ranges:
            # exact match first: labels like "a_state.[0]" contain fnmatch
            # character-class metachars
            if label == glob or fnmatch.fnmatch(label, glob):
                iv = Interval(float(lo), float(hi))
                break
        if iv is None:
            aval = getattr(var, "aval", None)
            base = _dtype_interval(aval.dtype) if aval is not None and \
                hasattr(aval, "dtype") else TOP
            iv = base if base.finite else dataclasses.replace(
                base, known=False)
        out.append(iv)
    return out


def check_intervals(entry: TracedEntry) -> Report:
    """Abstract-interpret one traced entry; QL301/302/303 findings plus an
    info-level proof line when an envelope-scaled accumulator fits."""
    rep = Report()
    ctx = _Ctx(entry, rep)
    consts = [_const_interval(c) for c in entry.closed.consts]
    _eval_jaxpr(entry.closed.jaxpr, seed_invars(entry), consts, ctx)
    if ctx.proofs and not rep.errors():
        env = ctx.env
        rep.add("QL301", "int-overflow", "info",
                f"jaxpr:{entry.name}",
                f"proven: {ctx.proofs[0]}" + (
                    f" (envelope {env.layout!r})" if env else ""))
    return rep
