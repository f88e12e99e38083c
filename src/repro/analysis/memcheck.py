"""memcheck — jaxpr-level liveness and HBM-budget verification (QL4xx).

The fourth quantlint layer. The first three prove *value* properties (AST
hygiene, dataflow wiring, interval numerics); this one proves *memory*
properties over the same :class:`~repro.analysis.trace.TracedEntry`
ClosedJaxprs, against the per-entry :class:`MemContract` budgets the trace
builders declare:

  QL401 hbm-budget          peak-live bytes exceed the entry's declared
                            budget — at the traced window length or scaled
                            to the production envelope (serve_kv seq_max).
  QL402 dead-donation       a donated buffer XLA cannot actually reuse: no
                            output shares its shape+dtype, or every
                            candidate output is defined while the donated
                            buffer is still being read. The donation buys
                            nothing — the silent inverse of QL203 (which
                            catches *unsafe* donations, not useless ones).
  QL403 weight-traffic      the bytes the jaxpr's live invars move for a
                            labeled group (packed weights, KV state)
                            drifted from what the repo's own accessors
                            (``tree_weight_bytes``, ``hbm_per_slot_bytes``)
                            — and hence the bench rows — claim.
  QL404 cache-growth (info) window state whose HBM footprint scales with
                            the *allocated* ``max_len``, not the used
                            length: the quantified paged-KV gap, reported
                            into ``--mem-json`` for the roofline's
                            peak-memory term to cross-reference.

Liveness model
--------------
One linear scan per jaxpr: a buffer materializes when its defining equation
runs (while that equation's operands are still held) and dies after its
last consuming equation, unless it is an output. Sub-jaxprs (jit / scan /
while / cond / shard_map) are walked recursively; their inner peak minus
the bytes of the invars that alias outer operands (scan consts + carry —
the carry is thereby counted ONCE across the whole loop body, not once per
trip) is added transiently at the call equation. Donation-matched outputs
write into the donated storage and cost nothing; the donated buffer is
pinned live to the end instead.

Every buffer is classified ``(fixed, per_len)``: carrying the entry's
``max_len`` dim in its shape means its bytes scale with the sequence
window, so one smoke-scale trace yields a *length-parametric* peak —
``peak(L) = max over boundaries of (fixed + per_len * L)`` — and the same
scan proves both the traced window and the production envelope
(``ShapeEnvelope.seq_max``), the QL301 trick applied to memory.

Soundness: the jaxpr is *pre-fusion*. ``int8_decode_attention`` takes
``codes.astype(f32)`` views of the cache that XLA fuses away, so the jaxpr
peak is an upper bound on the compiled peak. Budgets carry documented
headroom for exactly those views (``trace.mem_contract``); the rule exists
to catch asymptotic regressions — a dequantized window materialized as
persistent state, a doubled carry — not 5% drift.
"""
from __future__ import annotations

import dataclasses
import fnmatch
import json
import math
from typing import Any, Dict, List, Sequence, Tuple

from repro.analysis.report import Report
from repro.analysis.trace import TracedEntry
from repro.roofline.analysis import UnknownDtypeError, dtype_bytes

_MIB = float(2**20)

# call-like primitives whose sub-jaxpr params key is one of the usual three
_CALL_PRIMS = ("jit", "closed_call", "core_call", "remat_call", "remat",
               "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
               "checkpoint")


def _is_literal(v) -> bool:
    return hasattr(v, "val")


def _aval_bytes(aval) -> int:
    """Device bytes of one abstract value, via the roofline's dtype table
    (shared with the HBM-traffic model, so sub-byte packed dtypes agree).
    Extended dtypes the table doesn't know (PRNG key dtypes) fall back to
    their itemsize; a dtype with neither is the named UnknownDtypeError."""
    shape = getattr(aval, "shape", None)
    dt = getattr(aval, "dtype", None)
    if shape is None or dt is None:
        return 0  # abstract tokens and friends occupy no HBM
    n = 1
    for s in shape:
        n *= int(s)
    try:
        width = dtype_bytes(getattr(dt, "name", str(dt)))
    except UnknownDtypeError:
        itemsize = getattr(dt, "itemsize", None)
        if itemsize is None:
            raise
        width = float(itemsize)
    return math.ceil(n * width)


def _var_size(v, max_len: int) -> Tuple[int, int]:
    """(fixed, per_len) byte classification of a var: a ``max_len`` dim in
    the shape means the buffer scales with the sequence window."""
    aval = getattr(v, "aval", None)
    b = _aval_bytes(aval)
    shape = tuple(getattr(aval, "shape", ()) or ())
    if max_len and max_len in shape:
        return 0, -(-b // max_len)  # ceil(b / max_len) per window token
    return b, 0


def _sub_jaxprs(eqn) -> List[Tuple[Any, Tuple[Any, ...]]]:
    """(jaxpr, alias_invars) pairs for an equation's sub-jaxprs.

    ``alias_invars`` are the inner invars whose storage aliases an operand
    already counted live by the caller (everything for plain calls; consts
    + carry for scan/while — per-trip xs slices are genuinely new bytes).
    """

    def unwrap(j):
        return j.jaxpr if hasattr(j, "jaxpr") else j

    p = eqn.primitive.name
    out: List[Tuple[Any, Tuple[Any, ...]]] = []
    if p in _CALL_PRIMS or p == "shard_map":
        keys = ("jaxpr",) if p == "shard_map" else ("jaxpr", "call_jaxpr",
                                                    "fun_jaxpr")
        for key in keys:
            j = eqn.params.get(key)
            if j is not None:
                sub = unwrap(j)
                out.append((sub, tuple(sub.invars)))
                break
    elif p == "scan":
        sub = unwrap(eqn.params["jaxpr"])
        n_alias = eqn.params.get("num_consts", 0) + eqn.params.get(
            "num_carry", 0)
        out.append((sub, tuple(sub.invars[:n_alias])))
    elif p == "while":
        for key in ("cond_jaxpr", "body_jaxpr"):
            j = eqn.params.get(key)
            if j is not None:
                sub = unwrap(j)
                out.append((sub, tuple(sub.invars)))
    elif p == "cond":
        for j in eqn.params.get("branches", ()):
            sub = unwrap(j)
            out.append((sub, tuple(sub.invars)))
    return out


def _inner_extras(eqn, max_len: int, depth: int) -> List[Tuple[int, int]]:
    """Transient (fixed, per_len) bytes a call-like equation holds *beyond*
    its operands: each sub-jaxpr boundary minus the alias-invar bytes,
    clamped at zero componentwise (an inner boundary that already freed an
    operand never credits the caller). ``cond``/``while`` branches combine
    by max implicitly — every branch boundary is a candidate peak."""
    extras: List[Tuple[int, int]] = []
    for sub, alias_invars in _sub_jaxprs(eqn):
        af = al = 0
        for v in alias_invars:
            f, le = _var_size(v, max_len)
            af += f
            al += le
        for f, le in _walk_jaxpr(sub, max_len, depth=depth + 1).boundaries:
            extras.append((max(0, f - af), max(0, le - al)))
    return extras or [(0, 0)]


@dataclasses.dataclass
class _Liveness:
    """One jaxpr's liveness scan result."""
    boundaries: List[Tuple[int, int]]   # candidate (fixed, per_len) peaks
    last_use: Dict[int, int]            # id(var) -> last consuming eqn (-1)
    def_eqn: Dict[int, int]             # id(var) -> defining eqn

    def peak_at(self, length: int) -> int:
        return max(f + le * int(length) for f, le in self.boundaries)

    def argmax_at(self, length: int) -> Tuple[int, int]:
        return max(self.boundaries, key=lambda p: p[0] + p[1] * int(length))


def _walk_jaxpr(jaxpr, max_len: int, *, depth: int = 0,
                free_out_ids: frozenset = frozenset(),
                pinned_ids: frozenset = frozenset()) -> _Liveness:
    """Linear liveness scan of one jaxpr (recursing into sub-jaxprs).

    ``free_out_ids`` are donation-matched outvars (they write into donated
    storage — zero new bytes); ``pinned_ids`` are their donated invars
    (live to the end: their storage *is* the output)."""
    if depth > 32:
        raise RecursionError("memcheck: sub-jaxpr nesting exceeds 32 — "
                             "refusing to walk further (cyclic jaxpr?)")
    last_use: Dict[int, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[id(v)] = i
    def_eqn: Dict[int, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.outvars:
            def_eqn[id(v)] = i
    out_ids = {id(v) for v in jaxpr.outvars if not _is_literal(v)}

    live_f = live_l = 0
    live_ids: set = set()

    def add(v):
        nonlocal live_f, live_l
        if _is_literal(v) or id(v) in live_ids:
            return
        live_ids.add(id(v))
        f, le = _var_size(v, max_len)
        live_f += f
        live_l += le

    def drop(v):
        nonlocal live_f, live_l
        if _is_literal(v) or id(v) not in live_ids:
            return
        live_ids.discard(id(v))
        f, le = _var_size(v, max_len)
        live_f -= f
        live_l -= le

    for v in list(jaxpr.constvars) + list(jaxpr.invars):
        add(v)
    boundaries = [(live_f, live_l)]
    for i, eqn in enumerate(jaxpr.eqns):
        # outputs materialize while the operands are still held
        for v in eqn.outvars:
            if id(v) not in free_out_ids:
                add(v)
        for ef, el in _inner_extras(eqn, max_len, depth):
            boundaries.append((live_f + ef, live_l + el))
        # operands whose last consumer this is die now — unless they are
        # outputs, or pinned donated storage
        for v in eqn.invars:
            if (not _is_literal(v) and last_use.get(id(v)) == i
                    and id(v) not in out_ids and id(v) not in pinned_ids):
                drop(v)
        # an output nothing ever reads (and that isn't returned) frees
        # immediately
        for v in eqn.outvars:
            if id(v) not in last_use and id(v) not in out_ids:
                drop(v)
    return _Liveness(boundaries, last_use, def_eqn)


# --------------------------------------------------------------- donation
def _match_donations(entry: TracedEntry,
                     live: _Liveness) -> Tuple[Dict[int, int],
                                               frozenset, frozenset,
                                               List[Tuple[int, str]]]:
    """Greedily alias each donated invar to an output XLA could actually
    write into its storage: same shape+dtype, and the output's defining
    equation at-or-after the donated buffer's last read (equality is the
    healthy in-place consume-produce — scan carries, scatter updates).

    Returns (matches, free_out_ids, pinned_invar_ids, dead list) where
    ``dead`` carries QL402 reasons for donations that buy nothing."""
    jaxpr = entry.closed.jaxpr
    invars = jaxpr.invars
    # candidate outputs: real vars defined by an equation (an outvar that is
    # itself an invar is QL203's returned-unchanged case, not reusable
    # storage; a literal output occupies nothing)
    candidates = [(pos, v) for pos, v in enumerate(jaxpr.outvars)
                  if not _is_literal(v) and id(v) in live.def_eqn]
    taken: set = set()
    matches: Dict[int, int] = {}
    free_ids: set = set()
    pinned: set = set()
    dead: List[Tuple[int, str]] = []
    for i in sorted(entry.donated):
        var = invars[i]
        aval = var.aval
        lu = live.last_use.get(id(var), -1)
        shape_hits = [(pos, v) for pos, v in candidates
                      if pos not in taken and v.aval.shape == aval.shape
                      and v.aval.dtype == aval.dtype]
        viable = [(pos, v) for pos, v in shape_hits
                  if live.def_eqn[id(v)] >= lu]
        if viable:
            pos, v = min(viable, key=lambda pv: live.def_eqn[id(pv[1])])
            taken.add(pos)
            matches[i] = pos
            free_ids.add(id(v))
            pinned.add(id(var))
        elif shape_hits:
            dead.append((i, (
                "donated buffer cannot be reused: every same-shape/dtype "
                f"output (e.g. output #{shape_hits[0][0]}, defined at eqn "
                f"{live.def_eqn[id(shape_hits[0][1])]}) materializes while "
                f"the donated buffer is still read (last use eqn {lu}) — "
                "the lifetimes overlap, so XLA keeps both copies")))
        else:
            dead.append((i, (
                "donated buffer cannot be reused: no output shares its "
                f"shape {tuple(aval.shape)} and dtype {aval.dtype} — the "
                "donation frees nothing; drop it, or return the updated "
                "buffer so XLA can write in place")))
    return matches, frozenset(free_ids), frozenset(pinned), dead


# ------------------------------------------------------------- per-entry
def _where(entry: TracedEntry, tail: str = "mem") -> str:
    return f"jaxpr:{entry.name}#{tail}"


def _live_label_bytes(entry: TracedEntry, glob: str) -> int:
    """Bytes of the entry's DCE-live invars whose label matches ``glob`` —
    what the compiled program actually reads for that group."""
    from repro.analysis.jaxpr_checks import _used_invars

    used = _used_invars(entry.closed)
    return sum(_aval_bytes(v.aval)
               for v, lbl, u in zip(entry.closed.jaxpr.invars, entry.labels,
                                    used)
               if u and fnmatch.fnmatch(lbl, glob))


def check_memory(entry: TracedEntry) -> Tuple[Report, Dict[str, Any]]:
    """Liveness-scan one entry: QL401/QL402/QL403/QL404 findings plus the
    machine-readable record ``--mem-json`` aggregates."""
    rep = Report()
    mem = entry.mem
    max_len = mem.max_len if mem else 0

    pre = _walk_jaxpr(entry.closed.jaxpr, max_len)
    matches, free_ids, pinned, dead = _match_donations(entry, pre)
    for i, reason in dead:
        rep.add("QL402", "dead-donation", "error",
                _where(entry, entry.labels[i]), reason)
    live = _walk_jaxpr(entry.closed.jaxpr, max_len,
                       free_out_ids=free_ids, pinned_ids=pinned)

    record: Dict[str, Any] = {
        "entry": entry.name,
        "max_len": max_len,
        "donated": len(entry.donated),
        "donation_matched": len(matches),
        "donation_dead": len(dead),
    }
    peak_trace = live.peak_at(max_len)
    pf, pl = live.argmax_at(max_len)
    record.update(peak_trace_bytes=peak_trace, peak_fixed_bytes=pf,
                  peak_bytes_per_token=pl)

    if mem is None:
        rep.add("QL401", "hbm-budget", "info", _where(entry),
                f"no MemContract declared — measured peak-live "
                f"{peak_trace / _MIB:.3f} MiB "
                f"({pf / _MIB:.3f} fixed + {pl} B/token), unenforced")
        return rep, record

    budget_trace = mem.budget_at(max_len)
    record.update(budget_trace_bytes=budget_trace, slots=mem.slots,
                  envelope_len=mem.envelope_len, note=mem.note)
    if peak_trace > budget_trace:
        rep.add("QL401", "hbm-budget", "error", _where(entry),
                f"peak-live {peak_trace / _MIB:.3f} MiB exceeds the "
                f"declared budget {budget_trace / _MIB:.3f} MiB at the "
                f"traced window L={max_len} "
                f"(peak = {pf / _MIB:.3f} MiB fixed + {pl} B/token; "
                f"budget: {mem.note or 'undocumented'})")
    if mem.envelope_len:
        peak_env = live.peak_at(mem.envelope_len)
        budget_env = mem.budget_at(mem.envelope_len)
        record.update(peak_envelope_bytes=peak_env,
                      budget_envelope_bytes=budget_env)
        if peak_env > budget_env:
            ef, el = live.argmax_at(mem.envelope_len)
            rep.add("QL401", "hbm-budget", "error", _where(entry),
                    f"peak-live {peak_env / _MIB:.1f} MiB exceeds the "
                    f"budget {budget_env / _MIB:.1f} MiB at the production "
                    f"envelope L={mem.envelope_len} (scaled from the "
                    f"L={max_len} trace: {ef / _MIB:.3f} MiB fixed + "
                    f"{el} B/token vs budget {mem.per_len_bytes} B/token)")
        elif peak_trace <= budget_trace:
            rep.add("QL401", "hbm-budget", "info", _where(entry),
                    f"peak-live fits the budget at L={max_len} "
                    f"({peak_trace / _MIB:.3f} <= {budget_trace / _MIB:.3f} "
                    f"MiB) and at the envelope L={mem.envelope_len} "
                    f"({peak_env / _MIB:.1f} <= {budget_env / _MIB:.1f} "
                    "MiB) — the smoke trace proves the production window")
    elif peak_trace <= budget_trace:
        rep.add("QL401", "hbm-budget", "info", _where(entry),
                f"peak-live {peak_trace / _MIB:.3f} MiB fits the budget "
                f"{budget_trace / _MIB:.3f} MiB")

    # QL403: the jaxpr's live bytes per labeled group vs the accessor claim
    record["expect"] = []
    for measure, glob, expected in mem.expect:
        static = _live_label_bytes(entry, glob)
        record["expect"].append({"measure": measure, "glob": glob,
                                 "expected_bytes": expected,
                                 "static_bytes": static})
        slack = max(4096, int(0.01 * expected))
        if abs(static - expected) > slack:
            rep.add("QL403", "weight-traffic", "error",
                    _where(entry, measure),
                    f"live invars matching {glob!r} move {static} B in the "
                    f"jaxpr but the accessor claims {expected} B "
                    f"(drift {static - expected:+d} B > slack {slack} B) — "
                    "a dead/extra buffer, or the accessor and the jaxpr "
                    "disagree about what serving reads")
        else:
            rep.add("QL403", "weight-traffic", "info",
                    _where(entry, measure),
                    f"{measure}: jaxpr-live {static} B matches the "
                    f"accessor's {expected} B (slack {slack} B)")

    # QL404 (info): allocated-window growth — the paged-KV gap, quantified
    if max_len:
        wl = sum(_var_size(v, max_len)[1]
                 for v in entry.closed.jaxpr.invars)
        record["window_state_bytes_per_token"] = wl
        if wl and mem.envelope_len:
            pinned_env = wl * mem.envelope_len
            per_slot = wl // mem.slots if mem.slots else wl
            record["window_state_envelope_bytes"] = pinned_env
            rep.add("QL404", "cache-growth", "info", _where(entry),
                    f"window state pins {wl} B/token ({per_slot} B/token/"
                    f"slot) scaled by the *allocated* max_len, not the "
                    f"used length — {pinned_env / _MIB:.1f} MiB at the "
                    f"envelope L={mem.envelope_len} even for one-token "
                    "sequences; a paged KV cache reclaims that tail")
    return rep, record


# ----------------------------------------------------- cross-entry checks
def check_kv_static_gap(entries: Sequence[TracedEntry]) -> Report:
    """Prove the int8-KV-vs-bf16-KV HBM gap *statically*: the per-token
    window bytes of the two ``serve_decode`` jaxprs, read off their cache
    invars alone, must put int8 strictly below bf16 — the same claim the
    serve bench measures live (``hbm_per_slot_MiB``)."""
    rep = Report()

    def window_cache_bytes(entry: TracedEntry) -> int:
        ml = entry.mem.max_len if entry.mem else 0
        return sum(_var_size(v, ml)[1]
                   for v, lbl in zip(entry.closed.jaxpr.invars, entry.labels)
                   if fnmatch.fnmatch(lbl, "cache*"))

    int8 = [e for e in entries
            if e.name.startswith("serve_decode") and "bf16-kv" not in e.name]
    bf16 = [e for e in entries if e.name.startswith("serve_decode")
            and "bf16-kv" in e.name]
    if not int8 or not bf16:
        return rep
    bi, bb = window_cache_bytes(int8[0]), window_cache_bytes(bf16[0])
    where = "jaxpr:serve_decode#kv-gap"
    if bi < bb:
        rep.add("QL405", "kv-gap-static", "info", where,
                f"int8 KV pins {bi} B/token vs bf16's {bb} B/token "
                f"({bb / max(bi, 1):.2f}x), proven from the jaxprs alone — "
                "the static counterpart of the serve bench's "
                "hbm_per_slot_MiB gap")
    else:
        rep.add("QL405", "kv-gap-static", "error", where,
                f"int8 KV cache pins {bi} B/token, NOT below bf16's "
                f"{bb} B/token — the int8 cache stopped paying for itself "
                "(scales outgrew the codes, or the bf16 path shrank)")
    return rep


# ------------------------------------------------------- bench-row check
def check_bench_rows(paths: Sequence[str], log=print) -> Report:
    """QL403 against the *live* benchmark artifacts: rebuild the bench-LM's
    static byte expectations with the same accessors and compare them to
    the ``--json`` rows ``benchmarks.run`` wrote (``decode/*``'s
    weight_MiB_per_step; ``serve/decode/*``'s hbm_per_slot_MiB). Importing
    ``benchmarks.common`` requires the repo root on sys.path / as cwd —
    the CI analysis job provides both."""
    rep = Report()
    records: List[Dict[str, Any]] = []
    for p in paths:
        with open(p) as fh:
            records.extend(json.load(fh))
    rows = {r["name"]: r for r in records}

    import jax.numpy as jnp

    from benchmarks import common
    from repro.core import QuantRecipe
    from repro.core.qtensor import tree_weight_bytes
    from repro.serve import kv as skv
    from repro.serve.engine import EngineConfig, init_state

    model, params = common.get_trained_lm()

    # decode/* rows: weight_MiB_per_step must equal tree_weight_bytes of
    # the identically-built params (fp16 row uses the raw tree)
    for tag, bits in (("fp16", None), ("w8", 8), ("w4", 4)):
        row = rows.get(f"decode/{tag}")
        if row is None:
            rep.add("QL403", "weight-traffic", "warning",
                    f"bench:decode/{tag}",
                    "row missing from the bench artifacts — run "
                    "`python -m benchmarks.run --only decode --json ...`")
            continue
        if bits is None:
            pv = params
        else:
            recipe = QuantRecipe(method="rtn", w_bits=bits, a_bits=None,
                                 w_granularity="per_channel", iters=1,
                                 batch_size=16)
            pv, _, _ = common.ptq(model, params, recipe, as_qtensor=True)
        static_mib = tree_weight_bytes(pv) / _MIB
        got = float(row["weight_MiB_per_step"])
        # the row prints 3 decimals; 1% covers accessor-vs-format rounding
        slack = max(0.002, 0.01 * static_mib)
        if abs(got - static_mib) > slack:
            rep.add("QL403", "weight-traffic", "error", f"bench:decode/{tag}",
                    f"bench row claims {got:.3f} MiB/step but "
                    f"tree_weight_bytes on the same params gives "
                    f"{static_mib:.3f} MiB (slack {slack:.3f}) — the bench "
                    "and the accessor no longer measure the same thing")
        else:
            rep.add("QL403", "weight-traffic", "info", f"bench:decode/{tag}",
                    f"bench {got:.3f} MiB/step == static "
                    f"{static_mib:.3f} MiB")

    # serve/decode/* rows: hbm_per_slot_MiB from the one accessor
    slots, max_len = 4, 64  # bench_serve's config (benchmarks/tables.py)
    per_slot: Dict[str, float] = {}
    for tag, kv_quant, dtype in (("int8-kv", True, None),
                                 ("bf16-kv", False, jnp.bfloat16)):
        row = rows.get(f"serve/decode/{tag}")
        if row is None or "hbm_per_slot_MiB" not in row:
            rep.add("QL403", "weight-traffic", "warning",
                    f"bench:serve/decode/{tag}",
                    "row missing/skipped in the bench artifacts — run "
                    "`python -m benchmarks.run --only serve --json ...`")
            continue
        ecfg = EngineConfig(slots=slots, max_len=max_len, prefill_group=2,
                            kv_quant=kv_quant, dtype=dtype)
        state = init_state(model, ecfg)
        static_mib = skv.hbm_per_slot_bytes(state["cache"], slots) / _MIB
        got = float(row["hbm_per_slot_MiB"])
        per_slot[tag] = got
        slack = max(0.0002, 0.01 * static_mib)
        if abs(got - static_mib) > slack:
            rep.add("QL403", "weight-traffic", "error",
                    f"bench:serve/decode/{tag}",
                    f"bench row claims {got:.4f} MiB/slot but "
                    f"hbm_per_slot_bytes on a freshly-built cache gives "
                    f"{static_mib:.4f} MiB (slack {slack:.4f}) — the row "
                    "and the accessor drifted apart")
        else:
            rep.add("QL403", "weight-traffic", "info",
                    f"bench:serve/decode/{tag}",
                    f"bench {got:.4f} MiB/slot == static "
                    f"{static_mib:.4f} MiB")
    if len(per_slot) == 2 and per_slot["int8-kv"] >= per_slot["bf16-kv"]:
        rep.add("QL403", "weight-traffic", "error", "bench:serve/decode",
                f"measured int8-kv per-slot HBM {per_slot['int8-kv']:.4f} "
                f"MiB is not below bf16-kv's {per_slot['bf16-kv']:.4f} MiB")
    return rep


# ------------------------------------------------------------ mem report
def mem_report_json(records: Sequence[Dict[str, Any]], path: str,
                    log=print) -> None:
    """Write the ``--mem-json`` artifact: per-entry liveness records plus
    the aggregate the roofline's peak-memory term cross-references."""
    envelope_peaks = [r.get("peak_envelope_bytes") for r in records
                      if r.get("peak_envelope_bytes") is not None]
    doc = {
        "entries": list(records),
        "roofline": {
            # the peak-HBM figure repro.roofline charges for serving:
            # max over entries of the envelope-scaled jaxpr peak
            "peak_hbm_bytes_envelope": max(envelope_peaks, default=0),
            "window_bytes_per_token": {
                r["entry"]: r["window_state_bytes_per_token"]
                for r in records
                if r.get("window_state_bytes_per_token")},
            "see": "repro.roofline.analysis (dtype_bytes is shared, so the "
                   "two accountings cannot disagree on byte widths)",
        },
    }
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2)
    log(f"memcheck report written to {path}")
