"""jaxpr-level analyzers (QL2xx) over :class:`~repro.analysis.trace.TracedEntry`.

  QL201 unused-input        a pytree leaf passed into the jitted entry is
                            dead in the jaxpr (DCE removes it). This is the
                            analyzer that proves "a_state actually flows into
                            the kernel" — the PR 5 class of bug.
  QL202 retrace-budget      compile counts grow with layer count (or flap
                            with mesh on/off) instead of staying flat under
                            the engine cache.
  QL203 donation-unsafe     a donated carry buffer aliases another argument
                            (same device buffer twice) or is consumed by more
                            than one equation / returned unchanged — XLA may
                            free or overwrite it while still referenced.
  QL204 f64-promotion       a float64 value appears inside the jitted quant
                            path (silent 2x memory + slow path).
  QL205 weak-type-output    an entry output is weakly typed — downstream
                            promotion becomes caller-dependent.
  QL206 sharding-unconstrained  an entry that declares ``mesh=`` contains no
                            sharding constraint (or psum) touching the mesh's
                            data-parallel axes — "sharded" in the docstring
                            only.

``no_retrace`` is the reusable compile-flatness guard (also exposed as a
tier-1 pytest fixture in tests/conftest.py): it snapshots
``engine_stats().compile_count`` plus a process-wide XLA backend-compile
counter, and raises :class:`RetraceError` if the deltas exceed the budget.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Iterable, List, Optional

import jax

from repro.analysis.report import Report
from repro.analysis.trace import TracedEntry, toy_chain, toy_recipe
from repro.core import reconstruct as rec

try:  # jax internal, but stable across the versions this repo supports
    from jax._src.interpreters import partial_eval as _pe
except ImportError:  # pragma: no cover - older/newer jax layouts
    _pe = None


# ------------------------------------------------------------ jaxpr walking
def _subjaxprs(jaxpr) -> Iterable[Any]:
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):  # ClosedJaxpr
                yield v.jaxpr
            elif hasattr(v, "eqns"):  # raw Jaxpr
                yield v
            elif isinstance(v, (tuple, list)):
                for item in v:
                    if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                        yield item.jaxpr
                    elif hasattr(item, "eqns"):
                        yield item


def _all_jaxprs(jaxpr) -> Iterable[Any]:
    yield jaxpr
    for sub in _subjaxprs(jaxpr):
        yield from _all_jaxprs(sub)


# --------------------------------------------------------- QL201 unused input
def _used_invars(closed) -> List[bool]:
    """Which flat invars the jaxpr actually consumes (transitively, through
    scan/jit subjaxprs)."""
    jaxpr = closed.jaxpr
    if _pe is not None:
        _, used = _pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
        return list(used)
    # fallback: syntactic reachability (no transitive dead-code analysis)
    referenced = set()
    for j in _all_jaxprs(jaxpr):
        for eqn in j.eqns:
            for v in eqn.invars:
                referenced.add(id(v))
    for v in jaxpr.outvars:
        referenced.add(id(v))
    return [id(v) in referenced for v in jaxpr.invars]


def check_unused_inputs(entry: TracedEntry) -> Report:
    import fnmatch
    rep = Report()
    used = _used_invars(entry.closed)
    for label, u in zip(entry.labels, used):
        if u:
            continue
        if any(fnmatch.fnmatch(label, pat) for pat in entry.allow_unused):
            rep.add("QL201", "unused-input", "info",
                    f"jaxpr:{entry.name}#{label}",
                    "dead leaf (explicitly allowed for this entry)")
            continue
        rep.add("QL201", "unused-input", "error",
                f"jaxpr:{entry.name}#{label}",
                "leaf is passed into the jitted entry but dead in the "
                "jaxpr — state silently not consumed (the a_state-drop "
                "failure class)")
    return rep


# ------------------------------------------------------------- QL203 donation
def check_donation(entry: TracedEntry) -> Report:
    rep = Report()
    jaxpr = entry.closed.jaxpr
    outvar_ids = {id(v) for v in jaxpr.outvars}
    for i in sorted(entry.donated):
        var = jaxpr.invars[i]
        n_uses = sum(1 for eqn in jaxpr.eqns
                     for v in eqn.invars if v is var)
        if n_uses > 1:
            rep.add("QL203", "donation-unsafe", "error",
                    f"jaxpr:{entry.name}#{entry.labels[i]}",
                    f"donated buffer consumed by {n_uses} equations — XLA "
                    "may overwrite it while another consumer still reads it")
        if id(var) in outvar_ids:
            rep.add("QL203", "donation-unsafe", "error",
                    f"jaxpr:{entry.name}#{entry.labels[i]}",
                    "donated input returned unchanged — the caller receives "
                    "a handle to a buffer XLA was told it may free")
    # eager layer: the exemplar donated leaves must occupy distinct device
    # buffers (what _dealias guarantees; aliased buffers make XLA reject the
    # donation or, worse, double-donate)
    seen = {}
    for leaf, i in zip(entry.donated_leaves, sorted(entry.donated)):
        try:
            ptr = leaf.unsafe_buffer_pointer()
        except Exception:  # sharded/committed arrays: pointer not exposed
            continue
        if ptr in seen:
            rep.add("QL203", "donation-unsafe", "error",
                    f"jaxpr:{entry.name}#{entry.labels[i]}",
                    f"aliases the device buffer of "
                    f"{entry.labels[seen[ptr]]} — the same storage would be "
                    "donated twice (run states through _dealias)")
        else:
            seen[ptr] = i
    return rep


# ------------------------------------------------- QL204/QL205 promotion
def check_promotion(entry: TracedEntry) -> Report:
    import numpy as np
    rep = Report()
    flagged = set()
    for j in _all_jaxprs(entry.closed.jaxpr):
        for eqn in j.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt is not None and dt == np.float64:
                    key = (eqn.primitive.name, str(dt))
                    if key not in flagged:
                        flagged.add(key)
                        rep.add("QL204", "f64-promotion", "error",
                                f"jaxpr:{entry.name}#{eqn.primitive.name}",
                                "float64 value inside the jitted quant path "
                                "(unintended promotion: 2x memory, slow "
                                "path)")
    for i, v in enumerate(entry.closed.jaxpr.outvars):
        aval = getattr(v, "aval", None)
        if getattr(aval, "weak_type", False):
            rep.add("QL205", "weak-type-output", "warning",
                    f"jaxpr:{entry.name}#out[{i}]",
                    "weakly-typed output — downstream dtype promotion "
                    "becomes caller-dependent")
    return rep


# ------------------------------------------------------------ QL206 sharding
def check_sharding(entry: TracedEntry) -> Report:
    rep = Report()
    if entry.mesh is None or not entry.dp:
        return rep
    constrained_axes = set()
    for j in _all_jaxprs(entry.closed.jaxpr):
        for eqn in j.eqns:
            pname = eqn.primitive.name
            if pname == "sharding_constraint":
                spec = getattr(eqn.params.get("sharding"), "spec", ())
                for part in spec or ():
                    parts = part if isinstance(part, tuple) else (part,)
                    constrained_axes.update(p for p in parts if p)
            elif pname in ("psum", "pmean", "all_gather", "all_reduce"):
                axes = eqn.params.get("axes",
                                      eqn.params.get("axis_name", ()))
                if isinstance(axes, str):
                    axes = (axes,)
                constrained_axes.update(axes or ())
    if not constrained_axes.intersection(entry.dp):
        rep.add("QL206", "sharding-unconstrained", "error",
                f"jaxpr:{entry.name}#mesh",
                f"entry declares mesh axes {entry.dp} but its jaxpr carries "
                "no sharding constraint or collective touching them — the "
                "data-parallel contract exists only in the docstring")
    return rep


def check_entry(entry: TracedEntry) -> Report:
    rep = Report()
    rep.extend(check_unused_inputs(entry))
    rep.extend(check_donation(entry))
    rep.extend(check_promotion(entry))
    rep.extend(check_sharding(entry))
    return rep


# ----------------------------------------------------------- QL202 retrace
class RetraceError(AssertionError):
    """Raised by ``no_retrace`` when compile counts move past the budget."""


def _install_backend_listener() -> bool:
    """Count actual XLA backend compiles process-wide (cache hits emit no
    event). Delegates to :mod:`repro.obs.compile_events` — quantlint and
    telemetry share one jax.monitoring subscription, so each compile is
    also attributed to the enclosing telemetry span. Idempotent; returns
    installed-ness."""
    from repro.obs import compile_events
    return compile_events.install()


@contextlib.contextmanager
def no_retrace(budget: int = 0, xla_budget: Optional[int] = None):
    """Assert compile flatness across the enclosed region.

    ``budget`` bounds the growth of ``engine_stats().compile_count`` (the
    engine's own trace-time counters). ``xla_budget``, when given, also
    bounds raw XLA backend compilations (catches retraces in code that does
    not route through the engine counters, e.g. the deploy kernel wrappers);
    leave it None in code that runs eager jnp math with fresh shapes, since
    every new eager shape compiles too.
    """
    from repro.obs import compile_events
    installed = _install_backend_listener()
    s0 = dataclasses.replace(rec.engine_stats())
    b0 = compile_events.backend_compiles()
    yield
    s1 = rec.engine_stats()
    delta = s1.compile_count - s0.compile_count
    bdelta = compile_events.backend_compiles() - b0
    if delta > budget:
        raise RetraceError(
            f"engine compile count grew by {delta} (budget {budget}): "
            f"step +{s1.step_compiles - s0.step_compiles}, "
            f"schedule +{s1.schedule_compiles - s0.schedule_compiles}, "
            f"teacher +{s1.teacher_compiles - s0.teacher_compiles}, "
            f"student +{s1.student_compiles - s0.student_compiles}, "
            f"recon_err +{s1.recon_error_compiles - s0.recon_error_compiles}, "
            f"probe +{s1.probe_compiles - s0.probe_compiles} "
            f"(XLA backend compiles +{bdelta})")
    if xla_budget is not None and installed and bdelta > xla_budget:
        raise RetraceError(
            f"XLA backend compile count grew by {bdelta} "
            f"(budget {xla_budget}) while engine counters moved {delta}")


def _run_chain(blocks, recipe, d: int, mesh=None):
    x = jax.random.normal(jax.random.key(31), (recipe.batch_size, d))
    y = jax.random.normal(jax.random.key(32), (recipe.batch_size, d))
    for b in blocks:
        rec.reconstruct_block(b, recipe, x, y, jax.random.key(0), mesh=mesh)


def check_retrace(per_layer: bool = False, n_small: int = 2,
                  n_large: int = 4, iters: int = 4, d: int = 16,
                  mesh=None) -> Report:
    """Compile counts must stay flat across layer count (and across repeat
    runs under a mesh): warm the engine cache on a short chain, then demand
    zero new compiles for a longer chain of structurally identical blocks.

    ``per_layer=True`` is the seeded regression: blocks with ``apply_key=
    None`` defeat engine sharing, so every layer retraces — QL202 must fire.
    """
    rep = Report()
    token = None if per_layer else "quantlint-retrace"
    recipe = toy_recipe(iters=iters, batch_size=4)
    suffix = "_sharded" if mesh is not None else ""
    _run_chain(toy_chain(n_small, token=token, d=d), recipe, d, mesh)
    try:
        with no_retrace(0):
            _run_chain(toy_chain(n_large, token=token, d=d), recipe, d, mesh)
    except RetraceError as e:
        rep.add("QL202", "retrace-budget", "error",
                f"jaxpr:recon_chain{suffix}#L{n_small}->L{n_large}",
                f"compile counts grew with layer count: {e}")
    else:
        rep.add("QL202", "retrace-budget", "info",
                f"jaxpr:recon_chain{suffix}#L{n_small}->L{n_large}",
                "compile-flat across layer count")
    return rep
