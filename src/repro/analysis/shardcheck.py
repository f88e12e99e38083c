"""quantcheck layer 3: shard-safety checks (QL305/QL306).

Extends QL206's coarse "some collective or constraint touches the dp axes"
with two structural rules over explicit SPMD regions:

  QL305 lost-psum / wrong-axis collective
      Inside a ``shard_map``: every collective must reduce over at least
      one declared data-parallel axis, and an output declared *replicated*
      over a dp axis that shards an input must actually have been reduced
      over that axis by some collective. shard_map's own replication check
      (``check_vma=True``) proves the latter natively — so the rule only
      fires where that guard was turned off, which is exactly how the
      classic lost-psum ships: per-host losses declared replicated,
      ``check_vma=False`` silencing the one check that would have caught
      it, every host quietly training on a different objective.

  QL306 unconstrained collective in a donated scan body
      A raw collective inside the scan body of a donated-carry entry
      (the recon chunk shape) without any sharding constraint in the same
      body: the GSPMD partitioner has no anchor for the reduced value, so
      layouts drift step-over-step inside donated buffers. The engine's
      stream re-constrain path is the matching fix.
"""
from __future__ import annotations

from typing import Any, Iterable, List, Set, Tuple

from repro.analysis.jaxpr_checks import _all_jaxprs
from repro.analysis.report import Report
from repro.analysis.trace import TracedEntry

#: primitives that reduce/collect across mesh axes
COLLECTIVES = frozenset({
    "psum", "psum2", "pmean", "pmax", "pmin", "all_reduce", "all_gather",
    "all_gather_invariant", "reduce_scatter", "all_to_all",
})


def _axes_of(eqn) -> Tuple[str, ...]:
    ax = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(ax, str):
        ax = (ax,)
    return tuple(a for a in ax if isinstance(a, str))


def _body(v) -> Any:
    return v.jaxpr if hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns") else v


def _eqns_in(jaxpr) -> Iterable[Any]:
    for j in _all_jaxprs(jaxpr):
        yield from j.eqns


def _spec_axes(spec) -> Set[str]:
    """All mesh axes mentioned by one shard_map in_specs/out_specs entry."""
    out: Set[str] = set()
    for axes in spec:
        if isinstance(axes, str):
            axes = (axes,)
        out.update(a for a in axes or () if isinstance(a, str))
    return out


# ------------------------------------------------------------------- QL305
def check_shard_map(entry: TracedEntry) -> Report:
    rep = Report()
    dp = set(entry.dp)
    for jaxpr in _all_jaxprs(entry.closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "shard_map":
                continue
            body = _body(eqn.params["jaxpr"])
            colls = [(e, _axes_of(e)) for e in _eqns_in(body)
                     if e.primitive.name in COLLECTIVES]
            coll_axes: Set[str] = set()
            for _, axes in colls:
                coll_axes.update(axes)
            check_vma = bool(eqn.params.get("check_vma", True))

            if dp:
                for e, axes in colls:
                    if axes and not set(axes) & dp:
                        rep.add(
                            "QL305", "collective-wrong-axis", "error",
                            f"jaxpr:{entry.name}#shard_map/"
                            f"{e.primitive.name}",
                            f"{e.primitive.name} over mesh axes "
                            f"{sorted(axes)} never reduces over a declared "
                            f"data-parallel axis {sorted(dp)} — the "
                            "cross-replica reduction this entry promises "
                            "is running on the wrong axis")

            if not check_vma:
                in_axes: Set[str] = set()
                for spec in eqn.params["in_specs"]:
                    in_axes |= _spec_axes(spec)
                for i, spec in enumerate(eqn.params["out_specs"]):
                    out_axes = _spec_axes(spec)
                    missing = sorted((in_axes - out_axes)
                                     & (dp or in_axes) - coll_axes)
                    if missing:
                        rep.add(
                            "QL305", "lost-psum", "error",
                            f"jaxpr:{entry.name}#shard_map/out{i}",
                            f"output {i} is declared replicated over mesh "
                            f"axes {missing} that shard an input, but no "
                            "collective reduces over them and "
                            "check_vma=False disabled shard_map's own "
                            "replication proof — each shard returns a "
                            "different value (lost psum)")
    return rep


# ------------------------------------------------------------------- QL306
def check_scan_collectives(entry: TracedEntry) -> Report:
    rep = Report()
    if not entry.donated or entry.mesh is None:
        return rep
    for jaxpr in _all_jaxprs(entry.closed.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "scan":
                continue
            body = _body(eqn.params["jaxpr"])
            colls = [e for e in _eqns_in(body)
                     if e.primitive.name in COLLECTIVES]
            if not colls:
                continue
            anchored = any(e.primitive.name == "sharding_constraint"
                           for e in _eqns_in(body))
            if not anchored:
                names = sorted({e.primitive.name for e in colls})
                rep.add(
                    "QL306", "scan-collective-unconstrained", "error",
                    f"jaxpr:{entry.name}#scan",
                    f"collective(s) {names} inside the scan body of a "
                    "donated-carry entry with no sharding constraint in "
                    "the same body — the partitioner has no layout anchor "
                    "for the reduced value, so donated-buffer layouts can "
                    "drift across steps; re-constrain the stream inside "
                    "the body (see reconstruct's stream path)")
    return rep


def check_shard_safety(entry: TracedEntry) -> Report:
    """QL305 + QL306 for one traced entry."""
    rep = check_shard_map(entry)
    rep.extend(check_scan_collectives(entry))
    return rep


__all__: List[str] = ["COLLECTIVES", "check_shard_map",
                      "check_scan_collectives", "check_shard_safety"]
