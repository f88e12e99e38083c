"""JSONL event sink + RunManifest: run identity for every emitted record.

Every telemetry event, bench row, PTQ checkpoint meta, and serve stats dict
carries (a brief of) the same ``RunManifest`` so trajectories are comparable
across PRs: two BENCH_*.json files with different ``git_sha`` came from
different trees, and a ``schema_version`` bump marks a record-shape change
(the version is monotonic — readers may ignore unknown fields but must
refuse a *newer* schema they do not understand).

Manifest fields:
  schema_version     monotonic int — bump on any record-shape change
  git_sha            short sha of HEAD (``unknown`` outside a checkout)
  jax_version        jax.__version__
  backend            jax default backend (cpu/gpu/tpu) or the launch flag
  n_devices          jax.device_count()
  mesh               mesh tag (``debug``/``production``/axis string) or None
  recipe_digest      sha1 over the QuantRecipe repr (None outside PTQ)
  allocation_digest  digest of the automatic bit allocation (None if uniform)

The sink itself is append-only JSONL: one JSON object per line, flushed per
record so a crashed run keeps everything emitted before the crash. Records
are stamped with ``schema`` by :class:`repro.obs.telemetry.Telemetry`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import subprocess
from typing import Any, Dict, List, Optional

SCHEMA_VERSION = 2

# Version 2: span records carry ``id``, ``parent_id`` (None at a thread's
# top level) and ``t0_ns`` (start on ``telemetry.now_ns``'s clock), which
# place a span on the profiler trace's clock and give a layer's self time.
_SPAN_FIELDS_V2 = {"name": (str,), "id": (int,), "parent_id": (int, type(None)),
                   "t0_ns": (int,), "dur_us": (int, float)}

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


class JsonlSink:
    """Append-only JSONL file sink (one JSON object per line, per-record
    flush so partial runs stay readable)."""

    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._fh = open(path, "a")

    def emit(self, record: Dict[str, Any]) -> None:
        self._fh.write(json.dumps(record) + "\n")
        self._fh.flush()

    def close(self) -> None:
        try:
            self._fh.close()
        except ValueError:  # pragma: no cover - already closed
            pass


class ListSink:
    """In-memory sink for tests and the serve benchmark (records list)."""

    def __init__(self):
        self.records: List[Dict[str, Any]] = []

    def emit(self, record: Dict[str, Any]) -> None:
        self.records.append(record)

    def close(self) -> None:
        pass


def digest(obj: Any) -> str:
    """Stable short digest of an object's repr (recipes are frozen
    dataclasses, so repr is canonical)."""
    return hashlib.sha1(repr(obj).encode()).hexdigest()[:12]


def _git_sha() -> str:
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], cwd=_REPO_ROOT,
            capture_output=True, text=True, timeout=5)
        if out.returncode == 0 and out.stdout.strip():
            return out.stdout.strip()
    except Exception:  # pragma: no cover - git missing entirely
        pass
    return os.environ.get("GIT_SHA", "unknown")


@dataclasses.dataclass(frozen=True)
class RunManifest:
    schema_version: int
    git_sha: str
    jax_version: str
    backend: str
    n_devices: int
    mesh: Optional[str] = None
    recipe_digest: Optional[str] = None
    allocation_digest: Optional[str] = None

    @classmethod
    def collect(cls, backend: Optional[str] = None, mesh: Any = None,
                recipe: Any = None,
                allocation: Optional[dict] = None) -> "RunManifest":
        import jax
        if mesh is not None and not isinstance(mesh, str):
            mesh = ",".join(f"{n}={s}" for n, s in
                            zip(mesh.axis_names, mesh.devices.shape))
        alloc_digest = None
        if allocation:
            alloc_digest = str(allocation.get("digest") or digest(allocation))
        return cls(
            schema_version=SCHEMA_VERSION,
            git_sha=_git_sha(),
            jax_version=jax.__version__,
            backend=backend or jax.default_backend(),
            n_devices=jax.device_count(),
            mesh=mesh,
            recipe_digest=None if recipe is None else digest(recipe),
            allocation_digest=alloc_digest)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "RunManifest":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def brief(self) -> Dict[str, Any]:
        """The per-row stamp: enough to align a trajectory point with a
        commit without repeating the full manifest on every row."""
        return {"git_sha": self.git_sha,
                "schema_version": self.schema_version}

    def record(self) -> Dict[str, Any]:
        """The manifest as a sink record (the first line of every JSONL)."""
        return {"kind": "manifest", "schema": self.schema_version,
                **self.to_dict()}


_CURRENT: Optional[RunManifest] = None


def current_manifest() -> RunManifest:
    """Process-cached default manifest (git sha + versions + device count).

    Launch paths that know their recipe/mesh build a richer manifest with
    ``RunManifest.collect(...)``; everything that merely needs run identity
    (checkpoint meta, bench rows, serve stats) stamps this one.
    """
    global _CURRENT
    if _CURRENT is None:
        _CURRENT = RunManifest.collect()
    return _CURRENT


# ----------------------------------------------------------------- validation
def validate_events(path: str) -> List[str]:
    """Schema-check a telemetry JSONL: every line parses, carries ``kind`` +
    a ``schema`` no newer than this reader, a span record of schema 2 or
    later carries its id, parent id, start and duration, and at least one
    manifest record with a git sha is present. Returns a list of errors
    (empty = valid)."""
    errors: List[str] = []
    n, manifests = 0, 0
    try:
        fh = open(path)
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    with fh:
        for i, line in enumerate(fh, 1):
            if not line.strip():
                continue
            n += 1
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                errors.append(f"{path}:{i}: invalid JSON ({e})")
                continue
            if not isinstance(rec, dict) or "kind" not in rec:
                errors.append(f"{path}:{i}: record has no 'kind'")
                continue
            schema = rec.get("schema")
            if not isinstance(schema, int):
                errors.append(f"{path}:{i}: record has no int 'schema'")
            elif schema > SCHEMA_VERSION:
                errors.append(f"{path}:{i}: schema {schema} is newer than "
                              f"this reader ({SCHEMA_VERSION})")
            if (rec.get("kind") == "span" and isinstance(schema, int)
                    and schema >= 2):
                for k, types in _SPAN_FIELDS_V2.items():
                    if k not in rec or type(rec[k]) not in types:
                        errors.append(f"{path}:{i}: span record has no "
                                      f"valid {k!r}")
            if rec.get("kind") == "manifest":
                manifests += 1
                if not rec.get("git_sha"):
                    errors.append(f"{path}:{i}: manifest has no git_sha")
    if n == 0:
        errors.append(f"{path}: no records")
    if manifests == 0:
        errors.append(f"{path}: no manifest record — the run has no "
                      "identity; emit RunManifest first")
    return errors


def check_bench(path: str) -> List[str]:
    """Assert every bench JSON record is manifest-stamped (git sha + schema
    version) — the contract that makes BENCH_*.json trajectories comparable
    across PRs."""
    errors: List[str] = []
    try:
        with open(path) as fh:
            records = json.load(fh)
    except (OSError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable ({e})"]
    if not isinstance(records, list) or not records:
        return [f"{path}: expected a non-empty list of records"]
    for i, rec in enumerate(records):
        m = rec.get("manifest") if isinstance(rec, dict) else None
        if not isinstance(m, dict):
            errors.append(f"{path}[{i}] ({rec.get('name', '?')}): "
                          "no manifest stamp")
            continue
        if not m.get("git_sha"):
            errors.append(f"{path}[{i}]: manifest has no git_sha")
        if not isinstance(m.get("schema_version"), int):
            errors.append(f"{path}[{i}]: manifest has no schema_version")
    return errors


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(
        description="validate telemetry JSONL / bench JSON manifests")
    ap.add_argument("--validate", metavar="EVENTS_JSONL", default=None,
                    help="schema-check a telemetry events file")
    ap.add_argument("--check-bench", metavar="BENCH_JSON", default=None,
                    help="assert every bench record is manifest-stamped")
    args = ap.parse_args()
    if not args.validate and not args.check_bench:
        ap.error("pass --validate and/or --check-bench")
    errors: List[str] = []
    if args.validate:
        errors += validate_events(args.validate)
        if not errors:
            n = sum(1 for line in open(args.validate) if line.strip())
            print(f"{args.validate}: {n} records, schema <= "
                  f"{SCHEMA_VERSION}, manifest-stamped: OK")
    if args.check_bench:
        errs = check_bench(args.check_bench)
        errors += errs
        if not errs:
            print(f"{args.check_bench}: all records manifest-stamped: OK")
    for e in errors:
        print(f"error: {e}")
    if errors:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
