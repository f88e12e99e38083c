"""Repo-wide default allowlist for quantlint.

Every entry must carry a reason — the allowlist is the place where an
intentional violation is *documented*, not merely silenced. Entries here are
file-scoped globs (line numbers shift too easily under refactors); narrow,
line-level suppressions belong inline as ``# quantlint: ignore[QLxxx]``.

Rule catalog (see ROADMAP "Static analysis" for the prose version):

AST layer (QL1xx, analysis/ast_rules.py):
  QL101 jit-outside-engine        jax.jit outside the engine cache
  QL102 host-cast-in-trace        int()/float()/bool() on tracer values
  QL103 host-entropy-in-trace     time.* / np.random.* in traced code
  QL104 interpret-default-true    interpret=True as a kernel default
  QL105 pallas-missing-divis      pallas_call without a grid-divisibility
                                  guard (pad helper or assert on %)
  QL106 adhoc-host-clock          bare time.time/perf_counter/monotonic in
                                  host code outside repro/obs/ and
                                  benchmarks/ — route timing through
                                  repro.obs (Stopwatch/now()/spans)

jaxpr layer (QL2xx, analysis/jaxpr_checks.py):
  QL201 unused-input              pytree leaf passed in but dead in the jaxpr
  QL202 retrace-budget            compile count grows with layers / mesh
  QL203 donation-unsafe           donated buffer aliases another argument
  QL204 f64-promotion             float64 value inside a jitted quant path
  QL205 weak-type-output          weakly-typed output (promotion hazard)
  QL206 sharding-unconstrained    mesh= entry point without a dp-axis
                                  sharding constraint on its streams
  QL207 kernel-fallback           QTensor layout served by the dequantize
                                  fallback instead of a kernel

meta (analysis/report.py + ast_rules.py):
  QL110 stale-allowlist /         an allowlist entry — or an inline
        stale-inline-ignore       ``quantlint: ignore`` comment — suppressed
                                  nothing on a full run: the excused
                                  violation is gone; drop it (full runs
                                  only: partial layers would see false
                                  staleness)

quantcheck layer (QL3xx, analysis/intervals.py + diffcheck.py +
shardcheck.py — abstract-interpretation numerics verifier and cross-backend
kernel differ):
  QL301 int-overflow              an integer equation's value interval
                                  (contractions envelope-scaled to k_max)
                                  leaves its dtype range; a fitting int
                                  accumulator is reported as a proof (info)
  QL302 grid-saturation           a clamp bound is provably always active —
                                  the quantization grid collapses to a
                                  constant for the declared value ranges
  QL303 scale-underflow           a divisor interval entirely subnormal
                                  (< float32 tiny): the s1*s2*s3 product
                                  flushes to zero and kills FlexRound's
                                  reciprocal-rule gradients
  QL304 kernel-parity             Pallas-interpret vs XLA ref diverge on the
                                  shape lattice (bit-exact for single-tile /
                                  integer paths, tolerance elsewhere), or a
                                  layout dispatched to the wrong kernel
  QL305 lost-psum                 a shard_map collective reduces over the
                                  wrong mesh axis, or an output is declared
                                  replicated over a dp axis nothing reduced
                                  (with check_vma=False hiding it)
  QL306 scan-collective-          a collective inside a donated-carry scan
        unconstrained             body with no sharding constraint anchoring
                                  the reduced value's layout

memcheck layer (QL4xx, analysis/memcheck.py — jaxpr liveness vs per-entry
MemContract HBM budgets; opt-in via ``lint --mem``):
  QL401 hbm-budget                peak-live bytes exceed the entry's declared
                                  budget, at the traced window or scaled to
                                  the production envelope (serve_kv seq_max);
                                  a fitting peak is reported as a proof (info)
  QL402 dead-donation             a donated buffer no output can actually
                                  reuse (shape/dtype mismatch, or every
                                  candidate's lifetime overlaps) — the
                                  silent inverse of QL203
  QL403 weight-traffic            the jaxpr's live bytes for a labeled group
                                  drifted from the accessors' claim
                                  (tree_weight_bytes / hbm_per_slot_bytes),
                                  or from the live bench rows (--bench-rows)
  QL404 cache-growth (info)       window state scaling with the *allocated*
                                  max_len, not the used length — the
                                  quantified paged-KV gap (--mem-json)
  QL405 kv-gap-static             the int8-vs-bf16 per-token KV gap proven
                                  (info) or refuted (error) from the two
                                  serve_decode jaxprs alone
"""
from __future__ import annotations

from typing import List

from repro.analysis.report import AllowEntry

DEFAULT_ALLOWLIST: List[AllowEntry] = [
    # --- QL101: jax.jit outside the engine cache -------------------------
    AllowEntry(
        "QL101", "src/repro/core/reconstruct.py*",
        "the engine cache itself — every jit here is behind _get_engine / "
        "the schedule LRU, which is what QL101 protects"),
    AllowEntry(
        "QL101", "src/repro/kernels/*",
        "module-level jit'd public kernel wrappers: one callable per kernel, "
        "static block sizes — jit caching is keyed correctly by construction"),
    AllowEntry(
        "QL101", "src/repro/allocate/sensitivity.py*",
        "probe jit is cached per (recipe, mapping) in _PROBE_CACHE keyed by "
        "_probe_key; compile counts are asserted by tests/test_allocate.py"),
    AllowEntry(
        "QL101", "src/repro/launch/quantize.py*",
        "serve_smoke jits prefill/decode once per process at the end of a "
        "launch — no retrace surface"),
    AllowEntry(
        "QL101", "src/repro/launch/dryrun.py*",
        "AOT .lower() cost estimation; compiles are the measurement"),
    AllowEntry(
        "QL101", "src/repro/launch/train.py*",
        "pretraining step jit — one per run, outside the PTQ path"),
    AllowEntry(
        "QL101", "src/repro/analysis/*",
        "the linter's own trace harness: jits entry points once to obtain "
        "their jaxprs"),
    AllowEntry(
        "QL101", "src/repro/serve/engine.py*",
        "the serve engine's AOT compiles: every jit is lowered+compiled "
        "exactly once in __init__ (per bucket + decode), compile_count is "
        "frozen afterwards and pinned by the tier-1 no_retrace test"),
]


def default_allowlist() -> List[AllowEntry]:
    return list(DEFAULT_ALLOWLIST)
