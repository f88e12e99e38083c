"""quantlint (repro.analysis): the analyzers must flag exactly the seeded
shipped regressions — the PR 5 ``a_state`` drop, a per-layer retrace, an
int16 matmul accumulator, subnormal FlexRound scale products, a lost
shard_map psum — and stay quiet on the current clean code.

The seeded bugs are real bugs this repo shipped (or nearly shipped) and
fixed: ``_matmul_2d`` silently dropping ``a_state`` off the int8 path
degrades serving to the un-snapped grid, per-layer retraces are what the
engine cache exists to prevent, and the QL3xx fixtures are the numerics
hazards quantcheck's abstract interpreter and shard checker exist to prove
absent.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import RetraceError, no_retrace
from repro.analysis import ast_rules, jaxpr_checks, trace
from repro.analysis.allowlist import default_allowlist
from repro.analysis.coverage import FALLBACK, kernel_coverage
from repro.analysis.intervals import check_intervals
from repro.analysis.report import AllowEntry, Finding, Report
from repro.analysis.shardcheck import check_shard_safety


# ------------------------------------------------------------- report layer
def test_report_allowlist_downgrades_with_reason():
    rep = Report()
    rep.add("QL201", "unused-input", "error", "jaxpr:e#x", "dead")
    rep.add("QL201", "unused-input", "error", "jaxpr:other#y", "dead")
    out = rep.apply_allowlist([AllowEntry("QL201", "jaxpr:e#*", "by design")])
    assert out.exit_code() == 1  # the unmatched finding still fails
    kept = {f.where: f for f in out}
    assert kept["jaxpr:e#x"].severity == "info"
    assert kept["jaxpr:e#x"].allowlisted == "by design"
    assert kept["jaxpr:other#y"].severity == "error"


def test_finding_rejects_unknown_severity():
    with pytest.raises(ValueError):
        Finding("QL999", "x", "fatal", "a:1", "m")


# ---------------------------------------------------------------- AST layer
BAD_SRC = '''
import time
import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

def step(x):
    t = time.time()
    r = np.random.rand()
    m = float(jnp.max(x))
    k = float(x.shape[0])
    return x * m + t + r

compiled = jax.jit(step)

def kern(x, interpret=True):
    return pl.pallas_call(lambda ref, o: None, out_shape=x)(x)
'''


def test_ast_rules_fire_on_seeded_source():
    rep = ast_rules.lint_source(BAD_SRC, "bad.py")
    rules = sorted({f.rule for f in rep})
    assert rules == ["QL101", "QL102", "QL103", "QL104", "QL105"]
    # the host-cast rule must not fire on float(<static shape int>)
    casts = [f for f in rep if f.rule == "QL102"]
    assert len(casts) == 1 and ":11" in casts[0].where


def test_ast_inline_suppression():
    src = ("import jax\n"
           "f = jax.jit(abs)  # quantlint: ignore[QL101]\n")
    assert len(ast_rules.lint_source(src, "s.py")) == 0
    src_other_rule = ("import jax\n"
                      "f = jax.jit(abs)  # quantlint: ignore[QL104]\n")
    assert len(ast_rules.lint_source(src_other_rule, "s.py")) == 1


def test_ast_clean_on_current_src():
    """Every QL1xx finding in src/ must be covered by the default allowlist
    (an intentional, documented violation) — new ones fail this test."""
    import os

    import repro
    pkg = os.path.dirname(os.path.abspath(repro.__file__))
    rep = ast_rules.lint_tree(os.path.dirname(pkg),
                              rel_to=os.path.dirname(os.path.dirname(pkg)))
    rep = rep.apply_allowlist(default_allowlist())
    assert rep.errors() == [], rep.pretty()


# -------------------------------------------------- QL201 unused input
def test_unused_input_flags_seeded_a_state_drop():
    entry = trace.qtensor_matmul_entry("w8a8", drop_a_state=True)
    rep = jaxpr_checks.check_unused_inputs(entry)
    wheres = sorted(f.where for f in rep.errors())
    assert len(wheres) == 2, rep.pretty(verbose=True)
    assert all("a_state" in w for w in wheres)


def test_unused_input_quiet_on_clean_matmul_layouts():
    for row in trace.MATMUL_LAYOUTS:
        entry = trace.qtensor_matmul_entry(row[0])
        rep = jaxpr_checks.check_entry(entry)
        assert rep.errors() == [], f"{row[0]}: {rep.pretty(verbose=True)}"


def test_recon_chunk_and_probe_clean():
    for entry in (trace.recon_chunk_entry(), trace.probe_entry()):
        rep = jaxpr_checks.check_entry(entry)
        assert rep.errors() == [], f"{entry.name}: {rep.pretty(verbose=True)}"
        # the one intentionally-dead leaf is allowlisted, visible as info
        infos = [f for f in rep if f.severity == "info"]
        if entry.name == "recon_chunk":
            assert any("steps" in f.where for f in infos)


def test_unused_input_respects_entry_allowlist():
    entry = trace.qtensor_matmul_entry("w8a8", drop_a_state=True)
    allowed = dataclasses.replace(entry, allow_unused=("a_state*",))
    rep = jaxpr_checks.check_unused_inputs(allowed)
    assert rep.errors() == []
    assert len([f for f in rep if f.severity == "info"]) == 2


# ------------------------------------------------------- QL203 donation
def test_donation_alias_detected():
    f = jax.jit(lambda a, b: (a + 1.0, b + 2.0), donate_argnums=(0, 1))
    x = jnp.ones((8,), jnp.float32)
    entry = trace.trace_jitted(f, (x, x), name="alias", argnames=("a", "b"),
                               donate_argnums=(0, 1))
    rep = jaxpr_checks.check_donation(entry)
    assert any("aliases the device buffer" in f.message
               for f in rep.errors()), rep.pretty(verbose=True)


def test_donation_clean_on_dealiased_chunk():
    entry = trace.recon_chunk_entry()
    assert jaxpr_checks.check_donation(entry).errors() == []


# ------------------------------------------------- QL204/QL206 negative
def test_f64_promotion_detected():
    with jax.enable_x64(True):
        f = jax.jit(lambda x: jnp.asarray(x, jnp.float64) * 2.0)
        entry = trace.trace_jitted(f, (jnp.ones((4,), jnp.float32),),
                                   name="f64", argnames=("x",))
        rep = jaxpr_checks.check_promotion(entry)
    assert any(f.rule == "QL204" for f in rep.errors())


def test_sharding_honesty_negative_control():
    """An unsharded jaxpr that *claims* a mesh must fail QL206."""
    if jax.device_count() < 8:
        pytest.skip("debug mesh needs 8 devices")
    from repro.launch.mesh import make_debug_mesh
    entry = trace.recon_chunk_entry()  # traced without a mesh
    fake = dataclasses.replace(entry, mesh=make_debug_mesh(), dp=("data",))
    assert jaxpr_checks.check_sharding(fake).exit_code() == 1


def test_sharded_chunk_constrains_dp_axes():
    if jax.device_count() < 8:
        pytest.skip("debug mesh needs 8 devices")
    from repro.launch.mesh import make_debug_mesh
    entry = trace.recon_chunk_entry(mesh=make_debug_mesh())
    rep = jaxpr_checks.check_entry(entry)
    assert rep.errors() == [], rep.pretty(verbose=True)


# -------------------------------------------------------- QL202 retrace
def test_retrace_flat_on_shared_token():
    rep = jaxpr_checks.check_retrace(per_layer=False)
    assert rep.exit_code() == 0, rep.pretty(verbose=True)


def test_retrace_flags_seeded_per_layer():
    rep = jaxpr_checks.check_retrace(per_layer=True)
    errs = rep.errors()
    assert len(errs) == 1 and errs[0].rule == "QL202", rep.pretty(True)
    assert "step +" in errs[0].message


def test_no_retrace_guard_raises(no_retrace):
    from repro.core import reconstruct as rec
    block = trace.toy_block(jax.random.key(41), "guard", token=None)
    recipe = trace.toy_recipe(iters=2, batch_size=2)
    x = jax.random.normal(jax.random.key(42), (2, 16))
    y = jax.random.normal(jax.random.key(43), (2, 16))
    with pytest.raises(RetraceError):
        with no_retrace(0):
            rec.reconstruct_block(block, recipe, x, y, jax.random.key(0))


# ----------------------------------------------------- QL207 coverage
def test_coverage_names_conv_fallback_sites():
    rep, rows = kernel_coverage()
    by_site = {r.site: r for r in rows}
    assert by_site["w8a8"].kernel == "qmatmul_int8_ref"
    assert by_site["w4_packed"].kernel == "dequant_matmul_w4_ref"
    assert by_site["experts_batched"].kernel == "dequant_matmul_batched_ref"
    conv_sites = [s for s in by_site if ".conv" in s or "patch_embed" in s]
    assert len(conv_sites) == 3
    assert all(by_site[s].kernel == FALLBACK for s in conv_sites)
    flagged = {f.where.split(":", 1)[1] for f in rep.warnings()}
    assert flagged == set(conv_sites)
    # only the conv frontends fall back — every matmul layout has a kernel
    assert all(not by_site[r[0]].fallback for r in trace.MATMUL_LAYOUTS)


# ---------------------------------------------- QL110 allowlist staleness
def test_stale_allowlist_entry_errors_on_full_run():
    rep = Report()
    rep.add("QL201", "unused-input", "error", "jaxpr:e#x", "dead")
    entries = [AllowEntry("QL201", "jaxpr:e#*", "by design"),
               AllowEntry("QL104", "src/gone.py*", "kernel long deleted")]
    # partial runs never audit staleness (false positives by construction)
    assert rep.apply_allowlist(entries).by_rule("QL110") == []
    audited = rep.apply_allowlist(entries, report_stale=True)
    stale = audited.by_rule("QL110")
    assert len(stale) == 1 and "QL104" in stale[0].where, audited.pretty(True)
    assert "kernel long deleted" in stale[0].message
    assert audited.exit_code() == 1


# ------------------------------------------------- QL102 taint regression
def test_ql102_quiet_on_concrete_jnp_values():
    """Host casts of values *not* data-dependent on a tracer argument are
    fine (they run at trace time on concrete arrays) — the old rule flagged
    any jnp-rooted expression."""
    src = ("import jax\n"
           "import jax.numpy as jnp\n"
           "def f(x):\n"
           "    eps = float(jnp.float32(1e-6))\n"
           "    lr = float(jnp.asarray([0.1]).max())\n"
           "    return x * eps * lr\n"
           "g = jax.jit(f)\n")
    assert ast_rules.lint_source(src, "s.py").by_rule("QL102") == []


def test_ql102_taint_flows_through_assignment():
    src = ("import jax\n"
           "import jax.numpy as jnp\n"
           "def f(x):\n"
           "    y = jnp.abs(x)\n"
           "    z = y.sum()\n"
           "    return int(z)\n"
           "g = jax.jit(f)\n")
    flagged = ast_rules.lint_source(src, "s.py").by_rule("QL102")
    assert len(flagged) == 1 and ":6" in flagged[0].where


# ------------------------------------- QL301/302/303 interval interpreter
def test_intervals_flags_seeded_int16_accumulator():
    rep = check_intervals(trace.int8_overflow_entry())
    errs = rep.errors()
    assert errs and all(f.rule == "QL301" for f in errs), rep.pretty(True)
    assert any("int16" in f.message for f in errs)


def test_intervals_proves_w8a8_accumulator_fits_envelope():
    rep = check_intervals(trace.qtensor_matmul_entry("w8a8"))
    assert rep.errors() == [], rep.pretty(True)
    proofs = [f for f in rep if f.rule == "QL301" and f.severity == "info"]
    assert proofs and "proven" in proofs[0].message, rep.pretty(True)


def test_intervals_flags_seeded_scale_underflow():
    rep = check_intervals(trace.flexround_apply_entry(underflow=True))
    errs = rep.errors()
    assert errs and all(f.rule == "QL303" for f in errs), rep.pretty(True)


def test_intervals_flags_provable_grid_saturation():
    f = jax.jit(lambda x: jnp.clip(jnp.round(x / 2.0), -7.0, 7.0))
    entry = trace.trace_jitted(f, (jnp.ones((8,), jnp.float32),),
                               name="sat", argnames=("x",),
                               ranges=(("x", 64.0, 256.0),))
    errs = check_intervals(entry).errors()
    assert errs and all(f.rule == "QL302" for f in errs), errs


def test_intervals_quiet_on_clean_entries():
    entries = (trace.flexround_apply_entry(), trace.recon_chunk_entry(),
               trace.probe_entry(), *trace.matmul_entries())
    for entry in entries:
        rep = check_intervals(entry)
        assert rep.errors() == [], f"{entry.name}: {rep.pretty(True)}"


# -------------------------------------------- QL305/306 shard safety
def test_shardcheck_flags_seeded_lost_psum():
    rep = check_shard_safety(trace.lost_psum_entry())
    errs = rep.errors()
    assert errs and all(f.rule == "QL305" for f in errs), rep.pretty(True)
    assert {f.name for f in errs} == {"collective-wrong-axis", "lost-psum"}


def test_shardcheck_quiet_on_sharded_recon():
    if jax.device_count() < 8:
        pytest.skip("debug mesh needs 8 devices")
    from repro.launch.mesh import make_debug_mesh
    entry = trace.recon_chunk_entry(mesh=make_debug_mesh())
    rep = check_shard_safety(entry)
    assert rep.errors() == [], rep.pretty(True)


# ------------------------------------------------- QL304 differential
def test_diffcheck_lattice_covers_edge_shapes():
    from repro.analysis.diffcheck import EXPECTED_KERNELS, shape_lattice
    for layout in EXPECTED_KERNELS:
        lat = shape_lattice(layout)
        assert len(lat) >= 20, (layout, len(lat))
        ks = {k for _, _, k, _ in lat}
        # grid-non-divisible K and (2-D layouts) multi-K-tile rows present
        assert any(k % 128 for k in ks), layout
        if layout != "experts_batched":
            assert any(k > 512 for k in ks), layout


def test_diffcheck_parity_cells_match_policy():
    from repro.analysis.diffcheck import EXPECTED_KERNELS, check_parity
    cells = [  # (layout, e, m, k, n, expected mode)
        ("w4_packed", 1, 5, 64, 24, "bit-exact"),      # single tile
        ("w8a8", 1, 5, 1024, 24, "bit-exact"),         # int32 path, 2 K tiles
        ("w8_weight_only", 1, 5, 1024, 24, "tolerance"),  # float, 2 K tiles
    ]
    for layout, e, m, k, n, mode in cells:
        row = check_parity(layout, e, m, k, n)
        assert row.ok and row.mode == mode, row
        assert (row.kernel_ref, row.kernel_pallas) == EXPECTED_KERNELS[layout]


# --------------------------------------------- seeded lint-run wiring
@pytest.mark.parametrize("bug,rule", [("int8_overflow", "QL301"),
                                      ("scale_underflow", "QL303"),
                                      ("lost_psum", "QL305")])
def test_seeded_quantcheck_runs_exit_nonzero(bug, rule):
    from repro.analysis import lint
    rep = lint.run_analysis(jaxpr_only=True, seed_bug=bug,
                            log=lambda *a, **k: None)
    assert rep.exit_code() == 1
    assert any(f.rule == rule for f in rep.errors()), rep.pretty(True)


# --------------------------------------------- QL4xx memcheck (liveness)
def test_memcheck_flags_seeded_dead_donation():
    from repro.analysis.memcheck import check_memory
    rep, rec = check_memory(trace.dead_donation_entry())
    errs = rep.errors()
    assert errs and all(f.rule == "QL402" for f in errs), rep.pretty(True)
    assert "no output shares its shape" in errs[0].message
    assert rec["donation_dead"] == 1 and rec["donation_matched"] == 0
    # QL203 must stay quiet on it: the donation is useless, not unsafe
    assert jaxpr_checks.check_donation(trace.dead_donation_entry()).errors() \
        == []


def test_memcheck_flags_donation_lifetime_overlap():
    """The second QL402 shape: a same-shape output exists but materializes
    while the donated buffer is still being read."""
    from repro.analysis.memcheck import check_memory

    def f(a):
        b = a * 2.0            # shape/dtype-matching candidate, defined early
        c = jnp.sum(a + b)     # ...but `a` is still read after b exists
        return b, c

    x = jnp.ones((16, 16), jnp.float32)
    entry = trace.trace_jitted(jax.jit(f, donate_argnums=(0,)), (x,),
                               name="overlap", argnames=("a",),
                               donate_argnums=(0,))
    errs = check_memory(entry)[0].errors()
    assert errs and all(f.rule == "QL402" for f in errs), errs
    assert "lifetimes overlap" in errs[0].message


def test_memcheck_flags_seeded_hbm_blowout():
    from repro.analysis.memcheck import check_memory
    rep, rec = check_memory(trace.hbm_blowout_entry())
    errs = rep.errors()
    assert errs and all(f.rule == "QL401" for f in errs), rep.pretty(True)
    # blows the budget both at the traced window and at the envelope
    assert len(errs) == 2
    assert rec["peak_trace_bytes"] > rec["budget_trace_bytes"]
    assert rec["peak_envelope_bytes"] > rec["budget_envelope_bytes"]


def test_memcheck_quiet_on_clean_entries():
    from repro.analysis.memcheck import check_memory
    entries = (trace.recon_chunk_entry(), trace.probe_entry(),
               trace.flexround_apply_entry(), *trace.matmul_entries())
    for entry in entries:
        rep, _ = check_memory(entry)
        assert rep.errors() == [], f"{entry.name}: {rep.pretty(True)}"


def test_memcheck_scan_carry_counted_once():
    """A donated-carry scan's memory is the carry once across the whole
    loop body — trip count must not multiply the peak."""
    from repro.analysis.memcheck import _walk_jaxpr

    def make(trips):
        def f(c):
            def body(carry, _):
                return carry * 0.5 + 1.0, None
            out, _ = jax.lax.scan(body, c, None, length=trips)
            return out
        x = jnp.ones((64, 64), jnp.float32)
        return trace.trace_jitted(jax.jit(f), (x,), name=f"scan{trips}",
                                  argnames=("c",))

    p2 = _walk_jaxpr(make(2).closed.jaxpr, 0).peak_at(0)
    p64 = _walk_jaxpr(make(64).closed.jaxpr, 0).peak_at(0)
    assert p2 == p64, (p2, p64)
    # sanity: the carry itself is in the peak
    assert p2 >= 64 * 64 * 4


def test_memcheck_static_kv_gap():
    """check_kv_static_gap proves int8-below-bf16 from per-token window
    bytes of the cache invars alone (and errors on the inverse)."""
    from repro.analysis.memcheck import check_kv_static_gap

    def mk(dtype, tag):
        cache = jnp.zeros((2, 24, 2, 16), dtype)
        p = jnp.ones((4,), jnp.float32)
        f = jax.jit(lambda p, c: c.astype(jnp.float32).sum() + p.sum())
        mem = trace.mem_contract((p, cache), max_len=24)
        return trace.trace_jitted(f, (p, cache),
                                  name=f"serve_decode[toy]{tag}",
                                  argnames=("params", "cache"), mem=mem)

    int8, bf16 = mk(jnp.int8, ""), mk(jnp.bfloat16, "[bf16-kv]")
    rep = check_kv_static_gap([int8, bf16])
    assert rep.errors() == [] and rep.by_rule("QL405"), rep.pretty(True)
    # inverse world: the "int8" cache grew past bf16 — must error
    fat = mk(jnp.float32, "")
    assert check_kv_static_gap([fat, bf16]).exit_code() == 1


@pytest.mark.parametrize("bug,rule", [("dead_donation", "QL402"),
                                      ("hbm_blowout", "QL401")])
def test_seeded_memcheck_runs_exit_nonzero(bug, rule):
    from repro.analysis import lint
    rep = lint.run_analysis(jaxpr_only=True, mem=True, seed_bug=bug,
                            log=lambda *a, **k: None)
    assert rep.exit_code() == 1
    assert any(f.rule == rule for f in rep.errors()), rep.pretty(True)


# ------------------------------------------ QL110 inline-ignore staleness
def test_stale_inline_ignore_errors_on_full_run():
    src = ("import jax\n"
           "x = 1  # quantlint: ignore[QL101]\n")
    # partial runs never audit staleness (mirrors the allowlist audit)
    assert ast_rules.lint_source(src, "s.py").by_rule("QL110") == []
    rep = ast_rules.lint_source(src, "s.py", report_stale_ignores=True)
    stale = rep.by_rule("QL110")
    assert len(stale) == 1 and ":2" in stale[0].where, rep.pretty(True)
    assert stale[0].name == "stale-inline-ignore"
    # a suppression that actually fired is not stale
    used = ("import jax\n"
            "f = jax.jit(abs)  # quantlint: ignore[QL101]\n")
    audited = ast_rules.lint_source(used, "s.py", report_stale_ignores=True)
    assert audited.by_rule("QL110") == [] and len(audited) == 0


def test_stale_ignore_scan_skips_docstrings():
    """Docstrings quoting the suppression syntax (this repo documents it in
    three places) are not suppressions — the scan is tokenizer-based."""
    src = ('"""Use `# quantlint: ignore[QL101]` to suppress."""\n'
           "x = 1\n")
    rep = ast_rules.lint_source(src, "s.py", report_stale_ignores=True)
    assert len(rep) == 0, rep.pretty(True)


# ------------------------------------------- roofline dtype accounting
def test_roofline_dtype_bytes_named_error_and_sub_byte():
    from repro.roofline.analysis import UnknownDtypeError, dtype_bytes
    assert dtype_bytes("s4") == 0.5
    assert dtype_bytes("u4") == 0.5
    assert dtype_bytes("int8") == 1    # numpy names map through NP_TO_HLO
    assert dtype_bytes("bf16") == 2
    with pytest.raises(UnknownDtypeError):
        dtype_bytes("float128")  # silent .get(dtype, 4) default is gone


def test_conv_fallback_warns_once_per_site():
    from repro.core import context as qctx
    qt = trace._export_qt((1, 3, 8, 16), 8)
    x = jax.random.normal(jax.random.key(44), (1, 2, 8, 8), jnp.float32)
    ctx = qctx.QuantCtx(mode="deploy", backend="xla")
    site = "test.analysis.conv_warn_once"
    qctx._CONV_FALLBACK_WARNED.discard(site)
    with warnings.catch_warnings(record=True) as w1:
        warnings.simplefilter("always")
        ctx.conv2d(site, x, qt)
    msgs = [str(w.message) for w in w1
            if issubclass(w.category, RuntimeWarning)]
    assert len(msgs) == 1
    assert "(1, 3, 8, 16)" in msgs[0] and "bytes" in msgs[0]
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        ctx.conv2d(site, x, qt)
    assert not [w for w in w2 if issubclass(w.category, RuntimeWarning)]
