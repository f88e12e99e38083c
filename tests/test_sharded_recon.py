"""Data-parallel calibration: sharded reconstruction must be a pure
*placement* change — same RNG stream, same per-step math, same compile
counts as the single-device engine.

The debug-mesh (2x4) tests need 8 devices and are exercised by the
``recon-sharded-smoke`` CI job under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; they skip elsewhere.
The single-device-mesh tests run everywhere (tier-1), pinning the sharded
code path itself — device_put placement, the stream/replicated sharding
constraints inside the scanned step, and the weighted objective — against
the recorded legacy trajectories.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import QuantRecipe
from repro.core import reconstruct as rec
from repro.core.context import QuantCtx
from repro.core.reconstruct import quantize_blocks, reconstruct_block
from repro.launch.mesh import dp_axes, make_debug_mesh, make_flat_mesh
from repro.launch.sharding import stream_spec

from test_recon_engine import (FIXTURE, assert_matches_fixture, make_block,
                               make_chain)

RTOL = 2e-3


def _single_device_mesh():
    return make_flat_mesh(1)


def _debug_mesh_or_skip():
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices (run under XLA_FLAGS="
                    "--xla_force_host_platform_device_count=8)")
    return make_debug_mesh()


@pytest.fixture(scope="module")
def recorded():
    return dict(np.load(FIXTURE))


W4A8 = QuantRecipe(method="flexround", w_bits=4, w_symmetric=True, a_bits=8,
                   setting="qdrop", iters=50, lr=3e-3, batch_size=8)
W4A8_SHORT = QuantRecipe(method="flexround", w_bits=4, w_symmetric=True,
                         a_bits=8, setting="qdrop", iters=15, lr=3e-3,
                         batch_size=8)
FULLBATCH = QuantRecipe(method="flexround", w_bits=4, w_symmetric=True,
                        a_bits=8, iters=30, lr=3e-3, batch_size=32)

# The data-parallel loss is a psum of per-shard partial sums; the resulting
# ~1e-7 reduction-order drift is amplified *chaotically* once trajectories
# cross STE rounding boundaries (deterministic per platform, but it forks
# the long-horizon path exactly like a jax-version bump does for the
# unsharded fixtures). Parity is therefore asserted in two regimes: exact
# (tight tolerance over a short horizon / the curve prefix, where drift
# cannot yet amplify) and quality (final recon error equivalent).
PREFIX = 12


def _run_single(recipe, block_key, x_key, n, *, mesh=None, sample_weight=None,
                seed=3):
    block = make_block(jax.random.key(block_key), "layers.0")
    x = jax.random.normal(jax.random.key(x_key), (n, 24), jnp.float32)
    y = block.apply(block.params, x, QuantCtx(mode="fp"))
    return reconstruct_block(block, recipe, x, y, jax.random.key(seed),
                             mesh=mesh, sample_weight=sample_weight)


def _check_against_fixture(recorded, tag, recipe, block_key, x_key, n, *,
                           mesh=None, sample_weight=None):
    ws, as_, rep = _run_single(recipe, block_key, x_key, n, mesh=mesh,
                               sample_weight=sample_weight)
    assert_matches_fixture(recorded, f"{tag}/wstates", ws, msg=f"{tag} mesh")
    assert_matches_fixture(recorded, f"{tag}/astates", as_, msg=f"{tag} mesh")
    np.testing.assert_allclose(np.asarray(rep.loss_curve),
                               recorded[f"{tag}/loss_curve"], rtol=RTOL,
                               atol=1e-5, err_msg=f"{tag} mesh: loss")
    np.testing.assert_allclose(np.asarray(rep.mse_curve),
                               recorded[f"{tag}/mse_curve"], rtol=RTOL,
                               atol=1e-5, err_msg=f"{tag} mesh: mse")


# ------------------------------------------------------- always-on coverage
def test_single_device_mesh_matches_recorded(recorded):
    """The sharded code path on a 1x1 mesh is the recorded trajectory."""
    _check_against_fixture(recorded, "block_w4a8_qdrop", W4A8,
                           block_key=7, x_key=8, n=48,
                           mesh=_single_device_mesh())


def test_all_ones_sample_weight_matches_unweighted(recorded):
    """weight=1 everywhere == the plain-mean objective (the straggler
    rescale B/weight.sum() degenerates to 1)."""
    _check_against_fixture(recorded, "block_w4a8_qdrop", W4A8,
                           block_key=7, x_key=8, n=48,
                           sample_weight=jnp.ones((48,), jnp.float32))


def test_zero_weight_samples_do_not_contribute():
    """Full-batch recon with garbage samples at weight 0 must land exactly
    where a run on the clean samples alone lands (bs==n on both sides, so
    the RNG streams coincide)."""
    recipe = QuantRecipe(method="flexround", w_bits=4, w_symmetric=True,
                         a_bits=None, iters=30, lr=3e-3, batch_size=64)
    block = make_block(jax.random.key(7), "layers.0")
    x_clean = jax.random.normal(jax.random.key(8), (16, 24), jnp.float32)
    y_clean = block.apply(block.params, x_clean, QuantCtx(mode="fp"))
    garbage = 100.0 * jax.random.normal(jax.random.key(9), (16, 24))
    x_all = jnp.concatenate([x_clean, garbage])
    y_all = jnp.concatenate([y_clean, jnp.zeros_like(y_clean)])
    w = jnp.concatenate([jnp.ones((16,)), jnp.zeros((16,))])

    ws_clean, _, _ = reconstruct_block(block, recipe, x_clean, y_clean,
                                       jax.random.key(3))
    ws_masked, _, rep = reconstruct_block(block, recipe, x_all, y_all,
                                          jax.random.key(3), sample_weight=w)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        ws_clean, ws_masked)
    assert np.isfinite(np.asarray(rep.loss_curve)).all()


def test_stream_spec_degrades_on_uneven_sample_counts():
    mesh = _single_device_mesh()
    assert stream_spec(5, mesh) == P(("data",))  # dp=1 divides everything
    if jax.device_count() >= 8:
        dmesh = make_debug_mesh()
        assert stream_spec(48, dmesh) == P(("data",))
        assert stream_spec(5, dmesh) == P()  # uneven -> replicated


# ------------------------------------------- Pallas deploy kernels per shard
def _lm_blocks(arch, n=8, seq=16, **cfg_changes):
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models import build_model
    cfg = dataclasses.replace(get_smoke_config(arch), **cfg_changes)
    model = build_model(cfg)
    tokens = jax.random.randint(jax.random.key(1), (n, seq), 0, cfg.vocab)
    return model.quant_blocks(model.init(jax.random.key(0)), tokens)


def _force_pallas(monkeypatch):
    """Deploy matmuls on Pallas kernels (interpreted here), as
    ``resolve_backend`` picks compiled ones on a TPU."""
    import repro.kernels.ops as kops
    monkeypatch.setattr(kops, "resolve_backend",
                        lambda backend="auto", interpret=None: ("pallas",
                                                                True))


def _student(block, recipe, x0, mesh):
    canon = rec._canon_names(block)
    plans = rec.site_plans(block, recipe)
    astates = rec.init_astates(block, recipe, x0)
    qparams = rec.finalize_block(block, recipe,
                                 rec.init_wstates(block, recipe))
    eng = rec._build_engine(block, recipe,
                            {canon[r]: plans[r] for r in block.sites}, canon,
                            mesh)
    a_c = {canon[r]: astates[r] for r in block.sites}
    return eng.student, (qparams, x0, a_c)


def test_pallas_student_runs_per_shard(monkeypatch):
    """On Pallas deploy kernels the data-parallel student runs under
    shard_map (the partitioner cannot split a Pallas kernel) and gives the
    unsharded student's output."""
    _force_pallas(monkeypatch)
    x0, blocks, _ = _lm_blocks("smollm-135m")
    recipe = QuantRecipe(method="flexround", w_bits=4, a_bits=8, iters=1,
                         batch_size=4, rules=("layers.0.wq:w_bits=8",))
    mesh = _single_device_mesh()
    f_sh, args = _student(blocks[0], recipe, x0, mesh)
    f_1, _ = _student(blocks[0], recipe, x0, None)
    assert "shard_map" in str(jax.make_jaxpr(f_sh)(*args))
    assert "shard_map" not in str(jax.make_jaxpr(f_1)(*args))
    np.testing.assert_allclose(np.asarray(f_sh(*args)),
                               np.asarray(f_1(*args)), rtol=1e-6, atol=1e-6)


def test_pallas_student_refuses_expert_blocks(monkeypatch):
    """Per-shard MoE routing would group tokens differently from the
    unsharded run, so the Pallas per-shard student refuses expert blocks."""
    _force_pallas(monkeypatch)
    x0, blocks, _ = _lm_blocks("llama4-scout-17b-a16e")
    block = blocks[-1]
    assert any(s.batch_dims for s in block.sites.values())
    recipe = QuantRecipe(method="flexround", w_bits=4, iters=1, batch_size=4)
    f, args = _student(block, recipe, x0, _single_device_mesh())
    with pytest.raises(NotImplementedError, match="expert block"):
        f(*args)


def test_moe_sharded_student_with_small_shards_matches_unsharded():
    """MoE routing groups span samples: with 32 tokens per shard, under the
    64-token moe_group, and capacity drops on, the data-parallel student
    (partitioned by XLA, not run per shard) routes as the unsharded one."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)")
    x0, blocks, _ = _lm_blocks("llama4-scout-17b-a16e", capacity_factor=1.0)
    assert x0.shape[0] * x0.shape[1] // 4 < 64
    recipe = QuantRecipe(method="flexround", w_bits=4, a_bits=8, iters=1,
                         batch_size=4)
    mesh = make_flat_mesh(4)
    f_sh, (qp, x, a_c) = _student(blocks[-1], recipe, x0, mesh)
    f_1, _ = _student(blocks[-1], recipe, x0, None)
    from repro.launch.sharding import replicated, stream_sharding
    x_sh = jax.device_put(x, stream_sharding(mesh, x.shape[0]))
    qp, a_c = jax.device_put((qp, a_c), replicated(mesh))
    np.testing.assert_allclose(np.asarray(f_sh(qp, x_sh, a_c)),
                               np.asarray(f_1(qp, x, a_c)), rtol=1e-5,
                               atol=1e-5)


# ------------------------------------------------------ debug-mesh (8 dev)
def test_debug_mesh_matches_recorded_block_exact(recorded):
    """Short horizon: the sharded run must reproduce the recorded states and
    full trajectories at the tight tolerance (same RNG, same schedule, same
    step math — sharding is purely a placement change here)."""
    _check_against_fixture(recorded, "block_w4a8_qdrop_short", W4A8_SHORT,
                           block_key=7, x_key=8, n=48,
                           mesh=_debug_mesh_or_skip())


def test_debug_mesh_long_run_quality_parity(recorded):
    """Full 50-step run: trajectory prefix exact, end state equivalent in
    quality (chaotic reduction-order amplification forks the late path; the
    recon error it lands on must not degrade)."""
    mesh = _debug_mesh_or_skip()
    _, _, rep = _run_single(W4A8, block_key=7, x_key=8, n=48, mesh=mesh)
    ref = recorded["block_w4a8_qdrop/loss_curve"]
    np.testing.assert_allclose(np.asarray(rep.loss_curve)[:PREFIX],
                               ref[:PREFIX], rtol=RTOL, atol=1e-5,
                               err_msg="sharded loss prefix")
    err_ref = recorded["block_w4a8_qdrop/err"][1]
    np.testing.assert_allclose(rep.err_after, err_ref, rtol=0.05,
                               err_msg="sharded err_after")
    assert np.isfinite(np.asarray(rep.loss_curve)).all()


def test_debug_mesh_matches_recorded_full_batch(recorded):
    """bs == n skips the gather: the full calibration tensors feed the step
    directly, so the whole objective reduces over the sharded axis. Prefix
    exact + quality at the end."""
    mesh = _debug_mesh_or_skip()
    _, _, rep = _run_single(FULLBATCH, block_key=11, x_key=12, n=32,
                            mesh=mesh)
    ref = recorded["full_batch/loss_curve"]
    np.testing.assert_allclose(np.asarray(rep.loss_curve)[:PREFIX],
                               ref[:PREFIX], rtol=RTOL, atol=1e-5,
                               err_msg="full-batch sharded loss prefix")
    np.testing.assert_allclose(rep.err_after, recorded["full_batch/err"][1],
                               rtol=0.05, err_msg="full-batch err_after")


def test_debug_mesh_streams_actually_sharded():
    """The point of the PR: calibration tensors must land distributed over
    the data axes, not replicated."""
    mesh = _debug_mesh_or_skip()
    from repro.launch.sharding import stream_sharding
    x = jax.device_put(jnp.zeros((48, 24)), stream_sharding(mesh, 48))
    assert not x.sharding.is_fully_replicated
    n_dp = np.prod([mesh.shape[a] for a in dp_axes(mesh)])
    assert x.addressable_shards[0].data.shape[0] == 48 // n_dp


def test_debug_mesh_compile_counts_flat_vs_unsharded(recorded):
    """Sharding must not break the compile-once cache: a 4-block chain under
    the debug mesh compiles exactly as many programs as unsharded, and the
    finalized params agree."""
    mesh = _debug_mesh_or_skip()
    # short horizon keeps the whole chain in the exact regime (finalize
    # turns any state drift into whole-grid-step code flips that the
    # advanced student stream then amplifies — see the PREFIX note); chunk <
    # iters still exercises multi-chunk carry donation
    recipe = QuantRecipe(method="flexround", w_bits=4, w_symmetric=True,
                         a_bits=8, iters=6, lr=3e-3, batch_size=8)
    x = jax.random.normal(jax.random.key(4), (32, 24), jnp.float32)

    counts, outs = {}, {}
    for tag, m in (("unsharded", None), ("sharded", mesh)):
        rec.reset_engine_stats()
        rec.clear_engine_cache()
        fin, _, _ = quantize_blocks(make_chain(4, token=(object(),)), recipe,
                                    x, chunk=3, as_qtensor=False, mesh=m)
        counts[tag] = _compile_counts()
        outs[tag] = fin
    assert counts["sharded"] == counts["unsharded"], counts
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=RTOL, atol=1e-5),
        outs["unsharded"], outs["sharded"])


def _compile_counts():
    st = rec.engine_stats()
    return {"step": st.step_compiles, "teacher": st.teacher_compiles,
            "student": st.student_compiles,
            "recon_err": st.recon_error_compiles,
            "schedule": st.schedule_compiles, "total": st.compile_count}


def test_debug_mesh_probe_stays_compile_flat():
    """The allocator probe rides the same engine under a mesh: compiles
    O(distinct apply_keys x bits), identical to the unsharded pass."""
    mesh = _debug_mesh_or_skip()
    from repro.allocate import probe_blocks
    recipe = QuantRecipe(method="flexround", w_bits=4, w_symmetric=True,
                         a_bits=None, iters=1, batch_size=8)
    x = jax.random.normal(jax.random.key(5), (32, 24), jnp.float32)

    compiles, scores = {}, {}
    for tag, m in (("unsharded", None), ("sharded", mesh)):
        rec.reset_engine_stats()
        rec.clear_engine_cache()
        probe = probe_blocks(make_chain(3, token=(object(),)), recipe, x,
                             bits=(4, 8), mesh=m)
        compiles[tag] = probe.compile_count
        scores[tag] = probe
    assert compiles["sharded"] == compiles["unsharded"], compiles
    for site, per in scores["unsharded"].scores.items():
        for b, s in per.items():
            np.testing.assert_allclose(
                scores["sharded"].scores[site][b].mse, s.mse, rtol=RTOL,
                atol=1e-7, err_msg=f"{site}@{b}")
