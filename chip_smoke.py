"""Run the main path once on one TPU chip and check what comes out.

    python chip_smoke.py [--seed N]            # one chip
    python chip_smoke.py --chips 4 [--seed N]  # data-parallel PTQ on four

One process, no child processes. With no arguments it builds smollm-135m at
its published widths (30 layers, d_model 576, vocab 49152) with weights from
``--seed``, runs FlexRound PTQ over all 30 blocks through ``quantize_blocks``
(W4A8 body, layer 0 at W8A8, so both kernel families serve), exports
QTensors, serves 8 seeded requests through ``ServeEngine`` + ``Scheduler``
on compiled Pallas kernels with an int8 KV cache, and compares the kernel
path's prefill logits with the pure-jnp reference path on the same chip.
``--chips 4`` runs only the data-parallel reconstruction over a (4, 1) mesh
and compares it with the same PTQ on one chip in this process.

Any failed check exits non-zero. The last line of a passing run is
``{"ok": true, "device": {...}}`` and nothing else. Times printed on the way
are information about this run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-135m"
#: kernel-vs-reference bound for one matmul on float32 input, as a fraction
#: of the reference's largest magnitude: at the compiler's default precision
#: both round their operands to bf16 alike and accumulate in f32, so they
#: differ by summation order only (2.2e-7 measured on a v5e). This is the
#: check that a kernel computes the right thing.
KERNEL_REL_TOL = 1e-4
#: the same bound on last-position prefill logits. The comparison runs on
#: float32 activations (the bf16 float params cast up, which is exact): in
#: bf16 the two programs round at different points, and with int8
#: activations any rounding difference moves activation codes by a grid
#: step, which later layers compound up to the quantization noise itself
#: (PERF.md). So the weight-only path is compared over all layers, and the
#: path with activation states over its first A8_DEPTH layers: the integer
#: kernel of layer 0 and the W4A8 path of layer 1.
LOGIT_REL_TOL = 1e-2
A8_DEPTH = 2
#: four-chip parity, as tests/test_sharded_recon.py: the loss-curve prefix
#: of the first block is exact up to reduction order, per-block err_after
#: agrees in quality
PREFIX, PREFIX_RTOL, PREFIX_ATOL, ERR_RTOL = 12, 2e-3, 1e-5, 0.05
KERNELS_REQUIRED = ("dequant_matmul_w4", "qmatmul_int8")


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


@dataclasses.dataclass(frozen=True)
class Sizes:
    calib: int = 32          # calibration sequences (divisible by 4)
    seq: int = 128           # tokens per calibration sequence
    iters: int = 20          # FlexRound steps per block
    batch: int = 16          # recon minibatch (divisible by 4)
    slots: int = 4
    requests: int = 8
    max_new: int = 16
    prompt_min: int = 16
    prompt_max: int = 96
    max_len: int = 128
    min_bucket: int = 16


CHIP = Sizes()


def _check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def check_device(chips: int) -> dict:
    """The run's device, as jax reports it; fails unless jax found TPUs."""
    import jax
    devs = jax.devices()
    found = devs[0].platform
    if found != "tpu":
        raise SmokeFailure(f"no TPU: jax found {len(devs)} {found} device(s) "
                           f"({devs[0].device_kind}); this smoke never "
                           "falls back to another platform")
    if len(devs) < chips:
        raise SmokeFailure(f"--chips {chips} needs {chips} TPU devices, "
                           f"jax found {len(devs)}")
    dev = {"platform": found, "kind": devs[0].device_kind, "count": len(devs)}
    print(f"device: {dev['kind']} x{dev['count']} ({found})", flush=True)
    return dev


def recipe(sizes: Sizes = CHIP):
    """W4A8 body, layer 0 at W8A8: the serve path reaches both the
    dequant-matmul and the integer-matmul kernels."""
    from repro.core import QuantRecipe
    return QuantRecipe(method="flexround", w_bits=4, a_bits=8,
                       iters=sizes.iters, batch_size=sizes.batch,
                       rules=("layers.0.*:w_bits=8",))


@dataclasses.dataclass
class Quantized:
    """What PTQ hands to serving, plus what the checks read."""
    model: object
    qparams: dict
    astates: dict
    recipe: object
    reports: list
    sites: list  # per block: canonical site name -> Site

    def ctx(self, backend: str, interpret=None, astates=None):
        from repro.core.context import QuantCtx
        return QuantCtx(mode="deploy", recipe=self.recipe,
                        astates=self.astates if astates is None else astates,
                        backend=backend, interpret=interpret)


def quantize(cfg, seed: int, sizes: Sizes = CHIP, mesh=None) -> Quantized:
    """Build ``cfg`` from ``seed`` and run FlexRound PTQ over every block."""
    import jax

    from repro.core.reconstruct import (engine_stats, quantize_blocks,
                                        reset_engine_stats)
    from repro.data import CalibrationSet, SyntheticTokens
    from repro.models import build_model
    from repro.obs.telemetry import Stopwatch

    model = build_model(cfg)
    params = model.init(jax.random.key(seed))
    rcp = recipe(sizes)
    cal = CalibrationSet.build(
        SyntheticTokens(vocab=cfg.vocab, seq_len=sizes.seq, seed=seed),
        sizes.calib)
    x0, blocks, assemble = model.quant_blocks(params, cal.tokens)
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} dtype={cfg.dtype}; PTQ {len(blocks)} blocks, "
          f"calib {sizes.calib}x{sizes.seq}, {sizes.iters} iters/block, "
          f"mesh={None if mesh is None else dict(mesh.shape)}", flush=True)
    reset_engine_stats()
    sw = Stopwatch()
    finalized, astates, reports = quantize_blocks(blocks, rcp, x0, mesh=mesh)
    qparams = assemble(finalized)
    jax.block_until_ready(qparams)
    wall = sw.elapsed_s()
    before = [r.err_before for r in reports]
    after = [r.err_after for r in reports]
    _check(np.isfinite(before).all() and np.isfinite(after).all(),
           f"non-finite recon error: before={before} after={after}")
    st = engine_stats()
    steps = sum(r.iters for r in reports)
    loop_s = sum(r.iters / r.steps_per_s for r in reports if r.steps_per_s)
    print(f"ptq: recon err {sum(before):.4e} -> {sum(after):.4e} over "
          f"{len(reports)} blocks; {steps} steps, "
          f"{steps / max(loop_s, 1e-9):.1f} steps/s in the loop, {wall:.1f}s "
          f"wall incl. compile; engine compiles step={st.step_compiles} "
          f"teacher={st.teacher_compiles} student={st.student_compiles} "
          f"recon_err={st.recon_error_compiles} "
          f"schedule={st.schedule_compiles} (total {st.compile_count})",
          flush=True)
    return Quantized(model, qparams, astates, rcp, reports,
                     [b.sites for b in blocks])


def _requests(cfg, seed: int, sizes: Sizes):
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    tokens=rng.integers(0, cfg.vocab,
                                        size=int(rng.integers(
                                            sizes.prompt_min,
                                            sizes.prompt_max + 1)),
                                        ).astype(np.int32),
                    max_new=sizes.max_new)
            for i in range(sizes.requests)]


def serve(q: Quantized, seed: int, sizes: Sizes = CHIP, *,
          backend: str = "auto", interpret=None):
    """Serve seeded requests through ServeEngine + Scheduler; check every
    request is answered in full and both kernel families were dispatched.
    Returns the requests."""
    from repro.analysis.diffcheck import record_pallas
    from repro.obs.telemetry import Stopwatch
    from repro.serve import EngineConfig, Scheduler, ServeEngine

    cfg = q.model.cfg
    ecfg = EngineConfig(slots=sizes.slots, max_len=sizes.max_len,
                        prefill_group=2, kv_quant=True,
                        min_bucket=sizes.min_bucket)
    hits = []
    sw = Stopwatch()
    with record_pallas(hits):
        engine = ServeEngine(q.model, q.qparams, q.ctx(backend, interpret),
                             ecfg)
    build_s = sw.elapsed_s()
    dispatched = sorted(set(hits))
    print(f"serve: engine built in {build_s:.1f}s, compile_count "
          f"{engine.compile_count} (buckets {engine.buckets}); Pallas "
          f"kernels dispatched: {dispatched} ({len(hits)} call sites)",
          flush=True)
    missing = [k for k in KERNELS_REQUIRED if k not in dispatched]
    _check(not missing, f"kernels never dispatched: {missing}")
    if not interpret:
        hlo = engine._decode_exec.as_text()
        _check("tpu_custom_call" in hlo,
               "decode program holds no compiled Pallas kernel "
               "(no tpu_custom_call in its HLO)")

    reqs = _requests(cfg, seed, sizes)
    sw = Stopwatch()
    with Scheduler(engine) as sched:
        outs = sched.run(reqs)
        st = sched.stats()
    dt = sw.elapsed_s()
    for r in reqs:
        got = outs.get(r.rid, [])
        _check(len(got) == r.max_new,
               f"request {r.rid} returned {len(got)}/{r.max_new} tokens")
        _check(all(0 <= t < cfg.vocab for t in got),
               f"request {r.rid} returned ids outside [0, {cfg.vocab}): "
               f"{got}")
    n_tok = sum(len(v) for v in outs.values())
    print(f"serve: {len(reqs)} requests (prompts "
          f"{sorted(len(r.tokens) for r in reqs)}) answered in full, "
          f"{n_tok} tokens in {dt:.2f}s ({n_tok / dt:.1f} tokens/s), "
          f"{st['decode_steps']} decode steps, compile_count still "
          f"{st['compile_count']}", flush=True)
    return reqs


def kernel_parity(q: Quantized, *, backend: str = "auto",
                  interpret=None) -> float:
    """Every site of layers 0 (W8A8, integer kernel) and 1 (W4A8, dequant
    kernel) through the kernel path and the reference path on the same
    float32 input, at decode and prefill row counts. Returns the largest
    error relative to the reference's magnitude; fails past KERNEL_REL_TOL.
    """
    import jax

    layers = q.qparams["layers"]
    _check(isinstance(layers, list), "mixed-precision layers did not "
                                     "restack to a per-layer list")
    kctx, rctx = q.ctx(backend, interpret), q.ctx("xla")
    worst = 0.0
    for li in (0, 1):
        for name, site in q.sites[li].items():
            qt = layers[li]
            for k in site.path:
                qt = qt[k]
            for m in (CHIP.slots, 2 * CHIP.max_len):
                x = jax.random.normal(jax.random.key(m), (m, qt.shape[0]))
                kern = np.asarray(kctx.linear(name, x, qt))
                ref = np.asarray(rctx.linear(name, x, qt))
                rel = float(np.max(np.abs(kern - ref)) /
                            np.max(np.abs(ref)))
                _check(np.isfinite(kern).all() and rel <= KERNEL_REL_TOL,
                       f"{name} ({qt.bits}-bit, M={m}): kernel off the "
                       f"reference by {rel:.3e} of its scale")
                worst = max(worst, rel)
    print(f"kernels: layers 0-1, {len(q.sites[0]) + len(q.sites[1])} sites "
          f"x M in (4, 256): kernel vs reference max error {worst:.3e} of "
          f"scale (bound {KERNEL_REL_TOL:g})", flush=True)
    return worst


def _f32_activations(params):
    """``params`` with every float leaf in float32 (exact for bf16) and the
    QTensors as they are, so activations run in float32 over the same
    quantized weights."""
    import jax
    import jax.numpy as jnp

    from repro.core.qtensor import QTensor

    def up(a):
        if isinstance(a, QTensor) or not jnp.issubdtype(a.dtype,
                                                        jnp.floating):
            return a
        return a.astype(jnp.float32)
    return jax.tree.map(up, params, is_leaf=lambda a: isinstance(a, QTensor))


def compare_reference(q: Quantized, reqs, *, backend: str = "auto",
                      interpret=None) -> dict:
    """Last-position prefill logits of every request: kernel path against
    the pure-jnp reference path (``backend="xla"``), same quantized params,
    same device, float32 activations. Weight-only over all layers, and with
    the activation states over the first A8_DEPTH layers. Fails past
    LOGIT_REL_TOL of the reference's scale."""
    import jax
    import jax.numpy as jnp

    model, cfg = q.model, q.model.cfg
    n = max(len(r.tokens) for r in reqs)
    tokens = np.zeros((len(reqs), n), np.int32)
    for i, r in enumerate(reqs):
        tokens[i, :len(r.tokens)] = r.tokens
    true_len = np.array([len(r.tokens) for r in reqs], np.int32)

    def logits_for(ctx, params):
        def f(params, toks, tl):
            cache = model.init_cache(toks.shape[0], toks.shape[1],
                                     kv_quant=True)
            last, _ = model.prefill(params, toks, cache, ctx, true_len=tl)
            out = last[:, -1] @ model.lm_head(params).astype(last.dtype)
            return (out * cfg.logit_mult).astype(jnp.float32)
        return np.asarray(jax.jit(f)(params, tokens, true_len))

    p32 = _f32_activations(q.qparams)
    res = {}
    for tag, astates, params in (
            ("weight-only", {}, p32),
            ("A8", q.astates, dict(p32, layers=p32["layers"][:A8_DEPTH]))):
        kern = logits_for(q.ctx(backend, interpret, astates), params)
        ref = logits_for(q.ctx("xla", astates=astates), params)
        _check(np.isfinite(kern).all() and np.isfinite(ref).all(),
               f"non-finite prefill logits ({tag})")
        scale = float(np.max(np.abs(ref)))
        err = float(np.max(np.abs(kern - ref)))
        agree = float(np.mean(kern.argmax(-1) == ref.argmax(-1)))
        rel_l2 = float(np.linalg.norm(kern - ref) / np.linalg.norm(ref))
        print(f"reference: {tag}, {len(params['layers'])} layers, float32 "
              f"activations: kernel vs xla last-position prefill logits "
              f"over {len(reqs)} requests: max|diff| {err:.4e} = "
              f"{err / scale:.3e} of scale {scale:.4e} (bound "
              f"{LOGIT_REL_TOL:g}), relative L2 {rel_l2:.3e}, greedy-token "
              f"agreement {agree:.3f}", flush=True)
        _check(err <= LOGIT_REL_TOL * scale,
               f"{tag}: kernel path off the reference by {err:.4e} > "
               f"{LOGIT_REL_TOL * scale:.4e}")
        res[tag] = {"max_abs_err": err, "bound": LOGIT_REL_TOL * scale,
                    "agreement": agree, "rel_l2": rel_l2}
    return res


class _SpanRecords:
    """Telemetry sink that keeps the records in memory."""

    def __init__(self):
        self.records = []

    def emit(self, record: dict) -> None:
        self.records.append(record)

    def close(self) -> None:
        pass


def data_parallel_parity(cfg, seed: int, n_devices: int,
                         sizes: Sizes = CHIP):
    """The same PTQ over a (n_devices, 1) data-parallel mesh and on one
    device; fails if the calibration stream was not sharded or the two
    runs disagree beyond the sharded-recon parity bounds."""
    from repro.launch.mesh import make_flat_mesh
    from repro.obs.telemetry import TELEMETRY

    _check(sizes.calib % n_devices == 0 and sizes.batch % n_devices == 0,
           f"calib {sizes.calib} / batch {sizes.batch} do not divide over "
           f"{n_devices} devices")
    mesh = make_flat_mesh(n_devices)
    spans = _SpanRecords()
    with TELEMETRY.enabled_scope(sink=spans):
        sharded = quantize(cfg, seed, sizes, mesh=mesh).reports
    # the sharding of the stream each recon chunk was handed, as the engine
    # placed it (the recon.chunk span records it)
    streams = sorted({r["attrs"]["stream"] for r in spans.records
                      if r.get("name") == "recon.chunk"})
    print(f"dp: mesh {dict(mesh.shape)}, calibration stream sharding as "
          f"the recon engine received it: {streams}", flush=True)
    _check(streams and all("data" in s for s in streams),
           f"calibration stream not sharded over 'data': {streams}")
    single = quantize(cfg, seed, sizes, mesh=None).reports

    p_sh = np.asarray(sharded[0].loss_curve)[:PREFIX]
    p_1 = np.asarray(single[0].loss_curve)[:PREFIX]
    prefix_dev = float(np.max(np.abs(p_sh - p_1) /
                              (PREFIX_ATOL + np.abs(p_1))))
    err_sh = np.array([r.err_after for r in sharded])
    err_1 = np.array([r.err_after for r in single])
    err_dev = np.abs(err_sh - err_1) / np.abs(err_1)
    print(f"dp: block-0 loss prefix ({PREFIX} steps) max rel dev "
          f"{prefix_dev:.3e}; err_after rel dev max {err_dev.max():.3e} "
          f"(block {int(err_dev.argmax())}), median "
          f"{float(np.median(err_dev)):.3e} over {len(err_dev)} blocks",
          flush=True)
    np.testing.assert_allclose(p_sh, p_1, rtol=PREFIX_RTOL, atol=PREFIX_ATOL,
                               err_msg="sharded block-0 loss prefix")
    np.testing.assert_allclose(err_sh, err_1, rtol=ERR_RTOL,
                               err_msg="sharded per-block err_after")
    return {"prefix_dev": prefix_dev, "err_dev_max": float(err_dev.max())}


def _cache_counter():
    """Count persistent-compilation-cache hits and misses from here on."""
    from jax import monitoring
    counts = {"hits": 0, "misses": 0}

    def on_event(event: str, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    monitoring.register_event_listener(on_event)
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for weights, calibration data and requests")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the data-parallel PTQ parity phase")
    args = ap.parse_args(argv)
    try:
        dev = check_device(args.chips)
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
        from repro.obs.telemetry import Stopwatch

        cache_dir = enable_compile_cache()
        cache = _cache_counter()
        sw = Stopwatch()
        cfg = get_config(ARCH)
        if args.chips == 4:
            data_parallel_parity(cfg, args.seed, 4)
        else:
            from repro.kernels.ops import resolve_backend
            resolved = resolve_backend("auto")
            print(f"kernels: backend auto resolves to {resolved}", flush=True)
            _check(resolved == ("pallas", False),
                   f"auto backend resolved to {resolved}, not compiled "
                   "Pallas")
            q = quantize(cfg, args.seed)
            reqs = serve(q, args.seed)
            kernel_parity(q)
            compare_reference(q, reqs)
        print(f"compile cache: {cache_dir} hits={cache['hits']} "
              f"misses={cache['misses']}; total {sw.elapsed_s():.1f}s",
              flush=True)
    except (SmokeFailure, AssertionError) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
