"""Production PTQ launcher: load a trained checkpoint, run block-wise
FlexRound (or any registered method), export integer weights.

    PYTHONPATH=src python -m repro.launch.quantize --arch smollm-135m \
        --smoke --method flexround --w-bits 8 --a-bits 8

Mixed precision via per-site rules (glob over site names, last match wins):

    ... --w-bits 4 --rule 'layers.0.*:w_bits=8' --rule 'layers.11.*:w_bits=8'

gives the standard LLM recipe (W4 body, W8 first/last layers); rules may also
override method, granularity, lr, or a_bits per site (``a_bits=none`` keeps a
site's activations fp).

Automatic mixed precision (sensitivity-guided, repro.allocate):

    ... --auto-bits 4.5                   # numel-weighted average bits
    ... --auto-bits 150000 --budget bytes # serving-bytes budget

probes every site at candidate bit-widths on the calibration set, solves the
budget and appends the emitted per-site rules to the recipe — probe, solve
and quantize in one invocation. The allocation is persisted to --resume-dir
(allocation.json) and stamped into every per-block checkpoint, so a resume
under a different allocation fails loudly.

Distributed calibration (--mesh): reconstruction runs data-parallel over the
mesh — the calibration set is built per-host from the deterministic
``SyntheticTokens.batch(step, host, n_hosts)`` shards (one simulated host per
data-parallel slice), assembled under the straggler policy, and its loss
weight is consumed by the recon objective; calibration/activation streams are
sharded over the mesh's data axes on the leading sample axis while rounding/
Adam/LSQ states stay replicated. ``--mesh debug`` needs 8 devices (run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` on CPU);
``--mesh production`` expects the 16x16 pod of launch/mesh.py.

Fault tolerance: per-block PTQ checkpoints (--resume-dir) — a preempted run
resumes at the first unfinished block with identical RNG; resuming under
different rules fails loudly (per-site plans are recorded in the checkpoint).
"""
from __future__ import annotations

import argparse
import os

import jax

from repro.checkpoint import CheckpointManager, save_pytree
from repro.configs import get_config, get_smoke_config
from repro.core import QuantRecipe, method_api
from repro.core.reconstruct import (DEFAULT_CHUNK, engine_stats,
                                    quantize_blocks, reset_engine_stats,
                                    site_plans)
from repro.data import CalibrationSet, SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (axis_size, dp_axes, make_debug_mesh,
                               make_production_mesh)
from repro.models import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--method", default="flexround",
                    choices=list(method_api.available_methods()))
    ap.add_argument("--setting", default="qdrop", choices=["brecq", "qdrop"])
    ap.add_argument("--recon", default="block", choices=["block", "layer"])
    ap.add_argument("--w-bits", type=int, default=8)
    ap.add_argument("--a-bits", type=int, default=None)
    ap.add_argument("--w-granularity", default="per_channel")
    ap.add_argument("--rule", action="append", default=[],
                    metavar="GLOB:K=V[,K=V...]",
                    help="per-site override, e.g. 'layers.0.*:w_bits=8'; "
                         "repeatable, later rules win")
    ap.add_argument("--calib", type=int, default=64)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--from-ckpt", default=None,
                    help="CheckpointManager dir of a trained model")
    ap.add_argument("--resume-dir", default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "pallas", "xla"],
                    help="deploy-mode kernel dispatch: auto = compiled "
                         "Pallas on TPU / XLA ref path elsewhere; pallas = "
                         "Pallas kernels (interpreted off-TPU); xla = "
                         "pure-jnp refs")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="after quantization, run a short deploy-mode decode "
                         "through the kernel serving path and report "
                         "us/step + weight bytes moved")
    ap.add_argument("--serve", action="store_true",
                    help="after quantization, run the continuous-batching "
                         "serve engine (bucketed AOT prefill, slot decode, "
                         "int8 KV) on a synthetic request stream and report "
                         "tokens/s, HBM/slot, compile_count")
    ap.add_argument("--serve-slots", type=int, default=4,
                    help="decode slots for --serve")
    ap.add_argument("--serve-requests", type=int, default=8,
                    help="synthetic request count for --serve")
    ap.add_argument("--serve-max-new", type=int, default=16,
                    help="tokens generated per request for --serve")
    ap.add_argument("--no-kv-quant", action="store_true",
                    help="serve with the fp KV cache instead of the int8 "
                         "default (A/B the HBM-per-slot win)")
    ap.add_argument("--analyze", action="store_true",
                    help="after quantization, run the quantlint analyzers "
                         "(repro.analysis): AST rules over src/, jaxpr "
                         "checks on the entry points, kernel-coverage "
                         "report; exit non-zero on error findings")
    ap.add_argument("--analyze-mem", action="store_true",
                    help="like --analyze, plus the memcheck layer (QL4xx): "
                         "jaxpr liveness vs the per-entry HBM-budget "
                         "contracts over the serve/deploy entries")
    ap.add_argument("--auto-bits", type=float, default=None, metavar="VALUE",
                    help="automatic mixed precision: probe per-site "
                         "sensitivity and allocate bit-widths to meet this "
                         "budget (interpreted per --budget); emitted rules "
                         "are appended to the recipe")
    ap.add_argument("--budget", default="avg_bits",
                    choices=["avg_bits", "bytes"],
                    help="meaning of --auto-bits: numel-weighted average "
                         "bits, or total serving bytes (packed codes + "
                         "affine grid)")
    ap.add_argument("--alloc-objective", default="combined",
                    choices=["mse", "fisher", "combined"],
                    help="sensitivity metric the allocator minimizes")
    ap.add_argument("--scan-chunk", type=int, default=DEFAULT_CHUNK,
                    help="optimization steps fused per device dispatch in "
                         "the scanned engine")
    ap.add_argument("--mesh", default=None, choices=["debug", "production"],
                    help="run reconstruction data-parallel over a device "
                         "mesh: calibration built per-host "
                         "(SyntheticTokens.batch shards + straggler loss "
                         "weight), streams sharded over the data axes, "
                         "states replicated. debug = 2x4 (8 devices, force "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=8); production = 16x16")
    ap.add_argument("--multi-pod", action="store_true",
                    help="with --mesh: add the pod axis (pod, data, model)")
    ap.add_argument("--telemetry", default=None, metavar="DIR",
                    help="enable the telemetry layer (repro.obs): spans/"
                         "counters/histograms stream to DIR/events.jsonl "
                         "as manifest-stamped JSONL")
    ap.add_argument("--profile", action="store_true",
                    help="wrap the run in jax.profiler.trace, emitting a "
                         "perfetto-loadable trace dir under --telemetry DIR "
                         "(or /tmp/repro_profile); telemetry spans are on "
                         "while it runs, so they land in the trace")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    if args.from_ckpt:
        state, _ = CheckpointManager(args.from_ckpt).restore()
        params = state["params"]
    else:
        print("no --from-ckpt: quantizing randomly-initialized weights "
              "(structure demo)")
        params = model.init(jax.random.key(0))

    recipe = QuantRecipe(method=args.method, setting=args.setting,
                         recon=args.recon, w_bits=args.w_bits,
                         w_granularity=args.w_granularity,
                         a_bits=args.a_bits, iters=args.iters, lr=args.lr,
                         batch_size=min(16, args.calib),
                         rules=tuple(args.rule))
    src = SyntheticTokens(vocab=cfg.vocab, seq_len=args.seq, seed=0)
    mesh, sample_weight = None, None
    if args.mesh is not None:
        mesh = build_mesh(args.mesh, multi_pod=args.multi_pod)
        cal, sample_weight = build_sharded_calibration(src, args.calib, mesh)
    else:
        cal = CalibrationSet.build(src, args.calib)

    from repro.obs.sink import JsonlSink, RunManifest
    from repro.obs.telemetry import TELEMETRY
    if args.telemetry:
        manifest = RunManifest.collect(backend=args.backend, mesh=args.mesh,
                                       recipe=recipe)
        events = os.path.join(args.telemetry, "events.jsonl")
        TELEMETRY.enable(sink=JsonlSink(events), manifest=manifest)
        print(f"telemetry: streaming to {events} "
              f"(git {manifest.git_sha}, schema {manifest.schema_version})")
    if args.profile:
        from repro.obs import profiler
        if not TELEMETRY.enabled:
            TELEMETRY.enable()  # spans without a sink: the trace holds them
        profiler.start(os.path.join(args.telemetry or "/tmp/repro_profile",
                                    "profile"))

    x0, blocks, assemble = model.quant_blocks(params, cal.tokens)

    reset_engine_stats()
    alloc_meta = None
    if args.auto_bits is not None:
        recipe, alloc_meta = apply_auto_bits(
            blocks, recipe, x0, value=args.auto_bits, budget=args.budget,
            objective=args.alloc_objective, resume_dir=args.resume_dir,
            mesh=mesh)
        if alloc_meta:
            TELEMETRY.emit({"kind": "allocation",
                            "digest": str(alloc_meta.get("digest", "")),
                            "name": alloc_meta.get("name")})

    if recipe.rules:
        overridden = [(n, p.summary()) for b in blocks
                      for n, p in site_plans(b, recipe).items()
                      if recipe.overrides_for(n)]
        print(f"rules override {len(overridden)} site(s):")
        for n, s in overridden:
            print(f"  {n}: {s}")
    finalized, astates, reports = quantize_blocks(
        blocks, recipe, x0, checkpoint_dir=args.resume_dir,
        progress=lambda s: print(s, flush=True),
        chunk=args.scan_chunk, allocation=alloc_meta,
        mesh=mesh, sample_weight=sample_weight)
    qparams = assemble(finalized)

    stats = engine_stats()
    # blocks replayed from a resume checkpoint carry no loop timing
    # (steps_per_s=0.0): only count units reconstructed by this process
    ran = [r for r in reports if r.steps_per_s > 0]
    steps = sum(r.iters for r in ran)
    loop_s = sum(r.iters / r.steps_per_s for r in ran)
    print(f"recon: {steps} steps over {len(ran)} unit(s) in "
          f"{loop_s:.2f}s ({steps / max(loop_s, 1e-9):.1f} steps/s); "
          f"compiles: step={stats.step_compiles} "
          f"teacher={stats.teacher_compiles} "
          f"student={stats.student_compiles} "
          f"recon_err={stats.recon_error_compiles} "
          f"schedule={stats.schedule_compiles} "
          f"probe={stats.probe_compiles} "
          f"(total {stats.compile_count})", flush=True)

    from repro.obs.sink import current_manifest
    out = args.out or f"/tmp/quantized_{cfg.name}_{args.method}"
    save_pytree(out, {"params": qparams, "astates": astates},
                {"arch": cfg.name, "method": args.method,
                 "w_bits": args.w_bits, "a_bits": args.a_bits,
                 # canonical --rule form so the metadata round-trips
                 "rules": [r.pattern + ":" + ",".join(
                     f"{k}={v}" for k, v in r.overrides)
                     for r in recipe.rules],
                 "manifest": current_manifest().to_dict()})
    tot0 = sum(r.err_before for r in reports)
    tot1 = sum(r.err_after for r in reports)
    print(f"quantized {len(blocks)} blocks: recon err {tot0:.3e} -> "
          f"{tot1:.3e}; saved to {out}")

    if args.serve_smoke:
        serve_smoke(model, qparams, astates, recipe, cfg,
                    backend=args.backend)

    if args.serve:
        serve_engine_run(model, qparams, astates, recipe, cfg,
                         backend=args.backend, slots=args.serve_slots,
                         requests=args.serve_requests,
                         max_new=args.serve_max_new,
                         kv_quant=not args.no_kv_quant)

    if args.profile:
        from repro.obs import profiler
        profiler.stop()
    if TELEMETRY.enabled:
        # final aggregate record, then flush/close the sink
        TELEMETRY.emit({"kind": "snapshot", **TELEMETRY.snapshot()})
        TELEMETRY.disable()

    if args.analyze or args.analyze_mem:
        from repro.analysis.lint import run_analysis
        rep = run_analysis(mem=args.analyze_mem)
        print(rep.pretty())
        if rep.exit_code():
            raise SystemExit("quantlint: error findings (see above)")


def build_mesh(kind: str, *, multi_pod: bool = False):
    """--mesh flag -> jax Mesh, with an actionable error when the process
    does not expose enough devices (the debug mesh is 8 virtual CPU devices
    in both its single- and multi-pod shapes — (2,4) and (2,2,2))."""
    need = 8 if kind == "debug" else (512 if multi_pod else 256)
    have = jax.device_count()
    if have < need:
        raise SystemExit(
            f"--mesh {kind} needs {need} devices but this process sees "
            f"{have}; on CPU run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "(debug mesh) or launch on the real pod")
    if kind == "debug":
        return make_debug_mesh(multi_pod=multi_pod)
    return make_production_mesh(multi_pod=multi_pod)


def build_sharded_calibration(src, n_calib: int, mesh):
    """Per-host calibration for a mesh run: one simulated host per
    data-parallel slice fetches exactly its ``SyntheticTokens.batch`` shard;
    the straggler policy assembles them and its loss weight feeds the recon
    objective. Returns (CalibrationSet, (N,) sample weight)."""
    n_hosts = axis_size(mesh, dp_axes(mesh))
    if n_calib % n_hosts:
        raise SystemExit(
            f"--calib {n_calib} does not divide over the mesh's "
            f"{n_hosts} data-parallel hosts; pick a multiple of {n_hosts}")
    cal, weight = CalibrationSet.build_sharded(src, n_calib, n_hosts)
    print(f"calibration: {n_calib} samples assembled from {n_hosts} "
          f"per-host shards (dp axes {dp_axes(mesh)}, "
          f"weight mass {float(weight.sum()):.0f}/{len(cal)})")
    if float(weight.sum()) == len(cal):
        # no host missed the deadline: the weighted mean would equal the
        # plain mean, but only sample_weight=None keeps the objective on the
        # exact reduction the recorded trajectories (and the sharded parity
        # suite) pin — so drop the all-ones mask
        return cal, None
    return cal, weight


def apply_auto_bits(blocks, recipe, x0, *, value: float, budget: str,
                    objective: str = "combined", resume_dir=None, mesh=None):
    """Probe -> solve -> append emitted rules. Returns (recipe, alloc_meta).

    When ``resume_dir`` holds an ``allocation.json`` from an earlier run the
    recorded allocation is validated against the requested budget and reused
    (no re-probe) so the resumed run quantizes under the identical rules;
    a different budget fails loudly.
    """
    from repro.allocate import AllocationReport, Budget, auto_allocate

    kind = "weight_bytes" if budget == "bytes" else budget
    report = None
    if resume_dir is not None:
        report = AllocationReport.load(resume_dir)
    if report is not None:
        want = {"kind": kind, "value": value}
        if report.budget != want or report.objective != objective:
            raise ValueError(
                f"resume dir {resume_dir} holds allocation "
                f"{report.name!r} for budget {report.budget} / objective "
                f"{report.objective!r} but this run requests {want} / "
                f"{objective!r}; re-run with the original settings or a "
                "fresh checkpoint dir")
        have = {n for b in blocks for n in b.sites}
        stale = sorted(set(report.bits()) - have)
        if stale:
            raise ValueError(
                f"resume dir {resume_dir} holds allocation {report.name!r} "
                f"for sites this model does not have (e.g. {stale[:3]}); "
                "its rules would silently match nothing — re-probe with a "
                "fresh checkpoint dir")
        print(f"reusing recorded allocation from {resume_dir}:")
    else:
        report = auto_allocate(blocks, recipe, x0, Budget(kind, value),
                               objective=objective, mesh=mesh)
        if resume_dir is not None:
            report.save(resume_dir)
    print(report.pretty(), flush=True)
    return recipe.with_rules(*report.rules()), report.meta()


def serve_smoke(model, qparams, astates, recipe, cfg, *, backend: str = "auto",
                batch: int = 2, prompt_len: int = 16, steps: int = 8) -> float:
    """Short deploy-mode decode through the kernel serving path.

    Prefills a tiny batch and times ``steps`` greedy decode steps with the
    quantized weights dispatched through ``kernels/ops.qtensor_matmul`` under
    the requested backend. Returns us/step (also printed, with the effective
    weight bytes each step moves)."""
    import jax.numpy as jnp

    from repro.core.context import QuantCtx
    from repro.core.qtensor import tree_weight_bytes

    from repro.serve.smoke import serve_capability

    ok, reason = serve_capability(model)
    if not ok:
        # the user asked for serving: a skip is a failure of this run (the
        # reason string is the machine-readable one the bench rows carry)
        raise SystemExit(f"serve-smoke: skipped arch={cfg.name} "
                         f"reason={reason}")
    ctx = QuantCtx(mode="deploy", recipe=recipe, astates=astates,
                   backend=backend)
    tokens = jax.random.randint(jax.random.key(0), (batch, prompt_len), 0,
                                cfg.vocab)
    cache = model.init_cache(batch, prompt_len + steps + 1)
    prefill = jax.jit(lambda p, t, c: model.prefill(p, t, c, ctx))
    step = jax.jit(lambda p, t, c, pos: model.decode_step(p, t, c, pos, ctx))
    from repro.obs.telemetry import Stopwatch

    _, cache = prefill(qparams, tokens, cache)
    tok = tokens[:, -1:]
    logits, cache = step(qparams, tok, cache, jnp.int32(prompt_len))  # warm
    sw = Stopwatch()
    for i in range(steps):
        logits, cache = step(qparams, tok, cache,
                             jnp.int32(prompt_len + 1 + i))
    jax.block_until_ready(logits)
    us = sw.elapsed_us() / steps
    wbytes = tree_weight_bytes(qparams)
    print(f"serve-smoke[{backend}]: {us:.1f} us/step, "
          f"weight bytes/step {wbytes / 2**20:.2f} MiB")
    return us


def serve_engine_run(model, qparams, astates, recipe, cfg, *,
                     backend: str = "auto", slots: int = 4,
                     requests: int = 8, max_new: int = 16,
                     kv_quant: bool = True):
    """Run the continuous-batching engine on a synthetic request stream.

    Deploy-mode weights (kernel dispatch per ``backend``), bucketed AOT
    prefill, slot decode with the int8 KV cache by default. Prints sustained
    tokens/s at full occupancy, HBM per slot, per-bucket prefill latency
    (p50 over the run, not just the last call), per-request TTFT/queue-wait
    percentiles, and the (flat) compile count. Exits non-zero with the
    machine-readable skip reason on families the slot layout cannot
    serve."""
    import numpy as np

    from repro.core.context import QuantCtx
    from repro.obs.telemetry import Stopwatch
    from repro.serve import EngineConfig, Request, Scheduler, ServeEngine
    from repro.serve.smoke import serve_capability

    ok, reason = serve_capability(model, engine=True, kv_quant=kv_quant)
    if not ok:
        raise SystemExit(f"serve: skipped arch={cfg.name} reason={reason}")
    ctx = QuantCtx(mode="deploy", recipe=recipe, astates=astates,
                   backend=backend)
    max_len = max(32, 2 * max_new)
    engine = ServeEngine(model, qparams, ctx,
                         EngineConfig(slots=slots, max_len=max_len,
                                      prefill_group=2, kv_quant=kv_quant))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    tokens=rng.integers(0, cfg.vocab,
                                        size=int(rng.integers(4, 16)),
                                        ).astype(np.int32),
                    max_new=max_new)
            for i in range(requests)]
    sw = Stopwatch()
    with Scheduler(engine) as sched:
        outs = sched.run(reqs)
        st = sched.stats()
    dt = sw.elapsed_s()
    n_tok = sum(len(v) for v in outs.values())
    pf = " ".join(f"b{b}={s['p50']:.0f}us(n={s['count']})"
                  for b, s in sorted(st["prefill_us"].items()))
    rq = st["requests"]
    print(f"serve[{backend}] kv={'int8' if kv_quant else 'fp'}: "
          f"{requests} requests x {max_new} tokens on {slots} slots -> "
          f"{n_tok / dt:.1f} tokens/s, "
          f"hbm_per_slot {st['hbm_per_slot_MiB']:.4f} MiB, "
          f"compile_count {st['compile_count']} "
          f"(buckets {st['buckets']}), prefill {pf}")
    print(f"serve requests: ttft p50={rq['ttft_us']['p50']:.0f}us "
          f"p95={rq['ttft_us']['p95']:.0f}us, "
          f"queue_wait p50={rq['queue_wait_us']['p50']:.0f}us "
          f"p95={rq['queue_wait_us']['p95']:.0f}us, "
          f"detok_errors={rq['detok_errors']}")
    return st


if __name__ == "__main__":
    main()
