"""Entry-point tracers: turn the repo's real compiled callables into
:class:`TracedEntry` objects the jaxpr analyzers consume.

Each builder constructs exemplar inputs at smoke scale, obtains the
ClosedJaxpr via ``jax.jit(...).trace(...)``, and labels every flattened
invar with a human-readable path (``astates.~s0.step`` …) so findings point
at the actual pytree leaf, not "invar 17". The entries cover the
ROADMAP-level contract surfaces:

  recon_chunk      the engine's donated, scanned ``run_chunk`` (mesh on/off)
  probe            the sensitivity probe step (repro.allocate)
  qtensor_matmul   one entry per QTensor layout in the ROADMAP kernel table
  deploy_decode    the smoke LM's deploy-mode decode step (opt-in: builds
                   and quantizes a model)
  serve_prefill /  the serve engine's bucketed prefill-insert and slot
  serve_decode     decode step (opt-in; same quantized smoke LM, donated
                   slot state, int8 KV-scale range contract for QL303)

Seeded-bug variants (``drop_a_state=...``, ``per_layer=...``) deliberately
re-introduce shipped regressions so tests can assert each analyzer flags
exactly them; they are never part of the default lint run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import reconstruct as rec
from repro.core import rtn
from repro.core.quant_config import QuantConfig, QuantRecipe
from repro.core.reconstruct import (BlockHandle, Site, init_astates,
                                    init_wstates, site_plans)
from repro.obs.sink import ListSink
from repro.obs.telemetry import TELEMETRY
from repro.optim.adam import AdamConfig, adam_init


@dataclasses.dataclass(frozen=True)
class MemContract:
    """Per-entry HBM budget: ``budget(L) = fixed_bytes + per_len_bytes*L``.

    The contract memcheck (QL401) proves the entry's jaxpr peak-live bytes
    against — at the traced window length *and*, when ``envelope_len`` is
    declared, scaled up to the production window (every buffer carrying a
    ``max_len`` dim scales linearly, everything else is fixed). Liveness is
    computed on the jaxpr, i.e. *pre-fusion*: XLA fusion only shrinks real
    peaks, so ``jaxpr peak <= budget`` soundly implies the compiled program
    fits. Budgets therefore carry explicit, documented headroom over their
    semantic components (weights + window state + activation slack), and
    the rule exists to catch asymptotic regressions — a dequantized window
    materialized as persistent state, a doubled carry — not 5%% drifts.

    ``expect`` rows feed QL403 weight-traffic honesty: ``(measure,
    label_glob, expected_bytes)`` — the bytes the live accessors
    (``tree_weight_bytes``, ``serve.kv.hbm_per_slot_bytes``) report for the
    exemplar pytrees, cross-checked against the bytes the jaxpr's *live*
    invars matching ``label_glob`` actually move.
    """
    fixed_bytes: int              # window-independent budget component
    per_len_bytes: int = 0        # budget bytes per token of the window
    max_len: int = 0              # traced [*, max_len] window (0 = none)
    envelope_len: int = 0         # production window (envelope seq_max)
    slots: int = 0                # decode slots (serve entries)
    note: str = ""                # where the numbers come from
    expect: Tuple[Tuple[str, str, int], ...] = ()

    def budget_at(self, length: int) -> int:
        return self.fixed_bytes + self.per_len_bytes * int(length)


@dataclasses.dataclass
class TracedEntry:
    """One traced entry point, ready for the jaxpr analyzers."""
    name: str
    closed: Any                      # ClosedJaxpr
    labels: List[str]                # one per flat invar, in invar order
    donated: frozenset               # flat invar indices donated to XLA
    allow_unused: Tuple[str, ...] = ()   # fnmatch globs over labels
    mesh: Any = None                 # jax Mesh when the entry declares one
    dp: Tuple[str, ...] = ()         # data-parallel axis names to honor
    donated_leaves: Tuple[Any, ...] = ()  # exemplar donated arrays (alias check)
    # quantcheck (repro.analysis.intervals) inputs: value-range seeds for
    # the interval interpreter — (label glob, lo, hi), first match wins —
    # and the shape-envelope name whose k_max scales every contraction in
    # the overflow proof (kernels.envelope.SHAPE_ENVELOPES key)
    ranges: Tuple[Tuple[str, float, float], ...] = ()
    envelope: Optional[str] = None
    # memcheck (repro.analysis.memcheck) input: the entry's HBM budget
    # contract; None skips QL401 (the liveness report still runs)
    mem: Optional[MemContract] = None


def _path_str(path) -> str:
    toks = []
    for p in path:
        if hasattr(p, "key"):
            toks.append(str(p.key))
        elif hasattr(p, "idx"):
            toks.append(f"[{p.idx}]")
        else:
            toks.append(str(p).strip("."))
    return ".".join(toks)


def trace_jitted(jitted, args: Tuple, *, name: str,
                 argnames: Sequence[str],
                 donate_argnums: Tuple[int, ...] = (),
                 allow_unused: Tuple[str, ...] = (),
                 mesh=None, dp: Tuple[str, ...] = (),
                 ranges: Tuple[Tuple[str, float, float], ...] = (),
                 envelope: Optional[str] = None,
                 mem: Optional[MemContract] = None) -> TracedEntry:
    """Trace ``jitted(*args)`` and label its flattened invars.

    ``argnames`` must name each positional argument; labels come out as
    ``<argname>.<pytree path>``. ``donate_argnums`` mirrors the jit's own
    donation so the donation analyzer knows which invars XLA may reuse.
    """
    closed = jitted.trace(*args).jaxpr
    labels: List[str] = []
    donated: set = set()
    donated_leaves: List[Any] = []
    for i, (aname, arg) in enumerate(zip(argnames, args)):
        flat = jax.tree_util.tree_flatten_with_path(arg)[0]
        for path, leaf in flat:
            sub = _path_str(path)
            labels.append(f"{aname}.{sub}" if sub else aname)
            if i in donate_argnums:
                donated.add(len(labels) - 1)
                donated_leaves.append(leaf)
    n_invars = len(closed.jaxpr.invars)
    if len(labels) != n_invars:
        raise RuntimeError(
            f"{name}: invar labeling out of sync — {len(labels)} flattened "
            f"arg leaves vs {n_invars} jaxpr invars; did the jit close over "
            "an argument or take kwargs?")
    return TracedEntry(name=name, closed=closed, labels=labels,
                       donated=frozenset(donated),
                       allow_unused=tuple(allow_unused), mesh=mesh, dp=dp,
                       donated_leaves=tuple(donated_leaves),
                       ranges=tuple(ranges), envelope=envelope, mem=mem)


# ------------------------------------------------------ memory contracts
def _tree_bytes(tree) -> int:
    """Actual device bytes of a pytree's array leaves."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "dtype"))


def _window_bytes(tree, max_len: int) -> int:
    """Bytes of leaves carrying a ``max_len`` dim (the sequence window)."""
    if not max_len:
        return 0
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "shape") and max_len in leaf.shape)


def mem_contract(args, *, max_len: int = 0, envelope_len: int = 0,
                 slots: int = 0, headroom: float = 2.0,
                 len_headroom: float = 8.0, fixed_extra: int = 1 << 20,
                 note: str = "",
                 expect: Tuple[Tuple[str, str, int], ...] = ()
                 ) -> MemContract:
    """Derive an entry's :class:`MemContract` from its exemplar arguments.

    ``fixed = headroom * (non-window arg bytes) + fixed_extra`` covers the
    arguments, their (donation-aliased) outputs and smoke-scale activation
    temporaries; ``per_len = len_headroom * (window arg bytes) / max_len``
    covers the window state plus the pre-fusion f32 views the decode
    attention takes of it (jaxpr liveness counts the ``astype(f32)`` of the
    int8 codes that XLA later fuses away — see :class:`MemContract`).
    """
    total = _tree_bytes(args)
    win = _window_bytes(args, max_len)
    per_len = int(len_headroom * win / max_len) if max_len else 0
    return MemContract(
        fixed_bytes=int(headroom * (total - win)) + fixed_extra,
        per_len_bytes=per_len, max_len=max_len, envelope_len=envelope_len,
        slots=slots, note=note, expect=tuple(expect))


# --------------------------------------------------------------- toy blocks
def toy_block(key, name: str, d: int = 16, h: int = 24,
              token=None) -> BlockHandle:
    """Two-linear gelu residual block (the recon-engine test exemplar)."""
    k1, k2 = jax.random.split(key)
    params = {
        "w1": jax.random.normal(k1, (d, h), jnp.float32) * d**-0.5,
        "w2": jax.random.normal(k2, (h, d), jnp.float32) * h**-0.5,
    }

    def apply(p, x, ctx, _n=name):
        z = jax.nn.gelu(ctx.linear(f"{_n}.w1", x, p["w1"]))
        return ctx.linear(f"{_n}.w2", z, p["w2"]) + x

    sites = {f"{name}.w1": Site(("w1",)), f"{name}.w2": Site(("w2",))}
    return BlockHandle(name, params, apply, sites, apply_key=token)


def toy_chain(n: int, token: Optional[str] = "quantlint-chain",
              d: int = 16, h: int = 24) -> List[BlockHandle]:
    """``token=None`` disables engine sharing — the deliberate per-layer
    retrace used as a seeded bug."""
    keys = jax.random.split(jax.random.key(7), n)
    return [toy_block(keys[i], f"blk{i}", d, h, token=token)
            for i in range(n)]


def toy_recipe(iters: int = 6, batch_size: int = 4, w_bits: int = 4,
               a_bits: Optional[int] = 8) -> QuantRecipe:
    return QuantRecipe(method="flexround", w_bits=w_bits, a_bits=a_bits,
                       iters=iters, batch_size=batch_size)


# ------------------------------------------------------------ recon chunk
_RUN_CHUNK_ARGS = ("params", "wstates", "astates", "wopt", "aopt", "x_q",
                   "y_fp", "idx", "k2s", "steps", "salts", "sweight")


def recon_chunk_entry(mesh=None, *, n: int = 8, bs: int = 4, iters: int = 6,
                      d: int = 16, h: int = 24) -> TracedEntry:
    """The engine's ``run_chunk`` exactly as ``_run_scan`` drives it:
    donated carry states, minibatch gather (``bs < n`` forces the gather so
    the mesh variant exercises the stream re-constrain path)."""
    assert bs < n, "bs < n keeps the gather (and sharding constraints) live"
    block = toy_block(jax.random.key(3), "entry", d, h,
                      token="quantlint-recon-entry")
    recipe = toy_recipe(iters=iters, batch_size=bs)
    plans = site_plans(block, recipe)
    x_q = jax.random.normal(jax.random.key(11), (n, d), jnp.float32)
    y_fp = jax.random.normal(jax.random.key(12), (n, d), jnp.float32)

    canon = rec._canon_names(block)
    wstates = init_wstates(block, recipe)
    astates = init_astates(block, recipe, x_q)
    c_w = {canon[r]: v for r, v in wstates.items()}
    c_a = {canon[r]: astates[r] for r in block.sites if r in astates}
    salts = {canon[r]: rec._salt(r) for r in block.sites}
    wopt = adam_init(c_w, rec._W_BASE_CFG)
    aopt = adam_init(c_a, AdamConfig(lr=recipe.lr_lsq))
    c_w, c_a, wopt, aopt = rec._dealias(c_w, c_a, wopt, aopt)

    idx, k2s = rec._batch_schedule(jax.random.key(0), iters, n, bs)
    steps = jnp.arange(iters, dtype=jnp.int32)

    eng = rec._build_engine(block, recipe,
                            {canon[r]: plans[r] for r in block.sites},
                            canon, mesh)
    args = (block.params, c_w, c_a, wopt, aopt, x_q, y_fp, idx, k2s, steps,
            salts, None)
    dp = ()
    if mesh is not None:
        from repro.launch.mesh import dp_axes
        dp = dp_axes(mesh)
    # trace under live telemetry: the recon loop's spans are host-side
    # only, so the jaxpr must be identical with the sink enabled — any
    # telemetry op leaking into the trace shows up to QL201/QL202
    with TELEMETRY.enabled_scope(sink=ListSink()):
        return trace_jitted(
            eng.run_chunk, args,
            name="recon_chunk" + ("_sharded" if mesh is not None else ""),
            argnames=_RUN_CHUNK_ARGS, donate_argnums=(1, 2, 3, 4),
            # FlexRound has no step-annealed rounding regularizer (that is
            # AdaRound's b-schedule), so the scanned step index is dead by
            # design under this recipe
            allow_unused=("steps",),
            mesh=mesh, dp=dp,
            # HBM contract: the donated Adam/rounding carries alias their
            # outputs in place, so the chunk's peak is args + the scanned
            # step's gradient/activation temporaries (grads mirror the
            # carries; 2x arg headroom covers them at any chunk length)
            mem=mem_contract(
                args, headroom=2.0,
                note="donated Adam/rounding carries + calibration streams; "
                     "grads mirror the carries (2x) + 1 MiB step slack"))


# ----------------------------------------------------------------- probe
def probe_entry(bits: int = 4, d: int = 16, h: int = 24) -> TracedEntry:
    """The sensitivity probe step (repro.allocate): traced one-hot gates
    select the quantized site, so every leaf — including every gate — must
    stay live in the jaxpr."""
    from repro.allocate import sensitivity as sens
    from repro.core import paths as pth

    block = toy_block(jax.random.key(5), "probe", d, h,
                      token="quantlint-probe-entry")
    recipe = toy_recipe()
    plans = site_plans(block, recipe)
    canon = rec._canon_names(block)
    cfgs_c = {canon[rn]: dataclasses.replace(plans[rn].weight, bits=bits)
              for rn in block.sites}
    probe_fn = sens._build_probe(block, cfgs_c, canon)

    wstates = {}
    for rn, site in block.sites.items():
        w = pth.get_path(block.params, site.path)
        wstates[canon[rn]] = rtn.init(w, cfgs_c[canon[rn]])
    first = sorted(canon.values())[0]
    gates = {c: jnp.asarray(c == first) for c in canon.values()}
    x = jax.random.normal(jax.random.key(21), (4, d), jnp.float32)
    y_fp = jax.random.normal(jax.random.key(22), (4, d), jnp.float32)
    args = (block.params, x, y_fp, wstates, gates)
    return trace_jitted(probe_fn, args,
                        name="probe_step",
                        argnames=("params", "x", "y_fp", "wstates", "gates"),
                        mem=mem_contract(
                            args, headroom=2.0,
                            note="params + RTN states + probe streams; the "
                                 "gated fake-quant materializes one "
                                 "quantized weight per site (2x)"))


# --------------------------------------------------------- qtensor_matmul
def _export_qt(shape, bits, granularity="per_channel", batch_dims=0):
    qcfg = QuantConfig(bits=bits, symmetric=False, observer="minmax",
                       granularity=granularity, batch_dims=batch_dims)
    w = jax.random.normal(jax.random.key(9), shape, jnp.float32) * 0.1
    return rtn.export(w, rtn.init(w, qcfg), qcfg, dtype=jnp.float32)


def _a_state_for(x):
    from repro.core import lsq
    aq = QuantConfig(bits=8, symmetric=False, granularity="per_tensor",
                     observer="minmax")
    st = lsq.init(jnp.asarray([float(jnp.min(x)), float(jnp.max(x))]), aq)
    return lsq.deploy_astate(st, aq)


# (name, weight shape, bits, batch_dims, with_a_state) — one row per QTensor
# layout in the ROADMAP kernel table. Dims are smoke-scale; the layout (pack
# axis, batch dims, a_state presence) is what selects the kernel.
MATMUL_LAYOUTS: Tuple[Tuple[str, Tuple[int, ...], int, int, bool], ...] = (
    ("w4_packed", (64, 32), 4, 0, False),
    ("w4a8_packed", (64, 32), 4, 0, True),
    ("w8a8", (48, 24), 8, 0, True),
    ("w8_weight_only", (48, 24), 8, 0, False),
    ("w4_odd_unpacked", (33, 24), 4, 0, False),
    ("experts_batched", (4, 32, 16), 4, 1, False),
)


def matmul_example(layout: str):
    """(x, qt, a_state) exemplar inputs for one kernel-table layout."""
    for name, shape, bits, batch_dims, with_a in MATMUL_LAYOUTS:
        if name != layout:
            continue
        qt = _export_qt(shape, bits, batch_dims=batch_dims)
        if batch_dims == 1:
            E, K, _ = shape
            x = jax.random.normal(jax.random.key(13), (E, 5, K), jnp.float32)
        else:
            x = jax.random.normal(jax.random.key(13), (5, shape[0]),
                                  jnp.float32)
        return x, qt, (_a_state_for(x) if with_a else None)
    raise KeyError(layout)


def qtensor_matmul_entry(layout: str, *,
                         drop_a_state: bool = False) -> TracedEntry:
    """One kernel-table layout traced through ``kernels.ops.qtensor_matmul``
    on the XLA ref path.

    ``drop_a_state=True`` re-introduces the PR 5 regression — the wrapper
    accepts the activation grid but never hands it to the kernel — so the
    unused-input analyzer has a known-bad fixture to flag.
    """
    from repro.kernels import ops as kops
    from repro.kernels.envelope import get_envelope
    x, qt, a_state = matmul_example(layout)
    env = get_envelope(layout)
    # value-range contract for the interval interpreter: activation
    # magnitude and grid-scale bounds come from the layout's envelope;
    # codes/zero live on the integer grid
    ranges = (
        ("x*", -env.x_abs_max, env.x_abs_max),
        ("qt.scale*", env.scale_min, env.scale_max),
        ("qt.zero*", 0.0, float(env.code_max)),
        ("a_state.[0]", env.scale_min, env.scale_max),    # deploy a_scale
        ("a_state.[1]", 0.0, 255.0),                      # deploy a_zero
    )

    def run(x, qt, a_state):
        passed = None if drop_a_state else a_state
        return kops.qtensor_matmul(x, qt, a_state=passed, backend="xla")

    args: Tuple[Any, ...] = (x, qt)
    argnames: Tuple[str, ...] = ("x", "qt")
    fn: Callable = lambda x, qt: run(x, qt, None)  # noqa: E731
    if a_state is not None:
        args = (x, qt, a_state)
        argnames = ("x", "qt", "a_state")
        fn = run
    name = f"qtensor_matmul[{layout}]"
    if drop_a_state:
        name += "[seeded:a_state_drop]"
    return trace_jitted(jax.jit(fn), args, name=name, argnames=argnames,
                        ranges=ranges, envelope=layout)


def matmul_entries() -> List[TracedEntry]:
    return [qtensor_matmul_entry(row[0]) for row in MATMUL_LAYOUTS]


# ----------------------------------------------------------- deploy decode
def _deploy_smoke_lm(arch: str):
    """Quantize the smoke LM (iters=0: export-only) and return the deploy
    pieces — ``(cfg, model, qparams, ctx)`` — that the decode and serving
    entries trace through."""
    from repro.configs import get_smoke_config
    from repro.core.context import QuantCtx
    from repro.core.reconstruct import quantize_blocks
    from repro.data import CalibrationSet, SyntheticTokens
    from repro.models import build_model

    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    recipe = QuantRecipe(method="flexround", w_bits=4, a_bits=8, iters=0,
                         batch_size=4)
    cal = CalibrationSet.build(SyntheticTokens(vocab=cfg.vocab, seq_len=16,
                                               seed=0), 4)
    x0, blocks, assemble = model.quant_blocks(params, cal.tokens)
    finalized, astates, _ = quantize_blocks(blocks, recipe, x0)
    qparams = assemble(finalized)
    ctx = QuantCtx(mode="deploy", recipe=recipe, astates=astates,
                   backend="xla")
    return cfg, model, qparams, ctx


def deploy_decode_entry(arch: str = "smollm-135m",
                        allow_unused: Tuple[str, ...] = (),
                        ) -> TracedEntry:
    """The smoke LM's deploy-mode decode step — every QTensor
    code/scale/zero leaf and every LSQ deploy grid must stay live through
    the serving path."""
    from repro.core.qtensor import tree_weight_bytes
    from repro.kernels.envelope import get_envelope
    from repro.serve import kv as skv

    cfg, model, qparams, ctx = _deploy_smoke_lm(arch)
    batch, prompt = 2, 8
    max_len = prompt + 4
    tokens = jax.random.randint(jax.random.key(1), (batch, prompt), 0,
                                cfg.vocab)
    cache = model.init_cache(batch, max_len)
    step = jax.jit(
        lambda p, t, c, pos: model.decode_step(p, t, c, pos, ctx))
    tok = tokens[:, -1:]
    args = (qparams, tok, cache, jnp.int32(prompt))
    return trace_jitted(
        step, args,
        name=f"deploy_decode[{cfg.name}]",
        argnames=("params", "tokens", "cache", "pos"),
        allow_unused=allow_unused,
        mem=mem_contract(
            args, max_len=max_len,
            envelope_len=get_envelope("serve_kv").seq_max, slots=batch,
            note="packed weights + [batch, max_len] fp KV window; len "
                 "headroom covers the attention's f32 window views",
            expect=(("weights", "params*", tree_weight_bytes(qparams)),
                    ("kv_cache", "cache*", skv.cache_bytes(cache)))))


# ------------------------------------------------------------ serve engine
def _serve_smoke_config(*, kv_quant: bool = True, dtype=None):
    """The one smoke-scale ``EngineConfig`` every serve trace entry uses.

    ``max_len=24`` deliberately: memcheck classifies a buffer as
    window-scaled when ``max_len`` appears in its shape, so the window
    length must be unique among the smoke LM's dims (d_model=64, heads=4,
    kv_heads=2, head_dim=16, d_ff=128, vocab=128) — 16 would make every
    attention head-dim buffer look like KV state. Buckets stay [8, 16].
    """
    from repro.serve import engine as seng

    kw = {} if dtype is None else {"dtype": dtype}
    return seng.EngineConfig(slots=2, max_len=24, prefill_group=2,
                             kv_quant=kv_quant, min_bucket=8, **kw)


def _serve_kv_ranges(prefix: str) -> Tuple[Tuple[str, float, float], ...]:
    """Value-range contract for the slot state's int8 KV cache: stored
    scales are floored at kv_quantize's KV_SCALE_MIN (so QL303 can prove
    no divisor/product over them is subnormal), codes live on the int8
    grid. First match wins, so scales precede the code catch-all."""
    from repro.kernels.envelope import get_envelope
    env = get_envelope("serve_kv")
    return (
        (f"{prefix}.*scale*", env.scale_min, env.scale_max),
        (f"{prefix}.*", -float(env.code_max), float(env.code_max)),
    )


def serve_prefill_entry(arch: str = "smollm-135m",
                        bucket: int = 8) -> TracedEntry:
    """The serve engine's bucketed prefill-insert (one bucket), traced on
    the exact function ``ServeEngine`` AOT-compiles: donated slot state
    (QL203 aliasing), every KV scale live (QL201), and the int8 KV scale
    range contract (QL303)."""
    from repro.core.qtensor import tree_weight_bytes
    from repro.kernels.envelope import get_envelope
    from repro.serve import engine as seng
    from repro.serve import kv as skv

    cfg, model, qparams, ctx = _deploy_smoke_lm(arch)
    ecfg = _serve_smoke_config()
    state = seng.init_state(model, ecfg)
    G = ecfg.prefill_group
    fn = jax.jit(seng.make_prefill(model, ctx, ecfg, bucket),
                 donate_argnums=(1,))
    tokens = jax.random.randint(jax.random.key(2), (G, bucket), 0,
                                cfg.vocab, dtype=jnp.int32)
    true_len = jnp.full((G,), bucket, jnp.int32)
    slot_ids = jnp.arange(G, dtype=jnp.int32)
    max_new = jnp.full((G,), 4, jnp.int32)
    args = (qparams, state, tokens, true_len, slot_ids, max_new)
    mem = mem_contract(
        args, max_len=ecfg.max_len,
        envelope_len=get_envelope("serve_kv").seq_max, slots=ecfg.slots,
        note="weights + donated [slots, max_len] slot state; the bucket's "
             "fresh prefill cache and activations are window-independent "
             "(bucket-sized) and ride in the fixed headroom",
        expect=(("weights", "params*", tree_weight_bytes(qparams)),
                ("kv_cache", "state.cache*",
                 skv.hbm_per_slot_bytes(state["cache"], ecfg.slots)
                 * ecfg.slots)))
    # traced under live telemetry: serve.prefill spans are host-side only
    with TELEMETRY.enabled_scope(sink=ListSink()):
        return trace_jitted(
            fn, args,
            name=f"serve_prefill[{cfg.name}][b{bucket}]",
            argnames=("params", "state", "tokens", "true_len", "slot_ids",
                      "max_new"),
            donate_argnums=(1,), ranges=_serve_kv_ranges("state.cache"),
            envelope="serve_kv", mem=mem)


def serve_decode_entry(arch: str = "smollm-135m",
                       kv_quant: bool = True) -> TracedEntry:
    """The serve engine's slot decode step (donated KV-cache carry,
    active-masked position/budget update) — the loop the engine runs once
    per emitted token, so a dead scale invar or a donation alias here is a
    production serving bug.

    ``kv_quant=False`` traces the bf16-KV variant of the same step: memcheck
    compares the two entries' static per-slot window bytes to prove, from
    the jaxprs alone, that the int8 cache pins strictly less HBM per slot
    than the bf16 cache (the claim the serve bench measures live).
    """
    from repro.core.qtensor import tree_weight_bytes
    from repro.kernels.envelope import get_envelope
    from repro.serve import engine as seng
    from repro.serve import kv as skv

    cfg, model, qparams, ctx = _deploy_smoke_lm(arch)
    ecfg = _serve_smoke_config(
        kv_quant=kv_quant, dtype=None if kv_quant else jnp.bfloat16)
    state = seng.init_state(model, ecfg)
    meta = {k: state[k] for k in ("tokens", "pos", "remaining")}
    fn = jax.jit(seng.make_decode(model, ctx, ecfg), donate_argnums=(1,))
    tag = "" if kv_quant else "[bf16-kv]"
    ranges = (_serve_kv_ranges("cache") if kv_quant
              else (("cache.*", -64.0, 64.0),))
    args = (qparams, state["cache"], meta)
    mem = mem_contract(
        args, max_len=ecfg.max_len,
        envelope_len=get_envelope("serve_kv").seq_max, slots=ecfg.slots,
        note="packed weights + donated [slots, max_len] KV window; len "
             "headroom covers the attention's pre-fusion f32 window views",
        expect=(("weights", "params*", tree_weight_bytes(qparams)),
                ("kv_cache", "cache*",
                 skv.hbm_per_slot_bytes(state["cache"], ecfg.slots)
                 * ecfg.slots)))
    # traced under live telemetry: serve.decode_step spans are host-side only
    with TELEMETRY.enabled_scope(sink=ListSink()):
        return trace_jitted(
            fn, args,
            name=f"serve_decode[{cfg.name}]{tag}",
            argnames=("params", "cache", "meta"),
            donate_argnums=(1,), ranges=ranges,
            envelope="serve_kv", mem=mem)


# ------------------------------------------------- quantcheck (QL3xx) entries
def flexround_apply_entry(*, underflow: bool = False,
                          d: int = 32, h: int = 16) -> TracedEntry:
    """The PTQ inner loop's fake-quant Ŵ = s1*(clip(round(W/(s1⊙S2⊙s3))+z)-z)
    traced for the interval interpreter.

    The healthy range contract mirrors ``flexround.project``: every divisor
    factor is floored at EPS = 1e-6, so the s1*s2*s3 product is provably
    normal (>= 1e-18 >> float32 tiny) and QL303 stays quiet. ``underflow=True``
    re-seeds the factors at ~1e-18 each — the projection bug quantcheck
    exists to catch — making the whole divisor interval subnormal.
    """
    from repro.core import flexround
    from repro.kernels.envelope import get_envelope

    qcfg = QuantConfig(bits=4, symmetric=False, observer="minmax",
                       granularity="per_channel")
    w = jax.random.normal(jax.random.key(17), (d, h), jnp.float32) * 0.1
    state = flexround.init(w, qcfg)
    env = get_envelope("flexround_apply")
    lo, hi = ((1e-20, 1e-18) if underflow
              else (env.scale_min, env.scale_max))
    ranges = (
        ("w*", -env.x_abs_max, env.x_abs_max),
        ("state.s1*", lo, hi),
        ("state.s2*", lo, hi),
        ("state.s3*", lo, hi),
        ("state.zero*", 0.0, float(qcfg.qmax)),
    )
    fn = jax.jit(lambda w, state: flexround.apply(w, state, qcfg))
    name = "flexround_apply"
    if underflow:
        name += "[seeded:scale_underflow]"
    return trace_jitted(fn, (w, state), name=name, argnames=("w", "state"),
                        ranges=ranges, envelope="flexround_apply")


def int8_overflow_entry() -> TracedEntry:
    """Seeded QL301 fixture: the W8A8 matmul accumulating in int16.

    int8 x int8 products reach 2^14; even the smoke-scale K = 48 contraction
    tops 2^19, and the envelope's k_max = 32768 pushes the proof bound to
    ~2^29 — either way far past int16. The healthy kernels accumulate in
    int32 (``preferred_element_type=jnp.int32``); this entry re-introduces
    the narrow accumulator so tests can pin quantcheck catching it.
    """
    a_q = jax.random.randint(jax.random.key(23), (8, 48), -128, 128,
                             dtype=jnp.int8)
    b_q = jax.random.randint(jax.random.key(24), (48, 24), -128, 128,
                             dtype=jnp.int8)

    def bad(a_q, b_q):
        acc = jax.lax.dot_general(
            a_q.astype(jnp.int16), b_q.astype(jnp.int16),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.int16)
        return acc.astype(jnp.float32)

    return trace_jitted(jax.jit(bad), (a_q, b_q),
                        name="qmatmul_int8[seeded:int16_acc]",
                        argnames=("a_q", "b_q"), envelope="w8a8")


def _one_device_mesh():
    """Smallest mesh carrying both named axes — enough for shard_map
    *tracing* (the analyzers never execute the entry)."""
    import numpy as np
    return jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


def lost_psum_entry(mesh=None) -> TracedEntry:
    """Seeded QL305 fixture: a sharded loss reduction whose psum runs over
    the *model* axis instead of the data axis, with ``check_vma=False``
    silencing shard_map's own replication check — the per-host loss is
    declared replicated but never actually reduced over data parallelism,
    so every host trains on a different objective (the classic lost psum).
    """
    from jax.sharding import PartitionSpec as P

    mesh = mesh or _one_device_mesh()

    def local_loss(x, y):
        err = jnp.mean((x - y) ** 2)
        # BUG (seeded): reduces over "model", leaving "data" unreduced
        return jax.lax.psum(err, "model")

    fn = jax.shard_map(local_loss, mesh=mesh,
                       in_specs=(P("data"), P("data")),
                       out_specs=P(), check_vma=False)
    x = jnp.ones((8, 16), jnp.float32)
    y = jnp.ones((8, 16), jnp.float32)
    return trace_jitted(jax.jit(fn), (x, y),
                        name="sharded_loss[seeded:lost_psum]",
                        argnames=("x", "y"), mesh=mesh, dp=("data",))


# ------------------------------------------------- memcheck (QL4xx) fixtures
def dead_donation_entry() -> TracedEntry:
    """Seeded QL402 fixture: an int8 codes buffer donated into a reduction
    that returns only an f32 scalar — no output shares the donated buffer's
    shape and dtype, so XLA cannot reuse the storage and the donation buys
    nothing. QL203 stays quiet (the buffer is consumed exactly once and not
    returned); this is its silent inverse, visible only to the liveness
    accounting."""
    codes = jax.random.randint(jax.random.key(31), (64, 64), -127, 128,
                               dtype=jnp.int8)

    def flush_stats(codes):
        return jnp.mean(jnp.abs(codes.astype(jnp.float32)))

    return trace_jitted(
        jax.jit(flush_stats, donate_argnums=(0,)), (codes,),
        name="kv_flush_stats[seeded:dead_donation]",
        argnames=("codes",), donate_argnums=(0,))


def hbm_blowout_entry() -> TracedEntry:
    """Seeded QL401 fixture: decode attention that dequantizes the *whole*
    int8 KV window to f32 before contracting — the regression
    ``serve.kv.int8_decode_attention`` exists to prevent. The budget is the
    honest dequant-free path's (int8 codes + f32 scales per window token,
    modest slack), so the materialized 4-bytes-per-element f32 window blows
    past it at the traced length, and 32x worse at the envelope length.
    """
    slots, max_len, heads, d = 2, 24, 2, 16
    codes = jax.random.randint(jax.random.key(32),
                               (slots, max_len, heads, d), -127, 128,
                               dtype=jnp.int8)
    scale = jnp.full((slots, max_len, heads, 1), 1e-2, jnp.float32)
    q = jax.random.normal(jax.random.key(33), (slots, 1, heads, d),
                          jnp.float32)

    def bad_attention(q, codes, scale):
        # BUG (seeded): rematerializes the full window in f32 as a named
        # intermediate (the healthy path folds scales post-contraction)
        kv = codes.astype(jnp.float32) * scale
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kv)
        return jnp.sum(jax.nn.softmax(s, axis=-1))

    args = (q, codes, scale)
    # tight, int8-sized budget: no 8x f32-view headroom, 2 KiB fixed slack
    mem = mem_contract(args, max_len=max_len, envelope_len=8192, slots=slots,
                       headroom=1.5, len_headroom=1.5, fixed_extra=2048,
                       note="dequant-free budget: int8 codes + scales only")
    return trace_jitted(
        jax.jit(bad_attention), args,
        name="decode_attention[seeded:hbm_blowout]",
        argnames=("q", "codes", "scale"), mem=mem)
