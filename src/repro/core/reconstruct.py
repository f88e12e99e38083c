"""Block/layer-wise PTQ reconstruction engine (paper §3.1, §4).

Implements the sequential reconstruction the paper uses everywhere:

  for each block B (transformer layer, or single linear for layer-wise):
      y_fp = B_fp(x_fp)                      # teacher on the fp stream
      learn rounding states minimizing ||y_fp - B_q(x_q)||^2 (+AdaRound reg)
      finalize B -> integer weights; advance both streams

``x_fp`` is the full-precision activation stream; ``x_q`` the stream produced
by already-quantized predecessors (the X̃ of Eq. ||WX - Ŵ X̃||). Activation
quantizers (LSQ) are initialized from the student stream and co-trained with
the rounding states (paper: LSQ technique for the activation step size).

Execution model (the hot path — this loop runs iters × layers times):

  scan engine             The minibatch schedule (epoch keys + gather
      indices) is precomputed on device once per block, then chunks of K
      optimization steps run inside a single jitted ``jax.lax.scan`` —
      Adam moments, rounding states, LSQ states and the PRNG stream are
      threaded as the scan carry and loss/mse trajectories come back as
      stacked outputs. One dispatch per K steps instead of one per step,
      and no host-side gathers. The RNG stream is bit-identical to the
      removed per-iteration legacy loop; parity is pinned against recorded
      legacy trajectories in tests/fixtures/recon_legacy_trajectories.npz.

  compiled-step cache     Blocks are canonicalized (site names rewritten to
      position-based tokens, per-site QDrop salts passed as traced uint32
      scalars, resolved SitePlans attached to the ctx) so the L identical
      layers of a transformer hit one compiled step/teacher/student/
      recon_error instead of L. Cache keys combine the block's ``apply_key``
      (models stamp structurally identical layers with a shared token),
      the canonicalized site plans (``SitePlan.cache_key``) and the recipe.
      Carried states are de-aliased (constant-dedup can hand identical init
      buffers to several sites) so ``donate_argnums`` is safe on the scan.

  probe mode              The sensitivity prober (repro.allocate) rides the
      same engine cache: ``probe_teacher`` hands out the per-``apply_key``
      compiled teacher, ``engine_scope`` bounds the lifetime of engines a
      probe pass builds, and probe-step traces are counted in
      ``EngineStats.probe_compiles`` so tests can assert the probe pass
      compiles O(distinct apply_keys) steps, not O(sites).

Distribution (data-parallel calibration): pass ``mesh=`` to
``reconstruct_block`` / ``quantize_blocks`` (and ``probe_blocks`` in
repro.allocate). The engine then places the calibration streams — ``x_q``,
``y_fp`` and the optional per-sample loss weights — with the leading sample
axis sharded over the mesh's data axes (``launch/sharding.stream_sharding``;
sample counts that don't divide the data-parallel size degrade to
replication), constrains the gathered minibatches to the same spec inside
the scanned step, and replicates the rounding/Adam/LSQ carry states and the
minibatch schedule (``NamedSharding(mesh, P())``). The loss/MSE reductions
are means over the *global* batch, so under jit the rounding-state gradients
all-reduce (psum) over the data axes automatically and every device steps
identical replicated states. Where the deploy kernels are Pallas (on TPU),
the student stream is ``shard_map``-ped over the same stream spec, because
Pallas kernels cannot be partitioned automatically; expert (MoE) blocks are
refused there, since their token routing spans samples. The mesh is part
of the engine cache key:
blocks still compile once per ``apply_key``, and the sharded trajectory
reproduces the unsharded one (both pinned in tests/test_sharded_recon.py).
``sample_weight`` consumes ``data/pipeline.assemble_global_batch``'s loss
weight: samples from dropped host shards carry weight 0 and the objective
becomes the weighted global-batch mean, so gradient magnitude stays unbiased
under straggler dropping. Per-block state is checkpointed (see
repro/checkpoint) so a failed node restarts at the block boundary; see
quantize_blocks(resume_dir=...).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import zlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lsq
from repro.core import paths as pth
from repro.core.context import QuantCtx
from repro.core.quant_config import QuantRecipe, SitePlan
from repro.obs.telemetry import TELEMETRY, Stopwatch
from repro.optim.adam import AdamConfig, adam_init, adam_update

DEFAULT_CHUNK = 100  # scan steps fused into one jitted dispatch

# Per-site lr rules ride adam_update's per-leaf lr_scale tree, so the base
# config carries lr=1.0 and each leaf scales it by its plan's lr.
_W_BASE_CFG = AdamConfig(lr=1.0)


@dataclasses.dataclass
class Site:
    """One quantizable weight inside a block."""
    path: Tuple  # path of the leaf within the block's param subtree
    kind: str = "linear"  # linear | conv
    batch_dims: int = 0


@dataclasses.dataclass
class BlockHandle:
    """A reconstruction unit: params + apply(params, x, ctx) -> y.

    ``apply_key``: optional hashable token identifying the *computation* of
    ``apply`` independent of this block's parameter values and site-name
    strings. Blocks that stamp the same token (e.g. the L identical layers a
    model's ``quant_blocks`` emits in one call) share one compiled recon
    step/teacher/student. The token must be fresh per ``quant_blocks`` call —
    apply closures bake per-call constants (rope tables, encoder output) into
    the trace. ``None`` disables sharing (the engine still caches per block
    object).
    """
    name: str
    params: Any
    apply: Callable[[Any, jax.Array, QuantCtx], jax.Array]
    sites: Dict[str, Site]
    apply_key: Optional[Any] = None


def _empty_curve() -> np.ndarray:
    return np.zeros((0,), np.float32)


@dataclasses.dataclass
class BlockReport:
    name: str
    err_before: float
    err_after: float
    iters: int
    seconds: float
    engine: str = "scan"
    steps_per_s: float = 0.0
    # Per-step loss/MSE trajectories (stacked scan outputs). Real fields —
    # not stapled-on attributes — so report serialization round-trips them.
    loss_curve: Any = dataclasses.field(default_factory=_empty_curve)
    mse_curve: Any = dataclasses.field(default_factory=_empty_curve)

    _CURVES = ("loss_curve", "mse_curve")

    def to_json(self) -> dict:
        """JSON-safe dict: trajectories as float lists (checkpoint meta)."""
        d = dataclasses.asdict(self)
        for k in self._CURVES:
            d[k] = np.asarray(getattr(self, k), np.float32).tolist()
        return d

    @classmethod
    def from_json(cls, d: dict) -> "BlockReport":
        """Inverse of ``to_json``, tolerating report-schema drift: unknown
        keys from a newer writer are dropped, missing keys fall back to the
        field defaults."""
        known = {f.name for f in dataclasses.fields(cls)}
        kept = {k: v for k, v in d.items() if k in known}
        for k in cls._CURVES:
            if k in kept:
                kept[k] = np.asarray(kept[k], np.float32)
        return cls(**kept)


# ------------------------------------------------------------- engine stats
@dataclasses.dataclass
class EngineStats:
    """Trace/compile counters (incremented at jit trace time, so each count
    is an actual XLA compilation, not a call)."""
    step_compiles: int = 0
    schedule_compiles: int = 0
    teacher_compiles: int = 0
    student_compiles: int = 0
    recon_error_compiles: int = 0
    probe_compiles: int = 0  # sensitivity-probe steps (repro.allocate)
    engine_builds: int = 0
    engine_hits: int = 0

    @property
    def compile_count(self) -> int:
        return (self.step_compiles + self.schedule_compiles +
                self.teacher_compiles + self.student_compiles +
                self.recon_error_compiles + self.probe_compiles)


_STATS = EngineStats()


def engine_stats() -> EngineStats:
    return _STATS


def reset_engine_stats() -> EngineStats:
    """Zero the counters (benchmarks/tests). The compiled-step cache itself
    is NOT cleared — pair with ``clear_engine_cache`` to measure cold."""
    for f in dataclasses.fields(EngineStats):
        setattr(_STATS, f.name, f.default)
    return _STATS


def clear_engine_cache() -> None:
    _ENGINE_CACHE.clear()
    _batch_schedule.clear_cache()


@contextlib.contextmanager
def engine_scope():
    """Evict engines built inside the scope when it exits.

    ``quantize_blocks`` and the sensitivity prober (repro.allocate) wrap
    their runs in this: their blocks' ``apply_key`` tokens are fresh per
    call, so entries built under the scope can never hit again, yet their
    apply closures pin per-call constants (rope tables, encoder outputs, the
    model itself). Entries that existed before the scope are untouched."""
    _SCOPE_STACK.append(set())
    try:
        yield
    finally:
        for k in _SCOPE_STACK.pop():
            _ENGINE_CACHE.pop(k, None)


def site_plans(block: BlockHandle, recipe: QuantRecipe) -> Dict[str, SitePlan]:
    """Resolve the recipe's rules once per block: site name -> SitePlan."""
    return {name: recipe.resolve(name, site)
            for name, site in block.sites.items()}


def init_wstates(block: BlockHandle, recipe: QuantRecipe) -> Dict[str, Any]:
    out = {}
    for name, site in block.sites.items():
        plan = recipe.resolve(name, site)
        w = pth.get_path(block.params, site.path)
        out[name] = plan.method.init(w, plan.weight)
    return out


def init_astates(block: BlockHandle, recipe: QuantRecipe, x_q: jax.Array,
                 prev: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """LSQ init from observed ranges on the student stream (eager pass).

    Per-site rules apply here too: a site whose plan has ``act is None``
    (weight-only override) gets no LSQ state and stays fp. Plans are resolved
    *first*: when every site of this block resolves to ``act is None`` the
    calibration forward pass is skipped entirely.
    """
    states = dict(prev or {})
    plans = site_plans(block, recipe)
    if all(p.act is None for p in plans.values()):
        return states
    ctx = QuantCtx(mode="calib", recipe=recipe)
    block.apply(block.params, x_q, ctx)
    for name, (lo, hi) in ctx.records.items():
        plan = plans.get(name) or recipe.resolve(name)
        if plan.act is None:
            continue
        sample = jnp.asarray([lo, hi], jnp.float32)
        states[name] = lsq.init(sample, plan.act)
    return states


def _trainable_mask(wstates, astates, plans: Dict[str, SitePlan]):
    wmask = {k: plans[k].method.trainable(v) for k, v in wstates.items()}
    amask = {k: lsq.trainable(v) for k, v in astates.items()}
    return wmask, amask


def _apply_mask(grads, mask):
    return jax.tree.map(lambda g, m: g if m else jnp.zeros_like(g), grads, mask)


# ----------------------------------------------------------- step math
def _make_step_fn(apply_fn: Callable, recipe: QuantRecipe,
                  plans: Dict[str, SitePlan], a_opt_cfg: AdamConfig):
    """Single optimization step (traced inside the engine's scan body).

    ``plans`` keys the same namespace as the state dicts (the engine passes
    canonical position tokens, see _RenameCtx). Sites may carry
    heterogeneous plans (method, bits, lr): each site's rounding state is
    updated by its own method, all inside one tree-wide Adam update whose
    per-leaf lr_scale carries the rule-overridden learning rates.

    ``sw`` (optional, leading-sample-axis weights from
    ``assemble_global_batch``) turns the MSE into a weighted global-batch
    mean — dropped-shard samples carry weight 0, so the straggler policy's
    B / weight.sum() loss rescale happens here. ``sw=None`` keeps the plain
    ``jnp.mean`` bit-identical to the recorded trajectories.
    """

    def loss_fn(params, wstates, astates, x_q, y_fp, sw, step, key, salts):
        ctx = QuantCtx(mode="recon", recipe=recipe, wstates=wstates,
                       astates=astates, key=key, plans=plans, site_salts=salts)
        y = apply_fn(params, x_q, ctx)
        se = jnp.square(y.astype(jnp.float32) - y_fp.astype(jnp.float32))
        if sw is None:
            mse = jnp.mean(se)
        else:
            per = jnp.mean(se.reshape(se.shape[0], -1), axis=1)
            w = sw.astype(jnp.float32)
            mse = jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1e-9)
        reg = jnp.float32(0.0)
        for name, st in wstates.items():
            plan = plans[name]
            reg = reg + plan.method.loss_extra(st, plan.weight, step, recipe)
        return mse + reg, mse

    def step_fn(params, wstates, astates, wopt, aopt, x_q, y_fp, sw, step,
                key, salts):
        (loss, mse), (gw, ga) = jax.value_and_grad(loss_fn, argnums=(1, 2),
                                                   has_aux=True)(
            params, wstates, astates, x_q, y_fp, sw, step, key, salts)
        wmask, amask = _trainable_mask(wstates, astates, plans)
        gw = _apply_mask(gw, wmask)
        w_lr = {k: jax.tree.map(lambda _: plans[k].lr, v)
                for k, v in wstates.items()}
        wstates, wopt, _ = adam_update(gw, wopt, wstates, _W_BASE_CFG,
                                       lr_scale=w_lr)
        wstates = {k: plans[k].method.project(v) for k, v in wstates.items()}
        if astates:
            ga = _apply_mask(ga, amask)
            astates, aopt, _ = adam_update(ga, aopt, astates, a_opt_cfg)
            astates = {k: lsq.project(v) for k, v in astates.items()}
        return wstates, astates, wopt, aopt, loss, mse

    return step_fn


def recon_error(block: BlockHandle, recipe: QuantRecipe, wstates, astates,
                x_q, y_fp) -> float:
    ctx = QuantCtx(mode="recon", recipe=recipe, wstates=wstates, astates=astates,
                   key=jax.random.key(recipe.seed), drop_enabled=False)
    y = block.apply(block.params, x_q, ctx)
    return float(jnp.mean(jnp.square(y.astype(jnp.float32) - y_fp.astype(jnp.float32))))


# ------------------------------------------------- canonicalization + cache
class _RenameCtx:
    """Ctx proxy translating model-side site names to canonical tokens.

    The model's apply closure bakes real site-name strings ("layers.3.wq");
    translating them at the ctx boundary lets one compiled step serve every
    structurally identical block: state dicts, plan lookups and QDrop salt
    lookups all key on the canonical token. Names outside the mapping pass
    through untouched (they hold no rounding/LSQ state here, so they stay fp).
    """
    __slots__ = ("_ctx", "_map")

    def __init__(self, ctx: QuantCtx, mapping: Dict[str, str]):
        self._ctx = ctx
        self._map = mapping

    def linear(self, name, *args, **kwargs):
        return self._ctx.linear(self._map.get(name, name), *args, **kwargs)

    def conv2d(self, name, *args, **kwargs):
        return self._ctx.conv2d(self._map.get(name, name), *args, **kwargs)

    def get_weight(self, name, *args, **kwargs):
        return self._ctx.get_weight(self._map.get(name, name), *args, **kwargs)

    def __getattr__(self, item):
        return getattr(self._ctx, item)


def _canon_names(block: BlockHandle) -> Dict[str, str]:
    """real site name -> position-based canonical token (sorted order, so
    structurally identical blocks map corresponding sites to the same
    token)."""
    return {rn: f"~s{i}" for i, rn in enumerate(sorted(block.sites))}


def _salt(name: str) -> jax.Array:
    # must match context.site_key's crc32 constant so scanned and legacy
    # engines consume the identical QDrop key stream
    return jnp.uint32(zlib.crc32(name.encode()) & 0x7FFFFFFF)


@dataclasses.dataclass
class _Engine:
    """Compiled callables for one equivalence class of blocks. Holds a strong
    ref to the exemplar apply fn so id()-keyed cache entries stay valid."""
    apply: Callable
    run_chunk: Callable
    teacher: Callable
    student: Callable
    recon_err: Callable


_ENGINE_CACHE: "collections.OrderedDict[Any, _Engine]" = collections.OrderedDict()
_ENGINE_CACHE_MAX = 64
# Engines built inside a quantize_blocks call are evicted when it returns:
# apply_key tokens are fresh per quant_blocks call, so those entries can
# never hit again, yet their closures pin per-call constants (rope tables,
# encoder outputs, the model itself). Entries from direct reconstruct_block
# use stay in the bounded LRU.
_SCOPE_STACK: List[set] = []


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _batch_schedule(key, iters: int, n: int, bs: int):
    """Epoch key/minibatch-index schedule, built on device in one dispatch.

    Replays the legacy loop's RNG exactly: per step ``key, k1, k2 =
    split(key, 3)``, gather indices drawn with ``choice(k1, n, (bs,),
    replace=False)``. Full-batch recon (bs == n) skips the gather tensor
    entirely — the engine reuses x_q/y_fp as-is.
    """
    _STATS.schedule_compiles += 1

    def split3(k, _):
        k, k1, k2 = jax.random.split(k, 3)
        return k, (k1, k2)

    _, (k1s, k2s) = jax.lax.scan(split3, key, None, length=iters)
    if bs == n:
        return None, k2s
    idx = jax.vmap(
        lambda k: jax.random.choice(k, n, (bs,), replace=False))(k1s)
    return idx, k2s


def _engine_key(block: BlockHandle, recipe: QuantRecipe,
                plans: Dict[str, SitePlan], canon: Dict[str, str],
                mesh=None):
    akey = (block.apply_key if block.apply_key is not None
            else ("~obj", id(block.apply)))
    sites = tuple(sorted(
        (canon[rn], s.kind, s.batch_dims, plans[rn].cache_key())
        for rn, s in block.sites.items()))
    # run_chunk closures bake the mesh (minibatch sharding constraints), so
    # the same block under a different mesh needs a distinct engine
    return (akey, sites, recipe, mesh)


def _constrain_stream(x, mesh):
    """Pin a leading-sample-axis tensor to the data-parallel stream spec
    (inside a trace, so the shape is static)."""
    from repro.launch.sharding import stream_sharding
    return jax.lax.with_sharding_constraint(x, stream_sharding(mesh,
                                                               x.shape[0]))


def _build_engine(block: BlockHandle, recipe: QuantRecipe,
                  plans_c: Dict[str, SitePlan],
                  mapping: Dict[str, str], mesh=None) -> _Engine:
    block_apply = block.apply

    def apply_c(p, x, ctx):
        return block_apply(p, x, _RenameCtx(ctx, mapping))

    a_opt_cfg = AdamConfig(lr=recipe.lr_lsq)
    step = _make_step_fn(apply_c, recipe, plans_c, a_opt_cfg)

    def run_chunk(params, wstates, astates, wopt, aopt, x_q, y_fp,
                  idx, k2s, steps, salts, sweight):
        _STATS.step_compiles += 1
        if mesh is not None:
            # carried states are replicated; the gather below re-shards the
            # minibatch over the data axes so the per-step loss is a mean
            # over the global batch (gradients psum automatically)
            from repro.launch.sharding import replicated
            repl = replicated(mesh)
            wstates, astates, wopt, aopt = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(a, repl),
                (wstates, astates, wopt, aopt))

        def body(carry, xs):
            ws, as_, wo, ao = carry
            if idx is None:
                k2, stp = xs
                xb, yb, wb = x_q, y_fp, sweight
            else:
                ix, k2, stp = xs
                xb = jnp.take(x_q, ix, axis=0)
                yb = jnp.take(y_fp, ix, axis=0)
                wb = None if sweight is None else jnp.take(sweight, ix,
                                                           axis=0)
                if mesh is not None:
                    xb = _constrain_stream(xb, mesh)
                    yb = _constrain_stream(yb, mesh)
            ws, as_, wo, ao, loss, mse = step(params, ws, as_, wo, ao,
                                              xb, yb, wb, stp, k2, salts)
            return (ws, as_, wo, ao), (loss, mse)

        xs = (k2s, steps) if idx is None else (idx, k2s, steps)
        carry, traj = jax.lax.scan(body, (wstates, astates, wopt, aopt), xs)
        return (*carry, *traj)

    def teacher(params, x):
        _STATS.teacher_compiles += 1
        return apply_c(params, x, QuantCtx(mode="fp"))

    def student(params, x, astates):
        _STATS.student_compiles += 1

        def run(params, x, astates):
            ctx = QuantCtx(mode="deploy", recipe=recipe, astates=astates,
                           plans=plans_c)
            return apply_c(params, x, ctx)

        from repro.kernels import ops as kops
        if mesh is None or kops.resolve_backend()[0] != "pallas":
            return run(params, x, astates)
        # the partitioner cannot split a Pallas kernel, so each device runs
        # the block on its own shard of the stream, with the weights and
        # activation states replicated. That equals the unsharded block
        # only if every sample's output depends on that sample alone, which
        # expert (MoE) blocks break: they route tokens in groups formed
        # over the whole batch. check_vma is off because Pallas outputs
        # carry no varying-axes type.
        if any(s.batch_dims for s in block.sites.values()):
            raise NotImplementedError(
                f"{block.name}: data-parallel PTQ of an expert block on "
                "Pallas deploy kernels would route tokens per shard, not "
                "over the whole batch; run it with mesh=None")
        from jax.sharding import PartitionSpec as P

        from repro.launch.sharding import stream_spec
        spec = stream_spec(x.shape[0], mesh)
        return jax.shard_map(run, mesh=mesh, in_specs=(P(), spec, P()),
                             out_specs=spec, check_vma=False)(params, x,
                                                              astates)

    def recon_err(params, wstates, astates, x_q, y_fp):
        _STATS.recon_error_compiles += 1
        ctx = QuantCtx(mode="recon", recipe=recipe, wstates=wstates,
                       astates=astates, key=jax.random.key(recipe.seed),
                       drop_enabled=False, plans=plans_c)
        y = apply_c(params, x_q, ctx)
        return jnp.mean(jnp.square(y.astype(jnp.float32) -
                                   y_fp.astype(jnp.float32)))

    # Carried states are de-aliased before the first chunk, so donation is
    # safe (the old "same buffer twice" rejection came from constant-dedup
    # aliasing identical init buffers across sites).
    return _Engine(
        apply=block_apply,
        run_chunk=jax.jit(run_chunk, donate_argnums=(1, 2, 3, 4)),
        teacher=jax.jit(teacher),
        student=jax.jit(student),
        recon_err=jax.jit(recon_err),
    )


def _get_engine(block: BlockHandle, recipe: QuantRecipe,
                plans: Dict[str, SitePlan], mesh=None
                ) -> Tuple[_Engine, Dict[str, str]]:
    canon = _canon_names(block)
    key = _engine_key(block, recipe, plans, canon, mesh)
    eng = _ENGINE_CACHE.get(key)
    if eng is not None:
        _STATS.engine_hits += 1
        _ENGINE_CACHE.move_to_end(key)
        return eng, canon
    eng = _build_engine(block, recipe,
                        {canon[rn]: plans[rn] for rn in block.sites}, canon,
                        mesh)
    _STATS.engine_builds += 1
    _ENGINE_CACHE[key] = eng
    if _SCOPE_STACK:
        _SCOPE_STACK[-1].add(key)
    while len(_ENGINE_CACHE) > _ENGINE_CACHE_MAX:
        _ENGINE_CACHE.popitem(last=False)
    return eng, canon


def _dealias(*trees):
    """Copy every leaf into its own freshly materialized buffer. JAX
    constant-dedup can hand several sites the same underlying buffer for
    identical init arrays (e.g. all-zero zero points); XLA rejects donating
    one buffer twice, so the carried states get unique storage before
    entering the donated scan."""
    return tuple(jax.tree.map(lambda x: jnp.array(x, copy=True), t)
                 for t in trees)


# ----------------------------------------------------------------- engines
def _place_sharded(mesh, x_q, y_fp, sample_weight, state_trees):
    """Device placement for a sharded run: calibration streams over the data
    axes on the leading sample axis, everything the scan carries replicated.
    All arrays end up committed to the same mesh so jitted calls never mix
    device sets."""
    from repro.launch.sharding import replicated, stream_sharding
    stream = stream_sharding(mesh, x_q.shape[0])
    x_q = jax.device_put(x_q, stream)
    y_fp = jax.device_put(y_fp, stream)
    if sample_weight is not None:
        sample_weight = jax.device_put(sample_weight, stream)
    state_trees = jax.device_put(state_trees, replicated(mesh))
    return x_q, y_fp, sample_weight, state_trees


def _run_scan(block: BlockHandle, recipe: QuantRecipe,
              plans: Dict[str, SitePlan], wstates, astates_all, x_q, y_fp,
              key, chunk: int, mesh=None, sample_weight=None):
    """Scan-fused engine: returns (wstates, astates_all, err0, err1,
    loop_seconds, loss_curve, mse_curve)."""
    eng, canon = _get_engine(block, recipe, plans, mesh)
    inv = {c: r for r, c in canon.items()}
    c_w = {canon[r]: v for r, v in wstates.items()}
    c_a = {canon[r]: astates_all[r] for r in block.sites if r in astates_all}
    salts = {canon[r]: _salt(r) for r in block.sites}

    a_opt_cfg = AdamConfig(lr=recipe.lr_lsq)
    wopt = adam_init(c_w, _W_BASE_CFG)
    aopt = adam_init(c_a, a_opt_cfg)
    c_w, c_a, wopt, aopt = _dealias(c_w, c_a, wopt, aopt)

    n = x_q.shape[0]
    bs = min(recipe.batch_size, n)
    idx, k2s = _batch_schedule(key, recipe.iters, n, bs)
    steps = jnp.arange(recipe.iters, dtype=jnp.int32)
    if mesh is not None:
        x_q, y_fp, sample_weight, placed = _place_sharded(
            mesh, x_q, y_fp, sample_weight,
            (c_w, c_a, wopt, aopt, salts, idx, k2s, steps))
        c_w, c_a, wopt, aopt, salts, idx, k2s, steps = placed

    # err0 runs on the (possibly mesh-placed) states but outside the timed
    # window: loop_s / steps_per_s measure the optimization loop itself
    err0 = float(eng.recon_err(block.params, c_w, c_a, x_q, y_fp))

    chunk = max(1, min(chunk, recipe.iters))
    sw = Stopwatch()
    losses, mses = [], []
    it = 0
    while it < recipe.iters:
        sl = slice(it, it + min(chunk, recipe.iters - it))
        # host-side span around the compiled dispatch: the traced run_chunk
        # jaxpr is identical with telemetry on or off (tier-1 pins zero
        # added compiles). sync= folds device completion into the span so
        # per-chunk time is honest, matching the block_until_ready below.
        with TELEMETRY.span("recon.chunk", block=block.name, start=it,
                            steps=sl.stop - it,
                            stream=str(getattr(getattr(x_q, "sharding", None),
                                               "spec", None))) as sp:
            c_w, c_a, wopt, aopt, lo, ms = eng.run_chunk(
                block.params, c_w, c_a, wopt, aopt, x_q, y_fp,
                None if idx is None else idx[sl], k2s[sl], steps[sl], salts,
                sample_weight)
            sp.block_on(ms)
        losses.append(lo)
        mses.append(ms)
        it = sl.stop
    if mses:
        jax.block_until_ready(mses[-1])
    loop_s = sw.elapsed_s()

    err1 = float(eng.recon_err(block.params, c_w, c_a, x_q, y_fp))
    w_out = {inv[c]: v for c, v in c_w.items()}
    a_out = dict(astates_all)
    a_out.update({inv[c]: v for c, v in c_a.items()})
    return (w_out, a_out, err0, err1, loop_s,
            jnp.concatenate(losses) if losses else jnp.zeros((0,)),
            jnp.concatenate(mses) if mses else jnp.zeros((0,)))


def reconstruct_block(block: BlockHandle, recipe: QuantRecipe, x_q: jax.Array,
                      y_fp: jax.Array, key: jax.Array,
                      astates: Optional[Dict[str, Any]] = None, *,
                      chunk: int = DEFAULT_CHUNK, mesh=None,
                      sample_weight: Optional[jax.Array] = None,
                      ) -> Tuple[Dict[str, Any], Dict[str, Any], BlockReport]:
    """Optimize rounding (+LSQ) states for one block. Returns final states.

    Runs the fused, compile-cached device loop. The RNG stream matches the
    removed per-iteration legacy loop bit-for-bit (trajectory parity is
    pinned against recorded fixtures in tests/test_recon_engine.py). The
    report carries the measured loop throughput (``steps_per_s``) and the
    loss/mse trajectories (``rep.loss_curve`` / ``rep.mse_curve``, stacked
    device arrays).

    ``mesh``: optional ``jax.sharding.Mesh`` — calibration tensors are
    sharded over the mesh's data axes on the leading sample axis and the
    optimization states replicated (see the module docstring; the RNG stream
    and trajectories match the unsharded run). ``sample_weight``: optional
    (N,) per-sample loss weights (``assemble_global_batch``), consumed as a
    weighted global-batch mean; None keeps the plain mean bit-identical to
    the recorded trajectories.
    """
    sw = Stopwatch()
    with TELEMETRY.span("recon.block", block=block.name, iters=recipe.iters):
        plans = site_plans(block, recipe)
        wstates = init_wstates(block, recipe)
        astates = astates if astates is not None else init_astates(
            block, recipe, x_q)

        wstates, astates, err0, err1, loop_s, loss_curve, mse_curve = \
            _run_scan(block, recipe, plans, wstates, astates, x_q, y_fp,
                      key, chunk, mesh, sample_weight)

    return wstates, astates, BlockReport(
        block.name, err0, err1, recipe.iters, sw.elapsed_s(),
        steps_per_s=recipe.iters / max(loop_s, 1e-9),
        loss_curve=loss_curve, mse_curve=mse_curve)


def finalize_block(block: BlockHandle, recipe: QuantRecipe, wstates,
                   as_qtensor: bool = True) -> Any:
    """Replace quantized leaves with QTensor (deploy) or dequant arrays.

    Each site exports with its own plan, so one block may hold QTensors of
    different bit-widths (mixed-precision recipes)."""
    from repro.core.qtensor import dequantize_qtensor
    params = block.params
    for name, site in block.sites.items():
        plan = recipe.resolve(name, site)
        w = pth.get_path(params, site.path)
        qt = plan.method.export(w, wstates[name], plan.weight, dtype=w.dtype)
        params = pth.set_path(params, site.path, qt if as_qtensor else
                              dequantize_qtensor(qt))
    return params


# --------------------------------------------------------------- probe entry
def probe_teacher(block: BlockHandle, recipe: QuantRecipe, mesh=None):
    """Compiled teacher for sensitivity-probe passes (repro.allocate).

    Shares the engine cache, so the L structurally identical blocks of a
    transformer compile one teacher. Call inside ``engine_scope()`` — probe
    passes build engines whose closures pin per-call constants. ``mesh``
    keys the engine like the recon entry points, so a sharded probe pass
    stays compile-flat under the same cache."""
    eng, _ = _get_engine(block, recipe, site_plans(block, recipe), mesh)
    return eng.teacher


def count_probe_compile() -> None:
    """Called by probe-step traces at trace time (repro.allocate), so
    ``engine_stats().probe_compiles`` counts actual XLA compilations."""
    _STATS.probe_compiles += 1


# --------------------------------------------------------------------- driver
def _explode_layerwise(block: BlockHandle, recipe: QuantRecipe, x_q):
    """Yield per-site sub-blocks for recon='layer' (AdaRound-style).

    Each site becomes a standalone linear/conv reconstruction problem whose
    inputs are captured from the block execution — one capture pass records
    every site's input, reused for all yielded sub-blocks.
    """
    ctx_q = QuantCtx(mode="capture", recipe=recipe)
    block.apply(block.params, x_q, ctx_q)
    for name, site in block.sites.items():
        x_site = ctx_q.records[name][0]
        w = pth.get_path(block.params, site.path)

        if site.kind == "conv":
            def apply_fn(p, x, ctx, _n=name):
                return ctx.conv2d(_n, x, p["w"])
        elif site.batch_dims:
            def apply_fn(p, x, ctx, _n=name, _bd=site.batch_dims):
                return ctx.linear(_n, x, p["w"], batch_dims=_bd)
        else:
            def apply_fn(p, x, ctx, _n=name):
                return ctx.linear(_n, x, p["w"])

        sub = BlockHandle(name=f"{block.name}/{name}", params={"w": w},
                          apply=apply_fn,
                          sites={name: Site(path=("w",), kind=site.kind,
                                            batch_dims=site.batch_dims)},
                          apply_key=("~layerwise", site.kind, site.batch_dims))
        yield name, site, sub, x_site


def quantize_blocks(blocks: List[BlockHandle], recipe: QuantRecipe,
                    x0: jax.Array, key: Optional[jax.Array] = None,
                    as_qtensor: bool = True,
                    checkpoint_dir: Optional[str] = None,
                    progress: Optional[Callable[[str], None]] = None, *,
                    chunk: int = DEFAULT_CHUNK,
                    allocation: Optional[dict] = None,
                    mesh=None,
                    sample_weight: Optional[jax.Array] = None,
                    ) -> Tuple[List[Any], Dict[str, Any], List[BlockReport]]:
    """Sequentially quantize a chain of blocks (the paper's full procedure).

    Returns (per-block finalized params, astates, reports). If
    ``checkpoint_dir`` is set, per-block state is saved after each block and
    a crashed run resumes at the first un-finalized block. Teacher/student/
    recon-step compilations are shared across structurally identical blocks
    (see ``BlockHandle.apply_key``).

    ``allocation``: optional summary of the bit allocation that emitted the
    recipe's rules (``AllocationReport.meta()`` from repro.allocate). It is
    recorded in every per-block checkpoint; a resume whose recipe or
    allocation no longer matches fails loudly, naming the allocation.

    ``mesh``: optional ``jax.sharding.Mesh`` for data-parallel calibration —
    the activation streams (x_fp / x_q / teacher outputs) are sharded over
    the mesh's data axes on the leading sample axis, optimization states
    replicated; trajectories match the unsharded run (module docstring).
    ``sample_weight``: optional (N,) per-sample loss weights aligned with
    ``x0``'s leading axis (``assemble_global_batch``'s straggler mask).
    """
    with engine_scope():
        # engines built here are released on exit: their apply closures pin
        # per-call constants and their apply_key tokens can never hit again
        return _quantize_blocks(blocks, recipe, x0, key, as_qtensor,
                                checkpoint_dir, progress, chunk, allocation,
                                mesh, sample_weight)


def _quantize_blocks(blocks, recipe, x0, key, as_qtensor, checkpoint_dir,
                     progress, chunk, allocation, mesh=None,
                     sample_weight=None):
    key = key if key is not None else jax.random.key(recipe.seed)
    ckpt = None
    if checkpoint_dir is not None:
        from repro.checkpoint.checkpoint import PTQCheckpointer
        ckpt = PTQCheckpointer(checkpoint_dir)

    if mesh is not None:
        from repro.launch.sharding import stream_sharding
        x0 = jax.device_put(x0, stream_sharding(mesh, x0.shape[0]))

    x_fp = x0
    x_q = x0
    astates: Dict[str, Any] = {}
    finalized: List[Any] = []
    reports: List[BlockReport] = []

    start = 0
    if ckpt is not None:
        resumed = ckpt.load(blocks, recipe, allocation=allocation)
        if resumed is not None:
            start, finalized, astates, reports, x_fp, x_q = resumed
            if mesh is not None:
                # checkpointed streams come back as single-device arrays;
                # re-place them or the resumed run loses the sharding (and
                # recompiles every engine for the replicated layout)
                from repro.launch.sharding import stream_sharding
                x_fp = jax.device_put(x_fp,
                                      stream_sharding(mesh, x_fp.shape[0]))
                x_q = jax.device_put(x_q,
                                     stream_sharding(mesh, x_q.shape[0]))

    def advance_student(block, eng, canon, params, x):
        a_c = {canon[r]: astates[r] for r in block.sites if r in astates}
        return eng.student(params, x, a_c)

    for i in range(len(blocks)):
        block = blocks[i]
        eng, canon = _get_engine(block, recipe, site_plans(block, recipe),
                                 mesh)
        y_fp = eng.teacher(block.params, x_fp)
        if i < start:
            # replay streams from checkpointed finalized params
            x_q = advance_student(block, eng, canon, finalized[i], x_q)
            x_fp = y_fp
            continue
        key, bkey = jax.random.split(key)
        astates = init_astates(block, recipe, x_q, prev=astates)

        if recipe.recon == "layer":
            wstates_all: Dict[str, Any] = {}
            for name, site, sub, x_site in _explode_layerwise(block, recipe,
                                                              x_q):
                sub_eng, _ = _get_engine(sub, recipe, site_plans(sub, recipe),
                                         mesh)
                y_site = sub_eng.teacher(sub.params, x_site)
                # fold the site's identity into the key: sibling sites must
                # draw independent minibatch schedules (sharing bkey gave
                # every site of a block the same gather indices)
                skey = jax.random.fold_in(bkey, _salt(name))
                ws, a_sub, rep = reconstruct_block(sub, recipe, x_site, y_site,
                                                   skey, astates=dict(astates),
                                                   chunk=chunk, mesh=mesh,
                                                   sample_weight=sample_weight)
                astates.update(a_sub)
                wstates_all[name] = ws[name]
                reports.append(rep)
            wstates = wstates_all
        else:
            wstates, astates, rep = reconstruct_block(block, recipe, x_q, y_fp,
                                                      bkey, astates=astates,
                                                      chunk=chunk, mesh=mesh,
                                                      sample_weight=sample_weight)
            reports.append(rep)

        new_params = finalize_block(block, recipe, wstates, as_qtensor=as_qtensor)
        finalized.append(new_params)
        x_q = advance_student(block, eng, canon, new_params, x_q)
        x_fp = y_fp
        if progress:
            progress(f"[{i + 1}/{len(blocks)}] {block.name} "
                     f"err {reports[-1].err_before:.3e} -> {reports[-1].err_after:.3e}")
        if ckpt is not None:
            plan_meta = [{n: p.summary()
                          for n, p in site_plans(b, recipe).items()}
                         for b in blocks[:i + 1]]
            ckpt.save(i + 1, finalized, astates, reports, x_fp, x_q,
                      plans=plan_meta, engine="scan", allocation=allocation)

    return finalized, astates, reports
