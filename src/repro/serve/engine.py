"""Continuous-batching serving engine: bucketed AOT prefill + slot decode.

The engine is the production loop around the kernel-backed deploy path
(ROADMAP Open item #1, modeled on MaxText's offline inference engine):

* **Bucketed AOT prefill** — one executable per power-of-two length bucket,
  compiled ahead of time with ``jax.jit(...).lower(...).compile()``. A
  prompt is right-padded to its bucket; under the causal mask the padded
  keys contribute exactly zero at real positions, leaving only XLA
  reduction-order rounding (~1e-6; the parity test pins the envelope and
  exact greedy tokens per bucket), so bucketing costs padded FLOPs, never
  accuracy. Each prefill call packs up to ``prefill_group`` prompts of
  *different* true lengths into one batch; short groups are padded with
  dummy rows whose slot id is out of bounds, so the scatter drops them —
  group size never changes the traced shape.

* **Slot-based decode** — a fixed ``[slots, max_len]`` KV state stepped by
  a single compiled ``decode_step`` with a donated carry. Each slot keeps
  its own position; finished slots go inactive in place and are re-filled
  by the next prefill without touching the compiled graph. After
  ``__init__``, ``compile_count`` is frozen: occupancy, request count, and
  bucket mix never retrace (pinned by quantlint's ``no_retrace`` guard in
  tier-1).

* **int8 KV cache by default** (``kv_quant=True``) — quantize-on-append
  via :mod:`repro.serve.kv`, attention reads the codes directly
  (dequant-free), HBM per slot drops ~3.5x vs f32 / ~1.8x vs bf16, which
  is what converts FlexRound's weight-memory win into concurrent users.

Greedy decoding with a fixed ``max_new`` per request (offline/benchmark
serving — no early EOS release, which would need per-request stop state on
device). The host side (admission queue, detokenize thread) lives in
:mod:`repro.serve.scheduler`.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.serve_metrics import ServeMetrics
from repro.obs.sink import current_manifest
from repro.obs.telemetry import TELEMETRY, Stopwatch
from repro.serve import kv as skv
from repro.serve.smoke import serve_capability


@dataclass
class EngineConfig:
    slots: int = 4
    max_len: int = 128
    prefill_group: int = 2   # prompts packed into one prefill call
    kv_quant: bool = True    # int8 KV cache (the serving default)
    min_bucket: int = 8
    dtype: Any = None        # fp KV dtype when kv_quant=False

    def buckets(self) -> List[int]:
        """Power-of-two prefill buckets up to the largest <= max_len."""
        out, b = [], self.min_bucket
        while b <= self.max_len:
            out.append(b)
            b *= 2
        if not out:
            raise ValueError(
                f"max_len={self.max_len} below min_bucket={self.min_bucket}")
        return out


@dataclass
class SlotView:
    """Host-side mirror of one device slot (no sync needed to read it)."""
    rid: Optional[int] = None
    remaining: int = 0
    emitted: List[int] = field(default_factory=list)


# ------------------------------------------------------- traced functions
# Module-level builders so the jaxpr analyzers (repro.analysis.trace) can
# jit + trace the exact functions the engine compiles, without standing up
# a full engine: serve_prefill/serve_decode TracedEntrys run QL201 (dead
# scale invars), QL203 (donated KV-carry aliasing) and QL303 (subnormal
# KV scales) over the same graphs production serves from.

def init_state(model, cfg: EngineConfig):
    """Fresh slot state — the donated carry every compiled call threads."""
    cache = model.init_cache(cfg.slots, cfg.max_len, dtype=cfg.dtype,
                             kv_quant=cfg.kv_quant)
    return {
        "cache": cache,
        "tokens": jnp.zeros((cfg.slots, 1), jnp.int32),
        "pos": jnp.zeros((cfg.slots,), jnp.int32),
        "remaining": jnp.zeros((cfg.slots,), jnp.int32),
    }


def _greedy(model, last, params):
    logit_mult = getattr(model.cfg, "logit_mult", 1.0)
    logits = (last @ model.lm_head(params).astype(last.dtype)) * logit_mult
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)


def make_prefill(model, ctx, cfg: EngineConfig, bucket: int):
    """One bucket's prefill-insert: prefill a (group, bucket) batch into a
    fresh cache, scatter it into the slot state, emit the first token."""
    G = cfg.prefill_group

    def prefill_insert(params, state, tokens, true_len, slot_ids, max_new):
        """tokens (G, bucket) right-padded; slot_ids==slots marks a
        dummy row — every scatter below drops it, so a half-empty
        admission group traces identically to a full one."""
        fresh = model.init_cache(G, bucket, dtype=cfg.dtype,
                                 kv_quant=cfg.kv_quant)
        last, fresh = model.prefill(params, tokens, fresh, ctx,
                                    true_len=true_len)
        first = _greedy(model, last, params)  # (G,)
        cache = state["cache"]
        for nm in fresh:
            cache[nm] = cache[nm].at[:, slot_ids, :bucket].set(
                fresh[nm].astype(cache[nm].dtype), mode="drop")
        state["cache"] = cache
        state["tokens"] = state["tokens"].at[slot_ids].set(
            first[:, None], mode="drop")
        state["pos"] = state["pos"].at[slot_ids].set(
            true_len, mode="drop")
        state["remaining"] = state["remaining"].at[slot_ids].set(
            jnp.maximum(max_new - 1, 0), mode="drop")
        return state, first
    return prefill_insert


def make_decode(model, ctx, cfg: EngineConfig):
    """The single decode step across all slots (active-masked).

    Only the KV cache is a donated carry: it is the buffer whose reuse
    pays (and it is consumed exactly once, by the layer scan). The
    per-slot bookkeeping vectors (``meta``: tokens/pos/remaining, a few
    ints per slot) are read by several equations each — donating them
    would be a QL203 aliasing hazard for no memory win — so they are
    threaded undonated.
    """
    def decode(params, cache, meta):
        active = meta["remaining"] > 0
        logits, cache = model.decode_step(
            params, meta["tokens"], cache, meta["pos"], ctx)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        emitted = jnp.where(active, nxt, -1)
        return cache, {
            "tokens": jnp.where(active[:, None], nxt[:, None],
                                meta["tokens"]),
            "pos": meta["pos"] + active,
            "remaining": meta["remaining"] - active,
        }, emitted
    return decode


class ServeEngine:
    """Fixed-capacity continuous-batching engine over one model + ctx.

    Raises ``KVQuantUnsupported`` (machine-readable ``reason``) for model
    families the slot layout cannot serve — same contract the benchmarks
    and ``launch/quantize --serve`` degrade through instead of crashing.
    """

    def __init__(self, model, params, ctx, config: EngineConfig = None):
        self.cfg = config or EngineConfig()
        ok, reason = serve_capability(model, engine=True,
                                      kv_quant=self.cfg.kv_quant)
        if not ok:
            raise skv.KVQuantUnsupported(reason, f"{model.cfg.name}: cannot "
                                         "build a slot-based serve engine")
        self.model = model
        self.params = params
        self.ctx = ctx
        self.buckets = self.cfg.buckets()
        self.compile_count = 0
        # per-bucket prefill latency histograms + request lifecycle metrics
        # (host-side, always on; the old prefill_us[bucket] scalar overwrote,
        # so only the last call per bucket survived)
        self.metrics = ServeMetrics()
        self.decode_steps = 0
        self.tokens_emitted = 0
        self.slots: List[SlotView] = [SlotView()
                                      for _ in range(self.cfg.slots)]
        self._finished: List[Tuple[int, List[int]]] = []
        self._build()

    # ------------------------------------------------------------ compile
    def _build(self):
        model, ctx, c = self.model, self.ctx, self.cfg
        G = c.prefill_group
        decode = make_decode(model, ctx, c)

        self.state = init_state(model, c)
        sds = lambda x: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), x)
        p_s, st_s = sds(self.params), sds(self.state)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731

        self._prefill_exec = {}
        self.compile_us: Dict[str, float] = {}
        with TELEMETRY.span("serve.build", buckets=len(self.buckets)):
            for b in self.buckets:
                sw = Stopwatch()
                self._prefill_exec[b] = (
                    jax.jit(make_prefill(model, ctx, c, b),
                            donate_argnums=(1,))
                    .lower(p_s, st_s, i32(G, b), i32(G), i32(G), i32(G))
                    .compile())
                self.compile_count += 1
                self.compile_us[f"prefill_b{b}"] = sw.elapsed_us()
            cache_s = sds(self.state["cache"])
            meta_s = sds({k: self.state[k]
                          for k in ("tokens", "pos", "remaining")})
            sw = Stopwatch()
            self._decode_exec = (jax.jit(decode, donate_argnums=(1,))
                                 .lower(p_s, cache_s, meta_s).compile())
            self.compile_count += 1
            self.compile_us["decode"] = sw.elapsed_us()

    # ------------------------------------------------------------ serving
    def free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s.rid is None]

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds the largest bucket "
                         f"{self.buckets[-1]} (max_len={self.cfg.max_len})")

    def admit(self, requests: Sequence[Tuple[int, np.ndarray, int]],
              ) -> List[Tuple[int, int]]:
        """Prefill up to ``prefill_group`` requests into free slots.

        ``requests``: (rid, prompt tokens (int32 1-D), max_new). Returns
        the (rid, first generated token) pairs — the prefill logits already
        yield token #1, so a request costs ``1 prefill + (max_new - 1)``
        decode steps. One compiled call regardless of group fill.
        """
        if not requests:
            return []
        with TELEMETRY.span("serve.admit",
                            rids=[rid for rid, _, _ in requests]):
            return self._admit(requests)

    def _admit(self, requests):
        c = self.cfg
        G = c.prefill_group
        free = self.free_slots()
        if len(requests) > min(G, len(free)):
            raise ValueError(f"admit got {len(requests)} requests for "
                             f"{len(free)} free slots, group {G}")
        lens = [len(t) for _, t, _ in requests]
        bucket = self.bucket_for(max(lens))
        tokens = np.zeros((G, bucket), np.int32)
        true_len = np.ones((G,), np.int32)  # dummy rows: gather at index 0
        slot_ids = np.full((G,), c.slots, np.int32)  # out of bounds = drop
        max_new = np.zeros((G,), np.int32)
        for row, (rid, toks, mn) in enumerate(requests):
            n = lens[row]
            if n + mn > c.max_len:
                mn = c.max_len - n  # clamp: KV writes must stay in range
            tokens[row, :n] = toks
            true_len[row] = n
            slot_ids[row] = free[row]
            max_new[row] = max(mn, 1)
        sw = Stopwatch()
        with TELEMETRY.span("serve.prefill", bucket=bucket,
                            group=len(requests)):
            with TELEMETRY.span("serve.prefill.dispatch"):
                self.state, first = self._prefill_exec[bucket](
                    self.params, self.state, tokens, true_len, slot_ids,
                    max_new)
            with TELEMETRY.span("serve.prefill.wait"):
                first = np.asarray(first)  # host sync: first tokens needed
        self.metrics.observe_prefill(bucket, sw.elapsed_us())
        out = []
        for row, (rid, _, _) in enumerate(requests):
            s = self.slots[slot_ids[row]]
            s.rid, s.remaining, s.emitted = rid, int(max_new[row]) - 1, []
            tok = int(first[row])
            s.emitted.append(tok)
            self.tokens_emitted += 1
            out.append((rid, tok))
            if s.remaining == 0:  # max_new=1: the prefill token was it
                self._finished.append((rid, s.emitted))
                self.metrics.on_finished(rid)
                self.slots[slot_ids[row]] = SlotView()
        self.metrics.set_occupancy(self.active)
        return out

    def step(self) -> List[Tuple[int, int]]:
        """One decode step across all slots; returns (rid, token) pairs for
        slots that were active. Frees slots whose budget is exhausted."""
        meta = {k: self.state[k] for k in ("tokens", "pos", "remaining")}
        sw = Stopwatch()
        with TELEMETRY.span("serve.decode_step") as sp:
            if TELEMETRY.enabled:  # the slot scan is paid only when read
                sp.annotate(active=self.active)
            with TELEMETRY.span("serve.decode.dispatch"):
                cache, meta, emitted = self._decode_exec(
                    self.params, self.state["cache"], meta)
            self.state = {"cache": cache, **meta}
            with TELEMETRY.span("serve.decode.wait"):
                emitted = np.asarray(emitted)  # host sync: tokens consumed
        step_us = sw.elapsed_us()
        self.decode_steps += 1
        with TELEMETRY.span("serve.decode.bookkeep"):
            out = []
            for i, s in enumerate(self.slots):
                if s.rid is None:
                    continue
                tok = int(emitted[i])
                s.emitted.append(tok)
                s.remaining -= 1
                self.tokens_emitted += 1
                out.append((s.rid, tok))
                if s.remaining <= 0:
                    self._finished.append((s.rid, s.emitted))
                    self.metrics.on_finished(s.rid)
                    self.slots[i] = SlotView()
            self.metrics.observe_decode(step_us, len(out))
            self.metrics.set_occupancy(self.active)
        return out

    @property
    def active(self) -> int:
        return sum(1 for s in self.slots if s.rid is not None)

    def drain_finished(self) -> List[Tuple[int, List[int]]]:
        done, self._finished = self._finished, []
        return done

    # ------------------------------------------------------------ metrics
    def hbm_per_slot_bytes(self) -> int:
        """Bytes of KV state one slot pins, from the live cache pytree —
        the one accessor the bench row and quantlint's QL403 both read."""
        return skv.hbm_per_slot_bytes(self.state["cache"], self.cfg.slots)

    def hbm_per_slot_mib(self) -> float:
        return self.hbm_per_slot_bytes() / 2**20

    def stats(self) -> Dict[str, Any]:
        """Drain point for the engine's metrics. ``prefill_us`` is a
        per-bucket histogram summary ({count, mean, p50, p95, max} —
        every admit counts, not just the last one per bucket);
        ``requests`` carries the per-request lifecycle summaries (queue
        wait / TTFT / decode-step percentiles, occupancy, backlog,
        detok_errors); ``manifest`` stamps the run identity."""
        return {
            "compile_count": self.compile_count,
            "buckets": list(self.buckets),
            "prefill_us": self.metrics.prefill_summary(),
            "decode_steps": self.decode_steps,
            "tokens_emitted": self.tokens_emitted,
            "hbm_per_slot_bytes": self.hbm_per_slot_bytes(),
            "hbm_per_slot_MiB": self.hbm_per_slot_mib(),
            "kv_quant": self.cfg.kv_quant,
            "kv_append": skv.append_form(self.state["cache"]),
            "requests": self.metrics.request_summary(),
            "manifest": current_manifest().brief(),
        }
