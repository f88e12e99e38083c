"""Decoder-only transformer LM covering the dense / moe / vlm families
(qwen2.5, smollm, granite, olmo, llama4-scout, deepseek-v3, phi3-vision).

Layers are stored stacked (L, ...) and executed with lax.scan (+ optional
jax.checkpoint) so HLO stays small even for the 61-layer/671B dry-run config.
Heterogeneous stacks (deepseek's leading dense layers) use two scans.

Every matmul routes through QuantCtx so the same code runs fp pretraining,
PTQ reconstruction, and int-weight serving.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.core.context import QuantCtx
from repro.core.reconstruct import BlockHandle, Site
from repro.models import attention as attn
from repro.models import common, mla, moe
from repro.serve import kv as skv

MTP_WEIGHT = 0.3

# promoted to repro.serve.kv (shared with encdec + the serving engine);
# kept as module aliases for callers of the original private names
_kv_quantize = skv.kv_quantize
_kv_dequantize = skv.kv_dequantize


def _cache_write(buf, li, pos, val):
    """Insert one token's (B, 1, ...) entry into layer ``li`` of a
    (L, B, Smax, ...) cache at the scalar ``pos`` (uniform batch)."""
    return jax.lax.dynamic_update_slice(
        buf, val.astype(buf.dtype)[None], (li, 0, pos) + (0,) * (buf.ndim - 3))


def _cache_append(cache, nm, li, pos, val):
    """Append one token to ``cache[nm]``'s layer ``li`` and return that
    layer after the write. ``pos`` is a scalar (uniform batch) or (B,)
    (serving slots, each at its own depth; see ``skv.append_token``)."""
    if pos.ndim:
        cache[nm], layer = skv.append_token(cache[nm], li, pos, val,
                                            skv.append_form(cache))
        return layer
    cache[nm] = _cache_write(cache[nm], li, pos, val)
    return jax.lax.dynamic_index_in_dim(cache[nm], li, 0, False)


def _layer_names(layers, layer_off: int):
    """Canonical per-layer site prefixes (``layers.<i>``, global index — the
    names ``quant_blocks`` gave the sites) for an unrolled mixed-precision
    layer list; a stacked scan shares one traced body, so it keeps the
    layer-agnostic prefix."""
    if not isinstance(layers, (list, tuple)):
        return None
    return [f"layers.{layer_off + i}" for i in range(len(layers))]


# ----------------------------------------------------------------- params
def _attn_params(key, cfg, dtype) -> dict:
    if cfg.use_mla:
        return mla.mla_params(key, cfg, dtype)
    ks = jax.random.split(key, 4)
    D, H, Hkv, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = D**-0.5
    p = {
        "wq": jax.random.normal(ks[0], (D, H * Dh), dtype) * s,
        "wk": jax.random.normal(ks[1], (D, Hkv * Dh), dtype) * s,
        "wv": jax.random.normal(ks[2], (D, Hkv * Dh), dtype) * s,
        "wo": jax.random.normal(ks[3], (H * Dh, D), dtype) * (H * Dh) ** -0.5,
    }
    if cfg.attn_bias:
        p["bq"] = jnp.zeros((H * Dh,), dtype)
        p["bk"] = jnp.zeros((Hkv * Dh,), dtype)
        p["bv"] = jnp.zeros((Hkv * Dh,), dtype)
    return p


def _layer_params(key, cfg, dtype, kind: str) -> dict:
    """kind: dense | moe."""
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": common.norm_params(cfg.norm, cfg.d_model, dtype),
        "attn": _attn_params(k1, cfg, dtype),
        "ln2": common.norm_params(cfg.norm, cfg.d_model, dtype),
    }
    if kind == "moe":
        p["mlp"] = moe.moe_params(k2, cfg, dtype)
    else:
        p["mlp"] = common.mlp_params(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return {k: v for k, v in p.items() if v is not None}


def _stacked(key, cfg, dtype, kind: str, n: int) -> dict:
    """Stacked (n, ...) layer params with independent per-layer randomness
    (vmap over keys keeps this eval_shape-safe for the dry-run)."""
    ks = jax.random.split(key, n)
    return jax.vmap(lambda k: _layer_params(k, cfg, dtype, kind))(ks)


class TransformerLM:
    def __init__(self, cfg):
        self.cfg = cfg

    # ------------------------------------------------------------- init
    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        ks = jax.random.split(key, 6)
        params: Dict[str, Any] = {
            "embed": jax.random.normal(ks[0], (cfg.vocab, cfg.d_model), dtype) * 0.02,
            "final_norm": common.norm_params(cfg.norm, cfg.d_model, dtype),
        }
        if params["final_norm"] is None:
            del params["final_norm"]
        n_moe = cfg.n_layers - cfg.first_dense
        if cfg.is_moe:
            if cfg.first_dense:
                params["dense_layers"] = _stacked(ks[1], cfg, dtype, "dense",
                                                  cfg.first_dense)
            params["layers"] = _stacked(ks[2], cfg, dtype, "moe", n_moe)
        else:
            params["layers"] = _stacked(ks[2], cfg, dtype, "dense", cfg.n_layers)
        if not cfg.tie_embeddings:
            params["lm_head"] = (
                jax.random.normal(ks[3], (cfg.d_model, cfg.vocab), dtype)
                * cfg.d_model**-0.5)
        if cfg.mtp:
            params["mtp"] = {
                "proj": jax.random.normal(ks[4], (2 * cfg.d_model, cfg.d_model),
                                          dtype) * (2 * cfg.d_model) ** -0.5,
                "layer": _layer_params(ks[5], cfg, dtype,
                                       "moe" if cfg.is_moe else "dense"),
                "norm": common.norm_params("rmsnorm", cfg.d_model, dtype),
            }
        return params

    # ------------------------------------------------------------ layers
    def _attn_full(self, p, x, ctx, name, sin, cos):
        cfg = self.cfg
        if cfg.use_mla:
            out, kv = mla.mla_forward(p["attn"], x, cfg, ctx, name, sin, cos)
            return out, kv
        B, S, _ = x.shape
        H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        a = p["attn"]
        q = ctx.linear(f"{name}.wq", x, a["wq"], a.get("bq")).reshape(B, S, H, Dh)
        k = ctx.linear(f"{name}.wk", x, a["wk"], a.get("bk")).reshape(B, S, Hkv, Dh)
        v = ctx.linear(f"{name}.wv", x, a["wv"], a.get("bv")).reshape(B, S, Hkv, Dh)
        q = common.apply_rope(q, sin, cos)
        k = common.apply_rope(k, sin, cos)
        o = attn.attention(q, k, v, causal=True, window=cfg.local_window,
                           chunk=cfg.attn_chunk)
        return ctx.linear(f"{name}.wo", o.reshape(B, S, H * Dh), a["wo"]), (k, v)

    def layer_apply(self, p, x, ctx, name, sin, cos, kind: str):
        """Full-sequence layer; returns (y, aux_loss, kv)."""
        cfg = self.cfg
        h = common.apply_norm(cfg.norm, x, p.get("ln1"))
        a_out, kv = self._attn_full(p, h, ctx, name, sin, cos)
        x = x + a_out * cfg.resid_mult
        h = common.apply_norm(cfg.norm, x, p.get("ln2"))
        if kind == "moe":
            m_out, aux = moe.moe_ffn(p["mlp"], h, cfg, ctx, name)
        else:
            m_out = common.mlp(p["mlp"], h, ctx, f"{name}.mlp", cfg.act)
            aux = jnp.float32(0.0)
        x = x + m_out * cfg.resid_mult
        return x, aux, kv

    def _scan_layers(self, stacked, x, ctx, sin, cos, kind, name,
                     collect_kv=False, layer_off=0):
        cfg = self.cfg

        def body(carry, p_l, name=name):
            def f(carry, p_l):
                h, aux = carry
                y, a, kv = self.layer_apply(p_l, h, ctx, name, sin, cos, kind)
                return (y, aux + a), (kv if collect_kv else None)
            if cfg.remat:
                f = jax.checkpoint(
                    f, policy=jax.checkpoint_policies.nothing_saveable)
            return f(carry, p_l)

        (x, aux), kvs = common.scan_layers(
            body, (x, jnp.float32(0.0)), stacked,
            names=_layer_names(stacked, layer_off))
        return x, aux, kvs

    # ----------------------------------------------------------- forward
    def backbone(self, params, tokens, ctx, extra_embeds=None,
                 collect_kv=False):
        """tokens (B,S) [+ optional (B,P,D) prefix embeds] -> hidden (B,S',D)."""
        cfg = self.cfg
        x = common.embed_tokens(params["embed"], tokens, cfg.emb_mult)
        if extra_embeds is not None:
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        B, S, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        sin, cos = common.rope_sin_cos(
            pos, cfg.qk_rope_dim if cfg.use_mla else cfg.head_dim,
            cfg.rope_theta)
        aux = jnp.float32(0.0)
        kvs = []
        if "dense_layers" in params:
            x, a, kv = self._scan_layers(params["dense_layers"], x, ctx, sin,
                                         cos, "dense", "dense", collect_kv)
            aux += a
            kvs.append(kv)
        kind = "moe" if cfg.is_moe else "dense"
        x, a, kv = self._scan_layers(params["layers"], x, ctx, sin, cos, kind,
                                     "layers", collect_kv,
                                     layer_off=cfg.first_dense
                                     if "dense_layers" in params else 0)
        aux += a
        kvs.append(kv)
        x = common.apply_norm(cfg.norm, x, params.get("final_norm"))
        return x, aux, kvs

    def lm_head(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["lm_head"]

    def loss(self, params, batch, ctx) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        x, aux, _ = self.backbone(params, batch["tokens"], ctx,
                                  batch.get("patch_embeds"))
        mask = batch.get("mask")
        labels = batch["labels"]
        if batch.get("patch_embeds") is not None:
            P = batch["patch_embeds"].shape[1]
            labels = jnp.pad(labels, ((0, 0), (P, 0)))
            m = jnp.pad(mask if mask is not None else
                        jnp.ones_like(batch["labels"], jnp.float32),
                        ((0, 0), (P, 0)))
            mask = m.at[:, :P].set(0.0)
        ce = common.fused_cross_entropy(x, self.lm_head(params), labels, mask,
                                        cfg.xent_chunk, cfg.logit_mult)
        metrics = {"ce": ce, "aux": aux}
        total = ce + 0.01 * aux
        if cfg.mtp:
            mtp_ce = self._mtp_loss(params, x, batch, ctx)
            metrics["mtp_ce"] = mtp_ce
            total = total + MTP_WEIGHT * mtp_ce
        return total, metrics

    def _mtp_loss(self, params, h, batch, ctx):
        """DeepSeek-style 1-depth multi-token prediction: predict t+2 from
        [h_t ; emb(t+1)] through one extra block and the shared head."""
        cfg = self.cfg
        m = params["mtp"]
        tokens = batch["tokens"]
        emb_next = common.embed_tokens(params["embed"], tokens, cfg.emb_mult)
        # align: h[:, :-1] with emb of tokens[:, 1:]
        cat = jnp.concatenate([h[:, :-1], emb_next[:, 1:]], axis=-1)
        z = ctx.linear("mtp.proj", cat, m["proj"])
        z = common.rmsnorm(z, m["norm"]["scale"])
        B, S, _ = z.shape
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        sin, cos = common.rope_sin_cos(
            pos, cfg.qk_rope_dim if cfg.use_mla else cfg.head_dim,
            cfg.rope_theta)
        z, _, _ = self.layer_apply(m["layer"], z, ctx, "mtp.layer", sin, cos,
                                   "moe" if cfg.is_moe else "dense")
        labels = batch["labels"]
        mtp_labels = jnp.pad(labels[:, 1:], ((0, 0), (0, 0)))  # already +1
        return common.fused_cross_entropy(z, self.lm_head(params), mtp_labels,
                                          None, cfg.xent_chunk, cfg.logit_mult)

    # ------------------------------------------------------------- serve
    def init_cache(self, batch: int, max_len: int, dtype=None,
                   kv_quant: bool = False):
        """kv_quant: int8 per-(token, head) absmax-quantized KV cache —
        halves the decode memory-roofline term (beyond-paper; §Perf)."""
        cfg = self.cfg
        skv.check_kv_quant_supported(cfg, kv_quant)
        dtype = dtype or jnp.dtype(cfg.dtype)
        L = cfg.n_layers
        if cfg.use_mla:
            return {
                "ckv": jnp.zeros((L, batch, max_len, cfg.kv_lora_rank), dtype),
                "kr": jnp.zeros((L, batch, max_len, cfg.qk_rope_dim), dtype),
            }
        kv_shape = (L, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        if kv_quant:
            s_shape = (L, batch, max_len, cfg.n_kv_heads, 1)
            return {
                "k": jnp.zeros(kv_shape, jnp.int8),
                "v": jnp.zeros(kv_shape, jnp.int8),
                "k_scale": jnp.zeros(s_shape, jnp.float32),
                "v_scale": jnp.zeros(s_shape, jnp.float32),
            }
        return {"k": jnp.zeros(kv_shape, dtype),
                "v": jnp.zeros(kv_shape, dtype)}

    def _all_layers(self, params):
        """(stacked params over ALL layers, kinds list) concat dense+moe."""
        cfg = self.cfg
        if "dense_layers" in params:
            return [(params["dense_layers"], "dense", cfg.first_dense),
                    (params["layers"], "moe", cfg.n_layers - cfg.first_dense)]
        kind = "moe" if cfg.is_moe else "dense"
        return [(params["layers"], kind, cfg.n_layers)]

    def prefill(self, params, tokens, cache, ctx, extra_embeds=None,
                true_len=None):
        """Run full sequence, fill cache; returns (last hidden, cache).

        ``true_len`` (B,) optionally marks each row's real prompt length
        inside a right-padded bucket: the returned hidden is gathered at
        ``true_len - 1`` instead of the last column. Causal masking makes
        hidden states at real positions bit-identical to an unpadded run
        (padded keys sit strictly in the future of every real query), so
        bucketed prefill costs no accuracy — only the padded FLOPs.
        """
        cfg = self.cfg
        x, _, kvs = self.backbone(params, tokens, ctx, extra_embeds,
                                  collect_kv=True)
        off = 0
        flat_kvs = [kv for kv in kvs if kv is not None]
        for (stack, kind, n), kv in zip(self._all_layers(params), flat_kvs):
            if cfg.use_mla:
                ckv, kr = kv  # (n,B,S,r), (n,B,S,dr)
                cache["ckv"] = jax.lax.dynamic_update_slice(
                    cache["ckv"], ckv.astype(cache["ckv"].dtype), (off, 0, 0, 0))
                cache["kr"] = jax.lax.dynamic_update_slice(
                    cache["kr"], kr.astype(cache["kr"].dtype), (off, 0, 0, 0))
            else:
                k, v = kv
                if "k_scale" in cache:
                    for nm, t in (("k", k), ("v", v)):
                        codes, scl = _kv_quantize(t)
                        cache[nm] = jax.lax.dynamic_update_slice(
                            cache[nm], codes, (off, 0, 0, 0, 0))
                        cache[f"{nm}_scale"] = jax.lax.dynamic_update_slice(
                            cache[f"{nm}_scale"], scl, (off, 0, 0, 0, 0))
                else:
                    cache["k"] = jax.lax.dynamic_update_slice(
                        cache["k"], k.astype(cache["k"].dtype),
                        (off, 0, 0, 0, 0))
                    cache["v"] = jax.lax.dynamic_update_slice(
                        cache["v"], v.astype(cache["v"].dtype),
                        (off, 0, 0, 0, 0))
            off += n
        if true_len is not None:
            B = x.shape[0]
            idx = jnp.asarray(true_len, jnp.int32) - 1
            x = x[jnp.arange(B), idx][:, None]
            return x, cache
        return x[:, -1:], cache

    def decode_step(self, params, token, cache, pos, ctx):
        """token (B,1) int32; pos int32 — scalar (uniform batch) or (B,)
        per-row absolute positions (serving slots). Returns
        (logits (B,1,V), cache)."""
        cfg = self.cfg
        pos = jnp.asarray(pos)
        if pos.ndim and cfg.use_mla:
            raise skv.unsupported(
                "mla", f"{cfg.name}: MLA decode takes a uniform scalar "
                "position; slot-based serving is not supported")
        x = common.embed_tokens(params["embed"], token, cfg.emb_mult)
        B = x.shape[0]
        pos_arr = (pos.reshape(B, 1) if pos.ndim
                   else jnp.full((B, 1), pos))
        sin, cos = common.rope_sin_cos(
            pos_arr, cfg.qk_rope_dim if cfg.use_mla else cfg.head_dim,
            cfg.rope_theta)
        off = 0
        for stack, kind, n in self._all_layers(params):
            x, cache = self._decode_scan(stack, x, cache, pos, off, n, kind,
                                         ctx, sin, cos)
            off += n
        x = common.apply_norm(cfg.norm, x, params.get("final_norm"))
        logits = (x @ self.lm_head(params).astype(x.dtype)) * cfg.logit_mult
        return logits, cache

    def _decode_scan(self, stack, x, cache, pos, layer_off, n, kind, ctx,
                     sin, cos):
        cfg = self.cfg

        def body(carry, inp, name="layers"):
            h, cache = carry
            p_l, i = inp
            li = layer_off + i
            z = common.apply_norm(cfg.norm, h, p_l.get("ln1"))
            if cfg.use_mla:
                ckv, kr = mla._kv_latent(p_l["attn"], z, cfg, ctx, name,
                                         sin, cos)
                cache["ckv"] = _cache_write(cache["ckv"], li, pos, ckv)
                cache["kr"] = _cache_write(cache["kr"], li, pos, kr)
                a_out = mla.mla_decode(
                    p_l["attn"], z, cfg, ctx, name, sin, cos,
                    jax.lax.dynamic_index_in_dim(cache["ckv"], li, 0, False),
                    jax.lax.dynamic_index_in_dim(cache["kr"], li, 0, False),
                    pos)
            else:
                B = z.shape[0]
                H, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
                a = p_l["attn"]
                q = ctx.linear(f"{name}.wq", z, a["wq"], a.get("bq")).reshape(
                    B, 1, H, Dh)
                k = ctx.linear(f"{name}.wk", z, a["wk"], a.get("bk")).reshape(
                    B, 1, Hkv, Dh)
                v = ctx.linear(f"{name}.wv", z, a["wv"], a.get("bv")).reshape(
                    B, 1, Hkv, Dh)
                q = common.apply_rope(q, sin, cos)
                k = common.apply_rope(k, sin, cos)
                if "k_scale" in cache:
                    layer = []
                    for nm, t in (("k", k), ("v", v)):
                        codes, scl = skv.kv_quantize(t)
                        layer += [
                            _cache_append(cache, nm, li, pos, codes),
                            _cache_append(cache, f"{nm}_scale", li, pos, scl)]
                    # dequant-free: scales fold in after the contractions,
                    # the cache never rematerializes in k.dtype
                    o = skv.int8_decode_attention(q, *layer, pos,
                                                  window=cfg.local_window)
                else:
                    k_l = _cache_append(cache, "k", li, pos, k)
                    v_l = _cache_append(cache, "v", li, pos, v)
                    o = attn.decode_attention(q, k_l, v_l, pos,
                                              window=cfg.local_window)
                a_out = ctx.linear(f"{name}.wo", o.reshape(B, 1, H * Dh),
                                   a["wo"])
            h = h + a_out * cfg.resid_mult
            z = common.apply_norm(cfg.norm, h, p_l.get("ln2"))
            if kind == "moe":
                m_out, _ = moe.moe_ffn(p_l["mlp"], z, cfg, ctx, name)
            else:
                m_out = common.mlp(p_l["mlp"], z, ctx, f"{name}.mlp", cfg.act)
            h = h + m_out * cfg.resid_mult
            return (h, cache), None

        (x, cache), _ = common.scan_layers(body, (x, cache), stack,
                                           jnp.arange(n),
                                           names=_layer_names(stack, layer_off))
        return x, cache

    # --------------------------------------------------------- PTQ plan
    def _layer_sites(self, kind: str) -> Dict[str, Site]:
        cfg = self.cfg
        sites: Dict[str, Site] = {}
        if cfg.use_mla:
            sites.update(mla.mla_sites("layers", cfg))
        else:
            for n in ("wq", "wk", "wv", "wo"):
                sites[f"layers.{n}"] = Site(("attn", n))
        if kind == "moe":
            sites.update(moe.moe_sites("layers", cfg))
        else:
            names = ["w_up", "w_down"] + (["w_gate"] if cfg.act == "swiglu"
                                          else [])
            sites.update({f"layers.mlp.{n}": Site(("mlp", n)) for n in names})
        return sites

    def quant_blocks(self, params, batch_tokens) -> Tuple[jax.Array, List[BlockHandle], Any]:
        """Returns (x0 hidden stream, per-layer BlockHandles, assemble_fn).

        assemble_fn(finalized_list) -> params with QTensor leaves restacked.
        """
        cfg = self.cfg
        x0 = common.embed_tokens(params["embed"], batch_tokens, cfg.emb_mult)
        B, S = batch_tokens.shape
        # batch-size-1 rope tables broadcast over any recon minibatch size
        pos = jnp.arange(S)[None]
        sin, cos = common.rope_sin_cos(
            pos, cfg.qk_rope_dim if cfg.use_mla else cfg.head_dim,
            cfg.rope_theta)
        blocks = []
        segs = self._all_layers(params)
        gi = 0  # global layer index across segments -> stable site names
        # fresh per call: apply closures bake this call's rope tables, so the
        # compiled-step share group must not leak across quant_blocks calls
        call_token = object()
        for seg_i, (stack, kind, n) in enumerate(segs):
            for i in range(n):
                p_l = jax.tree.map(lambda a: a[i], stack)
                # canonical site naming "layers.<i>.<site>" (shared across
                # model families) so recipe rules like "layers.0.*" are
                # portable; per-layer unique names also keep LSQ activation
                # steps learned per layer (paper's setup), not shared
                bname = f"layers.{gi}"
                gi += 1
                raw_sites = self._layer_sites(kind)
                sites = {k.replace("layers", bname, 1): v
                         for k, v in raw_sites.items()}

                def apply_fn(p, x, ctx, _kind=kind, _bn=bname):
                    y, _, _ = self.layer_apply(p, x, ctx, _bn, sin, cos, _kind)
                    return y

                blocks.append(BlockHandle(name=bname, params=p_l,
                                          apply=apply_fn, sites=sites,
                                          apply_key=(call_token, kind)))

        def assemble(finalized):
            out = dict(params)
            idx = 0
            for seg_i, (stack, kind, n) in enumerate(segs):
                layers = finalized[idx:idx + n]
                idx += n
                key = ("dense_layers" if (seg_i == 0 and len(segs) > 1)
                       else "layers")
                # mixed-precision layers restack to a list (eager unroll)
                out[key] = common.stack_layers(layers)
            return out

        return x0, blocks, assemble
