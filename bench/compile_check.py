"""Compile a configuration's serving programs for a described v5e chip.

    JAX_PLATFORMS=cpu python3 bench/compile_check.py --config olmo-1b-w4a16 \
        [--buckets 64,512]

No chip needed: the TPU compiler builds the prefill-insert programs of the
given buckets and the decode program, with compiled Pallas kernels, at the
configuration's full widths and engine sizes, for one chip of a described
``v5e:2x2``. Prints each program's compile time and ``memory_analysis()``,
beside the KV cache and weight bytes the configuration implies, so a
program the chip's compiler refuses or that cannot fit shows here.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--buckets", default="")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import cost
    from bench import model as bmodel
    from bench.reference import layer_bits
    from repro.core.context import QuantCtx
    from repro.serve.engine import (EngineConfig, init_state, make_decode,
                                    make_prefill)

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = bmodel.load_config(args.config)
    model = bmodel.build(cfg)
    rcp = bmodel.recipe(cfg)
    p_shapes = jax.eval_shape(model.init, jax.random.key(0))
    q_shapes = jax.eval_shape(lambda p: bmodel.export(model, p, rcp),
                              p_shapes)
    astates = {}
    if cfg["recipe"]["a_bits"] is not None:
        for i in range(cfg["arch"]["n_layers"]):
            for s in ("wq", "wk", "wv", "wo"):
                astates[f"layers.{i}.{s}"] = {"step": jnp.float32(0.05),
                                              "beta": jnp.float32(-3.0)}
            for s in ("w_gate", "w_up", "w_down"):
                astates[f"layers.{i}.mlp.{s}"] = {
                    "step": jnp.float32(0.05), "beta": jnp.float32(-3.0)}
    ctx = QuantCtx(mode="deploy", recipe=rcp, astates=astates,
                   backend="pallas", interpret=False)
    ecfg = EngineConfig(**cfg["engine"])
    st_shapes = jax.eval_shape(lambda: init_state(model, ecfg))

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    p, st = on_chip(q_shapes), on_chip(st_shapes)
    G = ecfg.prefill_group

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    arch = cfg["arch"]
    kv = cost.kv_bytes_per_token(arch) * ecfg.slots * ecfg.max_len
    w = cost.weight_bytes(arch, [int(b) for b in layer_bits(cfg)])
    print(f"{args.config}: KV cache {kv / 1e9:.3f} GB "
          f"({ecfg.slots} slots x {ecfg.max_len}), weight codes "
          f"{w['codes'] / 1e9:.3f} GB, embedding/head {w['head'] / 1e9:.3f} GB",
          flush=True)
    buckets = ([int(b) for b in args.buckets.split(",")] if args.buckets
               else ecfg.buckets())
    for b in buckets:
        t = time.perf_counter()
        c = (jax.jit(make_prefill(model, ctx, ecfg, b), donate_argnums=(1,))
             .lower(p, st, i32(G, b), i32(G), i32(G), i32(G)).compile())
        print(f"prefill bucket {b}: compiled in {time.perf_counter() - t:.1f}s;"
              f" {c.memory_analysis()}; tpu_custom_call in HLO: "
              f"{'tpu_custom_call' in c.as_text()}", flush=True)
    meta = {k: st[k] for k in ("tokens", "pos", "remaining")}
    t = time.perf_counter()
    c = (jax.jit(make_decode(model, ctx, ecfg), donate_argnums=(1,))
         .lower(p, st["cache"], meta).compile())
    print(f"decode: compiled in {time.perf_counter() - t:.1f}s; "
          f"{c.memory_analysis()}; tpu_custom_call in HLO: "
          f"{'tpu_custom_call' in c.as_text()}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
