"""Compile-only checks: the serving kernels at smollm-135m's widths must be
accepted by the TPU compiler for a described (not attached) v5e chip, and
the engine's decode at the benchmark's engine sizes must append K/V without
relayouting the whole cache.

Interpret-mode parity (test_kernels, test_deploy_parity, QL304) cannot show
what Mosaic refuses: casts it has no lowering for, dot operand types, tile
alignment, VMEM limits. Nothing here runs, so nothing here is a timing. The
topology is described inside a fixture, never at import: only the worker
that runs these tests loads the TPU compiler.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.context import QuantCtx
from repro.kernels.dequant_matmul_w4 import (dequant_matmul_batched,
                                             dequant_matmul_w4,
                                             dequant_matmul_w8)
from repro.kernels.qmatmul_int8 import qmatmul_int8
from repro.models import build_model
from repro.serve import kv as skv
from repro.serve.engine import EngineConfig, init_state, make_decode

_CFG = get_config("smollm-135m")
#: (K, N) of the attention q/o, k/v, and MLP up/down projections
WIDTHS = ((_CFG.d_model, _CFG.d_model),
          (_CFG.d_model, _CFG.n_kv_heads * _CFG.head_dim),
          (_CFG.d_model, _CFG.d_ff), (_CFG.d_ff, _CFG.d_model))
#: decode: one row per slot; prefill: the largest bucket x prefill group
ROWS = {"decode": 4, "prefill": 128 * 2}
EXPERTS = 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _args(kernel, m, k, n, dev):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=dev)  # noqa: E731
    row = s((1, n), jnp.float32)
    if kernel == "dequant_matmul_w4":
        return (s((m, k), jnp.bfloat16), s((k // 2, n), jnp.uint8), row, row)
    if kernel == "dequant_matmul_w8":
        return (s((m, k), jnp.bfloat16), s((k, n), jnp.uint8), row, row)
    if kernel == "dequant_matmul_batched":
        e_row = s((EXPERTS, 1, n), jnp.float32)
        return (s((EXPERTS, m, k), jnp.bfloat16),
                s((EXPERTS, k // 2, n), jnp.uint8), e_row, e_row)
    scalar = s((), jnp.float32)
    return (s((m, k), jnp.int8), s((k, n), jnp.int8), scalar, scalar, row,
            row)


_FNS = {
    "dequant_matmul_w4": dequant_matmul_w4,
    "dequant_matmul_w8": dequant_matmul_w8,
    "dequant_matmul_batched": lambda x, c, sc, z: dequant_matmul_batched(
        x, c, sc, z, packed=True),
    "qmatmul_int8": qmatmul_int8,
}


@pytest.mark.parametrize("phase", sorted(ROWS))
@pytest.mark.parametrize("k,n", WIDTHS)
@pytest.mark.parametrize("kernel", sorted(_FNS))
def test_kernel_compiles_for_v5e(one_chip, kernel, k, n, phase):
    args = _args(kernel, ROWS[phase], k, n, one_chip)
    compiled = jax.jit(_FNS[kernel]).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_data_parallel_ptq_student_compiles_for_v5e_2x2(topo, monkeypatch):
    """The PTQ student runs the deploy kernels over the data-parallel
    calibration stream. The partitioner cannot split a Pallas kernel, so
    the engine must run them per shard; compiled for four chips here."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import repro.kernels.ops as kops
    from repro.configs import get_smoke_config
    from repro.core import QuantRecipe
    from repro.core import reconstruct as rec
    from repro.models import build_model

    model = build_model(get_smoke_config("smollm-135m"))
    x0, blocks, _ = model.quant_blocks(model.init(jax.random.key(0)),
                                       jnp.zeros((8, 16), jnp.int32))
    block = blocks[0]
    recipe = QuantRecipe(method="flexround", w_bits=4, a_bits=8, iters=1,
                         batch_size=4, rules=("layers.0.wq:w_bits=8",))
    canon = rec._canon_names(block)
    plans = rec.site_plans(block, recipe)
    astates = rec.init_astates(block, recipe, x0)
    qparams = rec.finalize_block(block, recipe,
                                 rec.init_wstates(block, recipe))
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # a described chip is not this process's backend: steer the deploy
    # dispatch to compiled Pallas, as resolve_backend does on a TPU
    monkeypatch.setattr(kops, "resolve_backend",
                        lambda backend="auto", interpret=None: ("pallas",
                                                                False))
    eng = rec._build_engine(block, recipe,
                            {canon[r]: plans[r] for r in block.sites}, canon,
                            mesh)

    def sds(tree, spec):
        sh = NamedSharding(mesh, spec)
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree)

    a_c = {canon[r]: astates[r] for r in block.sites}
    compiled = eng.student.lower(sds(qparams, P()), sds(x0, P("data")),
                                 sds(a_c, P())).compile()
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == len(block.sites)


#: the benchmark's serving engines: (config, slots, layers unrolled as the
#: mixed-precision deploy path runs them, append form, dtypes of the whole
#: leaves its decode may still copy). olmo-1b's scatter still relayouts its
#: f32 scales (67 MB each), as before; never its int8 codes.
ENGINES = {"smollm-135m": (64, True, "select", set()),
           "olmo-1b": (32, False, "scatter", {"f32"})}


def _whole_cache_copies(name, one_chip, slots, unrolled):
    """Dtypes of the ``copy`` ops in the compiled slot decode whose result
    has the shape of a whole stacked cache leaf (a relayout of the whole
    leaf), and the cache's shapes."""
    model = build_model(get_config(name))
    ecfg = EngineConfig(slots=slots, max_len=2048, kv_quant=True)
    params = jax.eval_shape(model.init, jax.random.key(0))
    if unrolled:
        layer = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:],
                                                            a.dtype),
                             params["layers"])
        params = {**params, "layers": [layer] * model.cfg.n_layers}
    state = jax.eval_shape(lambda: init_state(model, ecfg))
    on = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        t)
    meta = {k: state[k] for k in ("tokens", "pos", "remaining")}
    hlo = (jax.jit(make_decode(model, QuantCtx(), ecfg), donate_argnums=(1,))
           .lower(on(params), on(state["cache"]), on(meta)).compile()
           .as_text())
    leaves = {tuple(a.shape) for a in jax.tree.leaves(state["cache"])}
    copies = [m.group(1) for m in re.finditer(
        r"= (\w+)\[([\d,]+)\]\{[^}]*\} copy\(", hlo)
        if tuple(int(d) for d in m.group(2).split(",")) in leaves]
    return copies, state["cache"]


@pytest.mark.parametrize("name", sorted(ENGINES))
def test_decode_appends_without_relayouting_the_cache(one_chip, name):
    """The slot decode appends each token in the layout the chip gives the
    stacked cache: no whole copy of the int8 codes, for either head dim."""
    slots, unrolled, form, may_copy = ENGINES[name]
    copies, cache = _whole_cache_copies(name, one_chip, slots, unrolled)
    assert skv.append_form(cache) == form
    assert set(copies) <= may_copy, copies


def test_scatter_into_a_sequence_minor_cache_relayouts_it(one_chip,
                                                          monkeypatch):
    """Control for the test above: the per-slot scatter, at smollm-135m's
    head dim of 64, copies whole int8 code leaves, so the check sees them."""
    monkeypatch.setattr(skv, "append_form", lambda cache: "scatter")
    copies, _ = _whole_cache_copies("smollm-135m", one_chip, 64, False)
    assert "s8" in copies
