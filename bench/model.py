"""A configuration's model, its weights from the seed, and its export.

The weights are the benchmark's input: drawn from ``--seed`` on the device
in one jitted call, in the configuration's dtype, laid out as the program's
parameter tree. The program then quantizes them: FlexRound's initial state
(``init``) exported to QTensors (``finalize_block``) by the program's own
code, also in one jitted call. The reference (``bench/reference.py``) never
sees what the program made; it draws the same weights again from the seed.
"""
from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp

HERE = pathlib.Path(__file__).resolve().parent

#: leaves whose path names one of these are gains stored zero-centred
#: (applied as 1 + g); a small spread keeps the reference honest about it
_GAIN_STD = 0.1
_EMBED_STD = 0.02


def load_config(name: str, directory: pathlib.Path = HERE / "configs") -> dict:
    return json.loads((directory / f"{name}.json").read_text())


def arch(cfg: dict):
    from repro.configs.base import ArchConfig
    return ArchConfig(**cfg["arch"])


def build(cfg: dict):
    from repro.models import build_model
    return build_model(arch(cfg))


def root_key(seed: int) -> jax.Array:
    """A key for any whole-number seed, wider than 32 bits included."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def weights(model, seed: int):
    """The parameter tree of ``model`` drawn from ``seed``, on the device.

    Matrices are normal with std fan_in**-0.5, the embedding 0.02, norm
    gains (stored zero-centred) 0.1. One jitted call; the same seed gives
    the same weights on every call.
    """
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def draw(key):
        out = []
        for i, (path, sd) in enumerate(paths):
            name = jax.tree_util.keystr(path)
            z = jax.random.normal(jax.random.fold_in(key, i), sd.shape,
                                  jnp.float32)
            if "embed" in name:
                std = _EMBED_STD
            elif "scale" in name:
                std = _GAIN_STD
            else:
                std = sd.shape[-2] ** -0.5
            out.append((z * std).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(root_key(seed))


def recipe(cfg: dict):
    from repro.core import QuantRecipe
    r = cfg["recipe"]
    return QuantRecipe(method=r["method"], w_bits=r["w_bits"],
                       a_bits=r["a_bits"], w_granularity=r["w_granularity"],
                       w_observer=r["w_observer"],
                       a_symmetric=r["a_symmetric"],
                       rules=tuple(f"{pat}:w_bits={bits}"
                                   for pat, bits in r["w_bits_rules"]))


def export(model, params, rcp):
    """QTensor parameters from FlexRound's initial rounding state, through
    the program's block plan and export, in one jitted call."""
    from repro.core.paths import get_path
    from repro.core.reconstruct import finalize_block

    def run(params):
        _, blocks, assemble = model.quant_blocks(
            params, jnp.zeros((1, 1), jnp.int32))
        done = []
        for b in blocks:
            states = {}
            for name, site in b.sites.items():
                plan = rcp.resolve(name, site)
                states[name] = plan.method.init(get_path(b.params, site.path),
                                                plan.weight)
            done.append(finalize_block(b, rcp, states))
        return assemble(done)

    return jax.jit(run)(params)
