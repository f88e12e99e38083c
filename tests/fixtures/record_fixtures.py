"""Recorder for the reconstruction RNG-parity fixtures.

``recon_legacy_trajectories.npz`` pins the trajectories the *legacy
per-iteration Python loop* produced for a fixed set of recipes/blocks/keys.
It was recorded at commit 807104f (the last commit carrying the
``--legacy-loop`` escape hatch) by running this script; the legacy engine has
since been removed, so the fixture — not a live second engine — is the parity
oracle for the scan-fused engine (see tests/test_recon_engine.py).

Re-recording (only if the *intended* RNG stream or step math changes, which
is a breaking trajectory change that must be called out in the PR): run

    PYTHONPATH=src python tests/fixtures/record_fixtures.py [out.npz]

and commit the regenerated npz together with the engine change. Post-removal
re-records run the scan engine (the only one left): the new recording then
*becomes* the oracle for subsequent refactors.

``--only TAG`` (repeatable) re-records just the named entries and keeps
every other tag from the existing npz — so an intended trajectory change in
one path (e.g. the per-site minibatch keys of layer-wise recon) does not
silently refresh the oracles for untouched paths.

Recording history of intended trajectory changes since the legacy capture:
  - partitionable threefry (jax's default since 0.5): sharding-invariant RNG is a
    hard requirement for data-parallel calibration (the legacy stream draws
    *different* QDrop masks when outputs are sharded), and it changes every
    random stream — all tags re-recorded.
  - layer-wise recon folds the site name into the minibatch key (sibling
    sites previously shared one gather schedule) — ``layerwise``
    re-recorded.
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                os.pardir, os.pardir, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import QuantRecipe  # noqa: E402
from repro.core.context import QuantCtx  # noqa: E402
from repro.core.reconstruct import (BlockHandle, Site, quantize_blocks,  # noqa: E402
                                    reconstruct_block)

OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                   "recon_legacy_trajectories.npz")

# The committed npz was recorded by the *legacy per-iteration loop* at
# commit 807104f (reconstruct_block(..., engine="legacy"), an argument that
# no longer exists). Re-records at head run the current scan engine.


def flatten_tree(prefix, tree):
    """Pytree -> {"prefix/<path>": np.ndarray} with deterministic path
    strings (DictKey -> key, SequenceKey -> [i]). Must stay in sync with the
    copy in tests/test_recon_engine.py."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        toks = []
        for p in path:
            if hasattr(p, "key"):
                toks.append(str(p.key))
            elif hasattr(p, "idx"):
                toks.append(f"[{p.idx}]")
            else:
                toks.append(str(p))
        out[prefix + "/" + "|".join(toks)] = np.asarray(leaf)
    return out


def make_block(key, name, d=24, h=40, token=None):
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


def make_chain(n, token, d=24, h=40):
    keys = jax.random.split(jax.random.key(3), n)
    return [make_block(k, f"layers.{i}", d=d, h=h, token=token)
            for i, k in enumerate(keys)]


def record_single(store, tag, recipe, block_key, x_key, n, seed=3):
    block = make_block(jax.random.key(block_key), "layers.0")
    x = jax.random.normal(jax.random.key(x_key), (n, 24), jnp.float32)
    y = block.apply(block.params, x, QuantCtx(mode="fp"))
    ws, as_, rep = reconstruct_block(block, recipe, x, y,
                                     jax.random.key(seed))
    store.update(flatten_tree(f"{tag}/wstates", ws))
    store.update(flatten_tree(f"{tag}/astates", as_))
    store[f"{tag}/err"] = np.asarray([rep.err_before, rep.err_after])
    store[f"{tag}/loss_curve"] = np.asarray(rep.loss_curve)
    store[f"{tag}/mse_curve"] = np.asarray(rep.mse_curve)


def record_block_w4a8_qdrop(store):
    # block mode, full path: LSQ co-training + QDrop RNG
    record_single(
        store, "block_w4a8_qdrop",
        QuantRecipe(method="flexround", w_bits=4, w_symmetric=True, a_bits=8,
                    setting="qdrop", iters=50, lr=3e-3, batch_size=8),
        block_key=7, x_key=8, n=48)


def record_block_w4a8_qdrop_short(store):
    # short-horizon twin of block_w4a8_qdrop for the sharded parity tests:
    # over ~15 steps reduction-order drift cannot yet amplify through the
    # STE rounding boundaries, so the data-parallel run must match this
    # recording at the tight tolerance (see tests/test_sharded_recon.py)
    record_single(
        store, "block_w4a8_qdrop_short",
        QuantRecipe(method="flexround", w_bits=4, w_symmetric=True, a_bits=8,
                    setting="qdrop", iters=15, lr=3e-3, batch_size=8),
        block_key=7, x_key=8, n=48)


def record_adaround_reg(store):
    # AdaRound annealed regularizer consuming the traced step index
    record_single(
        store, "adaround_reg",
        QuantRecipe(method="adaround", w_bits=4, w_symmetric=True, a_bits=None,
                    iters=40, lr=3e-3, batch_size=8),
        block_key=9, x_key=10, n=32)


def record_full_batch(store):
    # full-batch recon (bs == n skips the gather)
    record_single(
        store, "full_batch",
        QuantRecipe(method="flexround", w_bits=4, w_symmetric=True, a_bits=8,
                    iters=30, lr=3e-3, batch_size=32),
        block_key=11, x_key=12, n=32)


def record_chain_mixed(store):
    # 3-block chain under mixed-precision rules
    recipe = QuantRecipe(
        method="flexround", w_bits=4, w_symmetric=True, a_bits=8,
        setting="qdrop", iters=30, lr=3e-3, batch_size=8,
        rules=("layers.0.*:w_bits=8,lr=1e-3",
               "layers.2.w2:a_bits=none,method=adaround"))
    x = jax.random.normal(jax.random.key(1), (40, 24), jnp.float32)
    fin, ast, _ = quantize_blocks(make_chain(3, token=None), recipe, x,
                                  as_qtensor=False)
    store.update(flatten_tree("chain_mixed/finalized", fin))
    store.update(flatten_tree("chain_mixed/astates", ast))


def record_layerwise(store):
    # layer-wise (recon='layer') per-site sub-blocks
    recipe = QuantRecipe(method="flexround", w_bits=3, w_symmetric=True,
                         a_bits=None, recon="layer", iters=40, lr=3e-3,
                         batch_size=8)
    x = jax.random.normal(jax.random.key(2), (40, 24), jnp.float32)
    fin, _, reports = quantize_blocks(make_chain(2, token=None), recipe, x,
                                      as_qtensor=False)
    assert len(reports) == 4
    store.update(flatten_tree("layerwise/finalized", fin))


RECORDERS = {
    "block_w4a8_qdrop": record_block_w4a8_qdrop,
    "block_w4a8_qdrop_short": record_block_w4a8_qdrop_short,
    "adaround_reg": record_adaround_reg,
    "full_batch": record_full_batch,
    "chain_mixed": record_chain_mixed,
    "layerwise": record_layerwise,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("out", nargs="?", default=OUT)
    ap.add_argument("--only", action="append", default=None, metavar="TAG",
                    choices=sorted(RECORDERS),
                    help="re-record only these tags; every other tag is "
                         "carried over unchanged from the existing npz")
    args = ap.parse_args()

    tags = args.only or sorted(RECORDERS)
    store = {}
    if args.only and os.path.exists(args.out):
        keep = dict(np.load(args.out))
        store.update({k: v for k, v in keep.items()
                      if k.split("/", 1)[0] not in tags})
        print(f"merging: kept {len(store)} arrays from "
              f"{sorted({k.split('/', 1)[0] for k in store})}")

    for tag in tags:
        RECORDERS[tag](store)

    np.savez_compressed(args.out, **store)
    print(f"wrote {args.out}: {len(store)} arrays, "
          f"{os.path.getsize(args.out) / 1024:.1f} KiB")


if __name__ == "__main__":
    main()
