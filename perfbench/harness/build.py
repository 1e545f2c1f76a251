"""The system under test, built from a configuration file, its program
adapter and a seed.

What holds for every architecture lives here: the leaf-by-leaf make, pack
and drop loop, the KV pool's size, the engine and its prefill buckets, and
the list of a forward pass's matmuls read from the run's arrays.  What the
program is for one architecture lives in its adapter,
``adapters/<model_type>.py``, found by the configuration's ``model_type``
(``spec.Cell.adapter``).  An adapter is a module with

* ``model_config(cfg, qmode=None)``: the program's ``ModelConfig``;
* ``leaf(keys, sds, cfg, checkpoint)``: the parameter leaf at path
  ``keys`` (of the shape and dtype of ``sds``), assembled from
  ``checkpoint(role, layer, *index)`` matrices;
* ``matmul(keys, cfg)``: the share of a step's rows that each matrix of
  the leaf multiplies (1 for a dense or shared matrix, less for a routed
  expert), or None for a leaf that is no matmul weight.  Matmul weights
  are handed to the packer;
* ``kv_page_bytes(mc, page_size)``: bytes of one KV page over all layers;
* ``attention(cfg)``: each layer's ``workcount.Attention``.

The weights are the configuration's checkpoint, made on the device from
the seed by the configuration's own module, as a deployment loads a pruned
checkpoint: each leaf is made and handed to the program's packer, and the
unpacked copy is dropped before the next leaf is made, so unpacked and
packed weights are never all resident at once.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness.workcount import Matrix


def _nest(keys: list, leaf) -> dict:
    tree = leaf
    for k in reversed(keys):
        tree = {k: tree}
    return tree


def _dig(tree, keys: list):
    for k in keys:
        tree = tree[k]
    return tree


def build_params(adapter, model, cfg: dict, checkpoint):
    """The checkpoint in the program's tree, matmul weights packed by
    ``sodify_params``.  Returns ``(params, pack_s)``: ``pack_s`` is the
    host time the program spent packing, each leaf ending in
    ``block_until_ready``."""
    from repro.core.sod import sodify_params

    mc = model.cfg
    if mc.padded_vocab != cfg["vocab_size"]:
        raise ValueError("vocabulary must be a multiple of 128 here")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    pack_s = 0.0
    for path, sds in flat:
        keys = [p.key for p in path]
        leaf = adapter.leaf(keys, sds, cfg, checkpoint)
        if leaf.shape != sds.shape or leaf.dtype != sds.dtype:
            raise ValueError(f"{keys}: made {leaf.shape} {leaf.dtype}, the "
                             f"program wants {sds.shape} {sds.dtype}")
        jax.block_until_ready(leaf)
        if adapter.matmul(keys, cfg) is not None:
            t0 = time.perf_counter()
            leaf = _dig(sodify_params(_nest(keys, leaf), mc.sod,
                                      prune=False), keys)
            jax.block_until_ready(leaf)
            pack_s += time.perf_counter() - t0
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves), pack_s


# Device memory a step needs beside the weights and two pools: a layer's
# gather of every slot's pages, and a prefill's logits (bucket x vocabulary
# x 4 bytes) and its contiguous KV before it is written into pages.
STEP_HEADROOM_BYTES = 2 * 2**30


def pool_pages(page_bytes: int, traffic: dict, max_len: int,
               free_bytes: int) -> int:
    """Pages of the engine's KV pool, of ``page_bytes`` each: every slot's
    worst case where that fits, otherwise the largest pool that fits twice
    in the memory left after the weights and a step's headroom (a multiple
    of 16, plus the trash page).  Twice, because the engine's decode and
    prefill programs return a new pool without donating the old one."""
    worst = 1 + traffic["slots"] * math.ceil(max_len / traffic["page_size"])
    fit = (free_bytes - STEP_HEADROOM_BYTES) // (2 * page_bytes)
    if fit < 17:
        raise ValueError(f"{free_bytes} bytes free hold no pool of 16 pages")
    return min(worst, 1 + (fit - 1) // 16 * 16)


def engine(model, params, traffic: dict, max_len: int, n_pages: int):
    from repro.serving import Engine

    return Engine(model, params, max_slots=traffic["slots"],
                  page_size=traffic["page_size"], max_len=max_len,
                  n_pages=n_pages)


def bucket(mc, traffic: dict, plen: int) -> int:
    """The prefill length the engine pads a prompt of ``plen`` tokens to."""
    from repro.serving import bucket_len

    return bucket_len(plen, traffic["page_size"], mc.attn_chunk)


def matmuls(params, adapter, cfg: dict) -> list[Matrix]:
    """Every matmul of one forward pass, read from the run's arrays: one
    entry per matrix (a leaf stacks them by layer, and by expert where it
    has experts), tagged with what computes it: the program's kernel for a
    packed operand (TiledCSC: ``pallas_fused``, BlockCSR:
    ``pallas_block``), an XLA dot for a dense one."""
    from repro.core.formats import BlockCSR, TiledCSC

    out = []
    flat = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, (TiledCSC, BlockCSR)))[0]
    for path, leaf in flat:
        keys = [p.key for p in path]
        share = adapter.matmul(keys, cfg)
        if share is None:
            continue
        k, n = leaf.shape[-2:]
        if isinstance(leaf, TiledCSC):
            kernel, vals, valid = "pallas_fused", leaf.vals, leaf.rows >= 0
            per = jnp.sum(valid.reshape(-1, *valid.shape[-4:]), (1, 2, 3, 4))
        elif isinstance(leaf, BlockCSR):
            kernel, vals = "pallas_block", leaf.block_vals
            per = jnp.sum((vals != 0).reshape(-1, *vals.shape[-5:]),
                          (1, 2, 3, 4, 5))
        else:
            kernel, vals = "xla_dot", leaf
            per = np.full(math.prod(leaf.shape[:-2]), k * n, np.int64)
        for nnz in jax.device_get(per).tolist():
            out.append(Matrix(keys[-1], int(k), int(n), int(nnz),
                              vals.dtype.itemsize, kernel, share))
    return out
