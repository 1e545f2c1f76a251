"""The system under test, built from a configuration file and a seed.

This module is the one place that knows the program's interfaces: its
``ModelConfig`` and ``SoDConfig``, the layout of its parameter tree, its
packer ``core.sod.sodify_params`` and its serving ``Engine``.  The weights
are the configuration's pruned checkpoint, made on the device from the seed
by the configuration's own module, as a deployment loads a pruned
checkpoint: each leaf is made and handed to the program's packer, and the
unpacked copy is dropped before the next leaf is made, so unpacked and
packed weights are never all resident at once.
"""
from __future__ import annotations

import math
import time

import jax
import jax.numpy as jnp

from harness import weights
from harness.workcount import Matrix

# the program's RMSNorm multiplies by (1 + g): a published weight of 1
_NORMS = ("norm1", "norm2", "final_norm")


def model_config(cfg: dict, qmode: str | None = None):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    from repro.core.sod import SoDConfig

    sp = cfg["sparsity"]
    sod = SoDConfig(mode=sp["format"], density=sp["density"],
                    prune_method=sp["prune_method"], tile=tuple(sp["tile"]),
                    br=sp.get("block_rows", 8),
                    qmode=qmode or sp.get("qmode", "none"))
    if cfg["tie_word_embeddings"] or cfg["hidden_act"] != "silu":
        raise ValueError("only untied SiLU-GLU decoders are built here")
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=False, act="silu", dtype=cfg["torch_dtype"], sod=sod)


def _nest(keys: list, leaf) -> dict:
    tree = leaf
    for k in reversed(keys):
        tree = {k: tree}
    return tree


def _dig(tree, keys: list):
    for k in keys:
        tree = tree[k]
    return tree


def build_params(model, cfg: dict, checkpoint):
    """The checkpoint in the program's tree, projections packed by
    ``sodify_params``.  ``checkpoint(role, layer)`` gives one matrix.
    Returns ``(params, pack_s)``: ``pack_s`` is the host time the program
    spent packing, each leaf ending in ``block_until_ready``."""
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
        role = keys[-1]
        if role in _NORMS:
            leaves.append(jnp.zeros(sds.shape, sds.dtype))
            continue
        if role in ("embed", "head"):
            leaf = checkpoint(role, 0)
        elif role in weights.LAYER_ROLES:
            leaf = jnp.stack([checkpoint(role, i) for i in range(
                cfg["num_hidden_layers"])]).reshape(sds.shape)
        else:
            raise ValueError(f"no checkpoint matrix for leaf {keys}")
        if leaf.shape != sds.shape or leaf.dtype != sds.dtype:
            raise ValueError(f"{keys}: made {leaf.shape} {leaf.dtype}, the "
                             f"program wants {sds.shape} {sds.dtype}")
        jax.block_until_ready(leaf)
        if role != "embed":
            t0 = time.perf_counter()
            leaf = _dig(sodify_params(_nest(keys, leaf), mc.sod,
                                      prune=False), keys)
            jax.block_until_ready(leaf)
            pack_s += time.perf_counter() - t0
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves), pack_s


def kv_page_bytes(mc, page_size: int) -> int:
    """Bytes of one KV page over all layers (keys and values)."""
    return (mc.n_layers * 2 * page_size * mc.n_kv_heads * mc.head_dim
            * jnp.dtype(mc.dtype).itemsize)


# Device memory a step needs beside the weights and two pools: a layer's
# gather of every slot's pages, and a prefill's logits (bucket x vocabulary
# x 4 bytes) and its contiguous KV before it is written into pages.
STEP_HEADROOM_BYTES = 2 * 2**30


def pool_pages(mc, traffic: dict, max_len: int, free_bytes: int) -> int:
    """Pages of the engine's KV pool: every slot's worst case where that
    fits, otherwise the largest pool that fits twice in the memory left
    after the weights and a step's headroom (a multiple of 16, plus the
    trash page).  Twice, because the engine's decode and prefill programs
    return a new pool without donating the old one."""
    page = traffic["page_size"]
    worst = 1 + traffic["slots"] * math.ceil(max_len / page)
    fit = (free_bytes - STEP_HEADROOM_BYTES) // (2 * kv_page_bytes(mc, page))
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


def packed_matrices(params) -> list[Matrix]:
    """Every packed matmul of one forward pass, read from the run's packed
    arrays: one entry per layer and matrix, and one for the head."""
    from repro.core.formats import BlockCSR, TiledCSC

    out = []
    flat = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda x: isinstance(x, (TiledCSC, BlockCSR)))[0]
    for path, leaf in flat:
        if not isinstance(leaf, (TiledCSC, BlockCSR)):
            continue
        role = path[-1].key
        k, n = leaf.shape
        if isinstance(leaf, TiledCSC):
            vals, valid = leaf.vals, leaf.rows >= 0
            per = jnp.sum(valid.reshape(-1, *valid.shape[-4:]), (1, 2, 3, 4))
        else:
            vals = leaf.block_vals
            per = jnp.sum((vals != 0).reshape(-1, *vals.shape[-5:]),
                          (1, 2, 3, 4, 5))
        for nnz in jax.device_get(per).tolist():
            out.append(Matrix(role, int(k), int(n), int(nnz),
                              vals.dtype.itemsize))
    return out
