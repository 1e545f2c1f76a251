"""The program's InternLM2: how a configuration of ``model_type``
``internlm2`` is built, packed and counted (the interface is in
``harness/build.py``).

The program runs it as its ``family="dense"`` decoder: untied head, SiLU
gate, grouped-query attention over full context in every layer.  Each
projection leaf stacks the layers' matrices of one role; the embedding and
the head are single matrices; the RMSNorm gains are the program's
``1 + g``, so a published weight of 1 is a gain of 0.
"""
from __future__ import annotations

import jax.numpy as jnp

from harness.workcount import Attention

LAYER_ROLES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORMS = ("norm1", "norm2", "final_norm")


def model_config(cfg: dict, qmode: str | None = None):
    """The program's ``ModelConfig``; ``qmode`` replaces the stated
    value type of packed matrices."""
    from repro.configs.base import ModelConfig
    from repro.core.sod import SoDConfig

    sp = cfg["sparsity"]
    sod = SoDConfig(mode=sp["format"], density=sp.get("density", 1.0),
                    prune_method=sp.get("prune_method", "magnitude"),
                    tile=tuple(sp.get("tile", (128, 128))),
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


def leaf(keys: list, sds, cfg: dict, checkpoint):
    role = keys[-1]
    if role in NORMS:
        return jnp.zeros(sds.shape, sds.dtype)
    if role in ("embed", "head"):
        return checkpoint(role, 0)
    if role in LAYER_ROLES:
        return jnp.stack([checkpoint(role, i) for i in range(
            cfg["num_hidden_layers"])]).reshape(sds.shape)
    raise ValueError(f"no checkpoint matrix for leaf {keys}")


def matmul(keys: list, cfg: dict) -> float | None:
    """Every projection and the head see every row of a step."""
    return None if keys[-1] in NORMS + ("embed",) else 1.0


def kv_page_bytes(mc, page_size: int) -> int:
    """Bytes of one KV page over all layers (keys and values)."""
    return (mc.n_layers * 2 * page_size * mc.n_kv_heads * mc.head_dim
            * jnp.dtype(mc.dtype).itemsize)


def attention(cfg: dict) -> list[Attention]:
    """Every layer attends to the whole context: a score and a weighted
    value of ``head_dim`` multiply-adds per query head and position."""
    ops = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return [Attention(ops)] * cfg["num_hidden_layers"]
