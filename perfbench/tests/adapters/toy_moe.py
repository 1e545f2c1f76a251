"""A test's adapter: a toy mixture of experts through the program's
``family="moe"`` decoder, to show that an architecture beyond InternLM2's
is added as files alone.

Every layer routes each token to ``num_experts_per_tok`` of its
``n_routed_experts`` experts and adds one shared expert behind a sigmoid
gate.  A routed expert's matrix is the checkpoint's ``expert_<role>`` at
(layer, expert); a shared one's is ``shared_<role>`` at its layer.
"""
from __future__ import annotations

import jax.numpy as jnp

from harness.workcount import Attention

NORMS = ("norm1", "norm2", "final_norm")
EXPERT_ROLES = ("w_gate", "w_up", "w_down")


def model_config(cfg: dict, qmode: str | None = None):
    from repro.configs.base import ModelConfig
    from repro.core.sod import SoDConfig

    return ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        n_experts=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        d_shared_ff=cfg["shared_expert_intermediate_size"],
        ep_axis=cfg["n_routed_experts"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"], dtype=cfg["torch_dtype"],
        sod=SoDConfig(mode=cfg["sparsity"]["format"]))


def _routed(keys: list) -> bool:
    return "moe" in keys and "shared" not in keys and \
        keys[-1] in EXPERT_ROLES


def leaf(keys: list, sds, cfg: dict, checkpoint):
    role = keys[-1]
    layers = range(cfg["num_hidden_layers"])
    if role in NORMS:
        return jnp.zeros(sds.shape, sds.dtype)
    if role in ("embed", "head"):
        return checkpoint(role, 0)
    if _routed(keys):
        experts = range(cfg["n_routed_experts"])
        made = [jnp.stack([checkpoint(f"expert_{role}", i, e)
                           for e in experts]) for i in layers]
    else:
        name = f"shared_{role}" if "shared" in keys else role
        made = [checkpoint(name, i) for i in layers]
    return jnp.stack(made).reshape(sds.shape).astype(sds.dtype)


def matmul(keys: list, cfg: dict) -> float | None:
    """A routed expert multiplies the share of tokens routed to it."""
    if keys[-1] in NORMS + ("embed",):
        return None
    if _routed(keys):
        return cfg["num_experts_per_tok"] / cfg["n_routed_experts"]
    return 1.0


def kv_page_bytes(mc, page_size: int) -> int:
    return (mc.n_layers * 2 * page_size * mc.n_kv_heads * mc.head_dim
            * jnp.dtype(mc.dtype).itemsize)


def attention(cfg: dict) -> list[Attention]:
    ops = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
    return [Attention(ops)] * cfg["num_hidden_layers"]
