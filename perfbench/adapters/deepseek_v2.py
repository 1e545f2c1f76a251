"""The program's DeepSeek-V2: how a configuration of ``model_type``
``deepseek_v2`` is built, packed and counted (the interface is in
``harness/build.py``).

The program runs it as its latent-attention decoder (``kv_lora_rank`` >
0): ``dense_blocks`` holds the leading dense layers, ``blocks`` the MoE
layers, each an expert layer told which experts it holds
(``experts_held``, the configuration's own list) that routes over all
``router_experts``.  A routed expert's matrix is the checkpoint's
``expert_<role>`` at (layer, expert id); a shared expert's is
``shared_<role>`` at its layer (the program holds the shared experts as
one MLP of their summed width); layer 0's MLP is ``<role>`` at 0.  The
RMSNorm gains are the program's ``1 + g``, so a published weight of 1 is
a gain of 0.
"""
from __future__ import annotations

import jax.numpy as jnp

from harness.workcount import Attention

NORMS = ("norm1", "norm2", "final_norm", "kv_norm")
EXPERT_ROLES = ("w_gate", "w_up", "w_down")


def model_config(cfg: dict, qmode: str | None = None):
    """The program's ``ModelConfig``; ``qmode`` replaces the stated
    value type of packed matrices."""
    from repro.configs.base import ModelConfig
    from repro.core.sod import SoDConfig
    from repro.models.layers import Yarn

    sp = cfg["sparsity"]
    sod = SoDConfig(mode=sp["format"], density=sp.get("density", 1.0),
                    prune_method=sp.get("prune_method", "magnitude"),
                    tile=tuple(sp.get("tile", (128, 128))),
                    br=sp.get("block_rows", 8),
                    qmode=qmode or sp.get("qmode", "none"))
    rs = cfg["rope_scaling"]
    if (cfg["scoring_func"] != "softmax" or cfg["topk_method"] != "greedy"
            or cfg["norm_topk_prob"] or cfg["routed_scaling_factor"] != 1
            or cfg["q_lora_rank"] is not None or cfg["moe_layer_freq"] != 1
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]
            or rs["type"] != "yarn"):
        raise ValueError("only DeepSeek-V2-Lite's routing (softmax, greedy, "
                         "unnormalised, unscaled), uncompressed queries, "
                         "YaRN and untied head are built here")
    held = tuple(cfg["experts_held"])
    if len(held) != cfg["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=nope + rope,
        d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=False, act="silu", dtype=cfg["torch_dtype"],
        n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        d_shared_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        experts_held=held, first_dense_layers=cfg["first_k_dense_replace"],
        d_ff_dense=cfg["intermediate_size"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=cfg["v_head_dim"],
        yarn=Yarn(factor=float(rs["factor"]),
                  original_max_position=rs["original_max_position_embeddings"],
                  beta_fast=float(rs["beta_fast"]),
                  beta_slow=float(rs["beta_slow"]),
                  mscale=float(rs["mscale"]),
                  mscale_all_dim=float(rs["mscale_all_dim"])),
        sod=sod)


def _routed(keys: list) -> bool:
    return "moe" in keys and "shared" not in keys and \
        keys[-1] in EXPERT_ROLES


def leaf(keys: list, sds, cfg: dict, checkpoint):
    role = keys[-1]
    if role in NORMS:
        return jnp.zeros(sds.shape, sds.dtype)
    if role in ("embed", "head"):
        return checkpoint(role, 0)
    n_dense = cfg["first_k_dense_replace"]
    layers = (range(n_dense) if keys[0] == "dense_blocks"
              else range(n_dense, cfg["num_hidden_layers"]))
    if _routed(keys):
        made = [jnp.stack([checkpoint(f"expert_{role}", i, e)
                           for e in cfg["experts_held"]]) for i in layers]
    else:
        name = f"shared_{role}" if "shared" in keys else role
        made = [checkpoint(name, i) for i in layers]
    return jnp.stack(made).reshape(sds.shape).astype(sds.dtype)


def matmul(keys: list, cfg: dict) -> float | None:
    """A held routed expert's useful work is on the share of rows routed
    to it, ``num_experts_per_tok / router_experts`` (the program
    multiplies every row of the step and keeps those); every other matrix
    uses every row."""
    if keys[-1] in NORMS + ("embed",):
        return None
    if _routed(keys):
        return cfg["num_experts_per_tok"] / cfg["router_experts"]
    return 1.0


def kv_page_bytes(mc, page_size: int) -> int:
    """Bytes of one latent page over all layers: each position's normed
    latent and roped key, a row the program pads to whole 128-lane tiles
    (576 → 640)."""
    row = -(-(mc.kv_lora_rank + mc.qk_rope_head_dim) // 128) * 128
    return mc.n_layers * page_size * row * jnp.dtype(mc.dtype).itemsize


def attention(cfg: dict) -> list[Attention]:
    """Latent attention as the paper writes it, whatever form the program
    runs: per query head and attended position, a score over the un-roped
    and roped key dims and a weighted value of ``v_head_dim``."""
    h = cfg["num_attention_heads"]
    ops = 2.0 * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) \
        + 2.0 * h * cfg["v_head_dim"]
    return [Attention(ops)] * cfg["num_hidden_layers"]
