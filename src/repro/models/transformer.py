"""Model assemblies for all assigned families.

Three assemblies share one external interface (see ``model.py``):

  * ``TransformerLM`` — dense / MoE / VLM-backbone / audio-backbone decoders.
    Layers are stacked in groups of ``len(layer_pattern)`` (gemma-2's
    local/global alternation becomes a group of two) and executed under
    ``jax.lax.scan`` so HLO size is depth-independent — required to keep 80
    dry-run compiles tractable and standard production practice.
  * ``HybridLM``  — zamba2: mamba2 stacks with a *shared* attention+MLP block
    applied every ``hybrid_attn_every`` layers.
  * ``XLSTMLM``   — groups of (slstm_every-1) mLSTM blocks + 1 sLSTM block.

Every weight matmul goes through ``sod.apply`` → Sparse-on-Dense everywhere.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import plan as plan_mod
from repro.core import sod
from repro.models import attention as attn
from repro.models import cache as cache_mod
from repro.models import layers, moe, ssm, xlstm

Params = dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _scan(body, init, xs, cfg: ModelConfig):
    """lax.scan over stacked layer groups, or an unrolled python loop when
    ``cfg.scan_layers`` is False (exact cost_analysis for the dry-run)."""
    if cfg.scan_layers:
        return jax.lax.scan(body, init, xs)
    n = jax.tree_util.tree_leaves(xs)[0].shape[0]
    carry = init
    ys = []
    for g in range(n):
        carry, y = body(carry, jax.tree_util.tree_map(lambda t: t[g], xs))
        ys.append(y)
    if all(y is None for y in ys):
        return carry, None
    return carry, jax.tree_util.tree_map(lambda *t: jnp.stack(t), *ys)


def attn_spec(cfg: ModelConfig) -> attn.AttnSpec:
    return attn.AttnSpec(
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        rope_theta=cfg.rope_theta,
        scale=cfg.attn_scale,
        softcap=cfg.attn_softcap,
        chunk_q=cfg.attn_chunk,
        chunk_k=cfg.attn_chunk,
    )


def moe_spec(cfg: ModelConfig) -> moe.MoESpec:
    return moe.MoESpec(
        n_experts=cfg.n_experts,
        n_experts_padded=moe.pad_experts(cfg.n_experts, cfg.ep_axis),
        top_k=cfg.top_k,
        d_model=cfg.d_model,
        d_ff=cfg.d_ff,
        n_shared=cfg.n_shared_experts,
        d_shared_ff=cfg.d_shared_ff,
        capacity_factor=cfg.capacity_factor,
        router_aux_weight=cfg.router_aux_weight,
        act=cfg.act,
        dispatch_blocks=cfg.moe_dispatch_blocks,
        a2a_axis=cfg.moe_a2a_axis,
    )


def mamba_spec(cfg: ModelConfig) -> ssm.MambaSpec:
    return ssm.MambaSpec(
        d_model=cfg.d_model,
        d_state=cfg.ssm_state,
        expand=cfg.ssm_expand,
        headdim=cfg.ssm_headdim,
        conv_width=cfg.ssm_conv,
        chunk=cfg.ssm_chunk,
    )


def xlstm_spec(cfg: ModelConfig) -> xlstm.XLSTMSpec:
    return xlstm.XLSTMSpec(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        proj_factor=cfg.xlstm_proj_factor,
        chunk=cfg.ssm_chunk,
    )


# ---------------------------------------------------------------------------
# attention + (mlp | moe) block
# ---------------------------------------------------------------------------
def init_attn_block(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    dt = _dtype(cfg)
    p: Params = {
        "norm1": layers.init_rms_norm(cfg.d_model),
        "norm2": layers.init_rms_norm(cfg.d_model),
        "attn": attn.init_attention(ks[0], cfg.d_model, attn_spec(cfg), dt),
    }
    if cfg.family == "moe":
        p["moe"] = moe.init_moe(ks[1], moe_spec(cfg), dt)
    else:
        p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff, dt)
    if cfg.use_post_norms:
        p["norm1_post"] = layers.init_rms_norm(cfg.d_model)
        p["norm2_post"] = layers.init_rms_norm(cfg.d_model)
    return p


def _apply_mlp(bp: Params, h: jax.Array, cfg: ModelConfig):
    # Per-layer pack plans: the active ModelPlan's entries for this block's
    # projections (layer stacks share one path, hence one plan entry).
    if cfg.family == "moe":
        return moe.moe_mlp(bp["moe"], h, moe_spec(cfg),
                           plans=plan_mod.active_subplans("shared"))
    return layers.mlp(bp["mlp"], h, cfg.act,
                      plans=plan_mod.active_subplans("mlp")), 0.0


def attn_block_full(bp: Params, x: jax.Array, cfg: ModelConfig,
                    positions: jax.Array, window: int | None,
                    want_kv: bool):
    """Full-sequence block.  Returns (x, (k, v) | None, aux_loss)."""
    spec = attn_spec(cfg)
    h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
    q, k, v = attn._project_qkv(bp["attn"], h, spec, positions)
    s = x.shape[1]
    eff_window = None if (window is None or window >= s) else window
    ao = attn.chunked_attention(q, k, v, spec, window=eff_window)
    ao = sod.apply(ao.reshape(*x.shape[:2], -1), bp["attn"]["wo"],
                   plan=plan_mod.active_entry("attn.wo"))
    if cfg.use_post_norms:
        ao = layers.rms_norm(ao, bp["norm1_post"], cfg.norm_eps)
    x = x + ao
    h2 = layers.rms_norm(x, bp["norm2"], cfg.norm_eps)
    mo, aux = _apply_mlp(bp, h2, cfg)
    if cfg.use_post_norms:
        mo = layers.rms_norm(mo, bp["norm2_post"], cfg.norm_eps)
    x = x + mo
    return x, ((k, v) if want_kv else None), aux


def attn_block_decode(bp: Params, x: jax.Array, cache: Params,
                      pos: jax.Array, cfg: ModelConfig,
                      window: int | None,
                      block_tables: jax.Array | None = None,
                      valid_len: jax.Array | None = None, layer=0):
    """One decode block.  ``cache`` is a dense per-slot KV cache, or —
    when ``block_tables`` is given — the paged KV pool (the engine's
    slot→page mapping), of which this block is flat layer ``layer``.
    ``valid_len`` (paged only) is the optional per-row write cutoff
    forwarded to :func:`repro.models.attention.paged_decode_attention`."""
    spec = attn_spec(cfg)
    h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
    if block_tables is None:
        ao, cache = attn.decode_attention(bp["attn"], h, cache, pos, spec,
                                          window=window)
    else:
        ao, cache = attn.paged_decode_attention(
            bp["attn"], h, cache, block_tables, pos, spec, window=window,
            valid_len=valid_len, layer=layer)
    if cfg.use_post_norms:
        ao = layers.rms_norm(ao, bp["norm1_post"], cfg.norm_eps)
    x = x + ao
    h2 = layers.rms_norm(x, bp["norm2"], cfg.norm_eps)
    mo, _ = _apply_mlp(bp, h2, cfg)
    if cfg.use_post_norms:
        mo = layers.rms_norm(mo, bp["norm2_post"], cfg.norm_eps)
    return x + mo, cache


def attn_block_verify(bp: Params, x: jax.Array, pool: Params,
                      block_tables: jax.Array, start: jax.Array,
                      valid_len: jax.Array, cfg: ModelConfig,
                      window: int | None, layer=0):
    """One block over a speculative verification window.

    Mirrors :func:`attn_block_decode`'s paged branch exactly (same norm /
    residual order, ``wo`` applied inside the attention call with the same
    dispatch) with the single-token attention replaced by
    :func:`repro.models.attention.paged_verify_attention` — row ``b``
    scores C window positions starting at ``start[b]`` instead of one.
    Every other op is position-row-independent, so verify logits for a
    window position are bitwise what the sequential decode step would
    produce there.
    """
    spec = attn_spec(cfg)
    h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
    ao, pool = attn.paged_verify_attention(
        bp["attn"], h, pool, block_tables, start, valid_len, spec,
        window=window, layer=layer)
    if cfg.use_post_norms:
        ao = layers.rms_norm(ao, bp["norm1_post"], cfg.norm_eps)
    x = x + ao
    h2 = layers.rms_norm(x, bp["norm2"], cfg.norm_eps)
    mo, _ = _apply_mlp(bp, h2, cfg)
    if cfg.use_post_norms:
        mo = layers.rms_norm(mo, bp["norm2_post"], cfg.norm_eps)
    return x + mo, pool


def attn_block_prefill_chunk(bp: Params, x: jax.Array, pool: Params,
                             block_tables: jax.Array, start: jax.Array,
                             valid_len: jax.Array, cfg: ModelConfig,
                             window: int | None, layer=0):
    """One block over a prefill chunk against the paged KV pool.

    Mirrors :func:`attn_block_full` (same ``wo`` plan entry, same norm /
    residual order) with the full-sequence attention replaced by
    :func:`repro.models.attention.paged_prefill_attention`, so a prompt
    prefilled in chunks produces the same tokens as one fused prefill.
    """
    spec = attn_spec(cfg)
    h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
    ao, pool = attn.paged_prefill_attention(
        bp["attn"], h, pool, block_tables, start, valid_len, spec,
        window=window, layer=layer)
    ao = sod.apply(ao, bp["attn"]["wo"],
                   plan=plan_mod.active_entry("attn.wo"))
    if cfg.use_post_norms:
        ao = layers.rms_norm(ao, bp["norm1_post"], cfg.norm_eps)
    x = x + ao
    h2 = layers.rms_norm(x, bp["norm2"], cfg.norm_eps)
    mo, _ = _apply_mlp(bp, h2, cfg)
    if cfg.use_post_norms:
        mo = layers.rms_norm(mo, bp["norm2_post"], cfg.norm_eps)
    return x + mo, pool


# ---------------------------------------------------------------------------
# embedding / head / frontends
# ---------------------------------------------------------------------------
def init_embed_head(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4)
    dt = _dtype(cfg)
    p: Params = {"final_norm": layers.init_rms_norm(cfg.d_model)}
    v = cfg.padded_vocab
    if cfg.family == "audio":
        p["embed"] = jax.vmap(
            lambda k: layers.embed_init(k, v, cfg.d_model, dt)
        )(jax.random.split(ks[0], cfg.n_codebooks))
        p["head"] = layers.dense_init(
            ks[1], cfg.d_model, cfg.n_codebooks * v, dt)
        return p
    p["embed"] = layers.embed_init(ks[0], v, cfg.d_model, dt)
    if not cfg.tie_embeddings:
        p["head"] = layers.dense_init(ks[1], cfg.d_model, v, dt)
    if cfg.family == "vlm":
        p["patch_proj"] = layers.dense_init(
            ks[2], cfg.frontend_dim, cfg.d_model, dt)
    return p


def embed_inputs(params: Params, batch: Params, cfg: ModelConfig) -> jax.Array:
    tokens = batch["tokens"]
    if cfg.family == "audio":
        # tokens (B, S, n_codebooks): sum of per-codebook embeddings
        x = sum(
            layers.embed(params["embed"][c], tokens[..., c])
            for c in range(cfg.n_codebooks)
        )
    else:
        x = layers.embed(params["embed"], tokens, scale=cfg.embed_scale)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        prefix = sod.apply(
            batch["patch_embeds"].astype(x.dtype), params["patch_proj"])
        x = jnp.concatenate([prefix, x], axis=1)
    return x


def project_logits(params: Params, x: jax.Array, cfg: ModelConfig):
    x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
    v = cfg.padded_vocab
    head_plan = plan_mod.active_entry("head")
    if cfg.family == "audio":
        logits = sod.apply(x, params["head"], out_dtype=jnp.float32,
                           plan=head_plan)
        logits = logits.reshape(*x.shape[:-1], cfg.n_codebooks, v)
    elif cfg.tie_embeddings:
        logits = jnp.dot(x, params["embed"].T.astype(x.dtype),
                         preferred_element_type=jnp.float32)
    else:
        logits = sod.apply(x, params["head"], out_dtype=jnp.float32,
                           plan=head_plan)
    logits = layers.softcap(logits.astype(jnp.float32), cfg.final_softcap)
    if v != cfg.vocab:   # mask padded vocabulary slots
        mask = jnp.arange(v) >= cfg.vocab
        logits = jnp.where(mask, -1e30, logits)
    return logits


# ---------------------------------------------------------------------------
# TransformerLM (dense / moe / vlm / audio)
# ---------------------------------------------------------------------------
def init_transformer(key, cfg: ModelConfig) -> Params:
    if cfg.experts_held or cfg.first_dense_layers or cfg.d_ff_dense:
        raise ValueError("experts_held, first_dense_layers and d_ff_dense "
                         "are read only with latent attention "
                         "(kv_lora_rank > 0)")
    p_period = cfg.pattern_period
    n_groups = cfg.n_layers // p_period
    ks = jax.random.split(key, 2)
    keys = jax.random.split(ks[0], cfg.n_layers).reshape(
        n_groups, p_period, -1)
    blocks = jax.vmap(jax.vmap(lambda k: init_attn_block(k, cfg)))(keys)
    params = init_embed_head(ks[1], cfg)
    params["blocks"] = blocks
    return params


def transformer_forward(params: Params, batch: Params, cfg: ModelConfig,
                        want_cache: bool = False):
    x = embed_inputs(params, batch, cfg)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    p_period = cfg.pattern_period

    def group_body(carry, gp):
        x, aux = carry
        kvs = []
        for j in range(p_period):
            bp = jax.tree_util.tree_map(lambda t: t[j], gp)
            x, kv, a = attn_block_full(
                bp, x, cfg, positions, cfg.window_for(j), want_cache)
            aux = aux + a
            if want_cache:
                kvs.append(kv)
        if want_cache:
            ys = (
                jnp.stack([kv[0] for kv in kvs]),
                jnp.stack([kv[1] for kv in kvs]),
            )
        else:
            ys = None
        return (x, aux), ys

    body = group_body
    if cfg.remat and not want_cache:
        body = jax.checkpoint(group_body, prevent_cse=False)
    (x, aux), kv_stack = _scan(body, (x, 0.0), params["blocks"], cfg)
    logits = project_logits(params, x, cfg)
    cache = None
    if want_cache:
        cache = {"k": kv_stack[0], "v": kv_stack[1]}   # (G,P,B,S,KV,hd)
    return logits, aux, cache


def transformer_decode(params: Params, cache: Params, tokens: jax.Array,
                       pos: jax.Array, cfg: ModelConfig):
    x = embed_inputs(params, {"tokens": tokens}, cfg)
    p_period = cfg.pattern_period

    def group_body(x, inp):
        gp, kc, vc = inp
        ks, vs = [], []
        for j in range(p_period):
            bp = jax.tree_util.tree_map(lambda t: t[j], gp)
            slot = {"k": kc[j], "v": vc[j]}
            x, slot = attn_block_decode(bp, x, slot, pos, cfg,
                                        cfg.window_for(j))
            ks.append(slot["k"])
            vs.append(slot["v"])
        return x, (jnp.stack(ks), jnp.stack(vs))

    x, (knew, vnew) = _scan(
        group_body, x, (params["blocks"], cache["k"], cache["v"]), cfg)
    logits = project_logits(params, x, cfg)
    return logits, {"k": knew, "v": vnew}


def transformer_init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    p_period = cfg.pattern_period
    n_groups = cfg.n_layers // p_period
    dt = _dtype(cfg)
    shape = (n_groups, p_period, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def transformer_cache_spec(cfg: ModelConfig) -> Params:
    """Axis roles of :func:`transformer_init_cache` / prefill KV leaves:
    (G, P, B, S, KV, hd) — batch 2, sequence 3."""
    ax = cache_mod.CacheAxes(batch=2, seq=3)
    return {"k": ax, "v": ax}


# ---------------------------------------------------------------------------
# paged decode (continuous-batching engine)
# ---------------------------------------------------------------------------
def transformer_init_paged_pool(cfg: ModelConfig, n_pages: int,
                                page_size: int) -> Params:
    """Per-layer KV page pools, stacked (G, P, n_pages, page, KV, hd).

    Every layer indexes its own pool with the *same* block tables — a
    sequence's logical block j lives at one page id across all layers, so
    the engine keeps a single (slots, max_pages) table.
    """
    p_period = cfg.pattern_period
    n_groups = cfg.n_layers // p_period
    dt = _dtype(cfg)
    shape = (n_groups, p_period, n_pages, page_size,
             cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def _paged_layers(params: Params, x: jax.Array, pool: Params,
                  cfg: ModelConfig, block):
    """Run every attention block against the stacked paged pool.

    The pool rides in the layer scan's carry, never in its ``xs``/``ys``:
    ``block(bp, x, pool, layer, window)`` writes and reads its layer
    through :func:`repro.models.attention.paged_kv` at flat index
    ``layer = g·P + j``, so no layer's pool is sliced out and stacked back
    and a donated pool is updated in place.  The carry is the pool viewed
    as (G·P, n_pages, ...) (a bitcast), so its layout has no unit axis for
    the compiler to reorder inside the loop.  Unrolled
    (``cfg.scan_layers=False``) the index is a static int.
    """
    p_period = cfg.pattern_period
    n_groups = cfg.n_layers // p_period
    shape = pool["k"].shape
    pool = {k: a.reshape((-1,) + shape[2:]) for k, a in pool.items()}

    def group_body(carry, inp):
        x, pool = carry
        gp, g = inp
        for j in range(p_period):
            bp = jax.tree_util.tree_map(lambda t: t[j], gp)
            x, pool = block(bp, x, pool, g * p_period + j, cfg.window_for(j))
        return (x, pool), None

    (x, pool), _ = _scan(
        group_body, (x, pool),
        (params["blocks"], np.arange(n_groups, dtype=np.int32)), cfg)
    return x, {k: a.reshape(shape) for k, a in pool.items()}


def transformer_decode_paged(params: Params, pool: Params,
                             block_tables: jax.Array, tokens: jax.Array,
                             pos: jax.Array, cfg: ModelConfig,
                             valid_len: jax.Array | None = None):
    """One ragged decode step over the paged KV pool.

    ``pos`` is a (B,) vector — one position per engine slot.  Mirrors
    :func:`transformer_decode` with each layer's dense cache slice
    replaced by its layer of the page pool + the shared block tables.
    ``valid_len`` (optional, (B,)) gates each row's KV write: rows at or
    beyond their cutoff write to the trash page, letting one batched step
    cover a mix of decoding and prefilling/idle slots.
    """
    x = embed_inputs(params, {"tokens": tokens}, cfg)

    def block(bp, x, pool, layer, window):
        return attn_block_decode(bp, x, pool, pos, cfg, window,
                                 block_tables=block_tables,
                                 valid_len=valid_len, layer=layer)

    x, pool = _paged_layers(params, x, pool, cfg, block)
    return project_logits(params, x, cfg), pool


def transformer_verify_chunk(params: Params, pool: Params,
                             block_tables: jax.Array, tokens: jax.Array,
                             start: jax.Array, valid_len: jax.Array,
                             cfg: ModelConfig):
    """Verify a speculative k-token window for every engine slot at once.

    ``tokens`` is (B, C) — row ``b`` holds its committed last token plus
    C-1 draft proposals, covering cache positions ``[start[b],
    start[b] + C)``; writes at or beyond ``valid_len[b]`` land in the
    trash page.  Mirrors :func:`transformer_decode_paged` with each
    single-token block swapped for :func:`attn_block_verify`, so logits
    row ``(b, i)`` is bitwise the sequential decode output at position
    ``start[b] + i`` given the fed window prefix — the property the
    engine's accept rule relies on.
    """
    x = embed_inputs(params, {"tokens": tokens}, cfg)

    def block(bp, x, pool, layer, window):
        return attn_block_verify(bp, x, pool, block_tables, start, valid_len,
                                 cfg, window, layer=layer)

    x, pool = _paged_layers(params, x, pool, cfg, block)
    return project_logits(params, x, cfg), pool


def transformer_prefill_chunk(params: Params, pool: Params,
                              block_tables: jax.Array, tokens: jax.Array,
                              start: jax.Array, valid_len: jax.Array,
                              cfg: ModelConfig):
    """Prefill one fixed-size chunk of a prompt into the paged KV pool.

    ``tokens`` is (B, C) — the engine admits one sequence at a time, B=1 —
    covering prompt positions ``[start, start + C)``; the final chunk is
    zero-padded past ``valid_len`` (pad KV goes to the trash page).
    Returns (logits for all C positions, updated pool): the engine slices
    the last real prompt position's logits out on the host to get the
    sequence's first generated token.
    """
    x = embed_inputs(params, {"tokens": tokens}, cfg)

    def block(bp, x, pool, layer, window):
        return attn_block_prefill_chunk(bp, x, pool, block_tables, start,
                                        valid_len, cfg, window, layer=layer)

    x, pool = _paged_layers(params, x, pool, cfg, block)
    return project_logits(params, x, cfg), pool


# ---------------------------------------------------------------------------
# LatentLM (DeepSeek-V2): latent attention, leading dense layers, then a
# scan over layers whose MLP is a held-expert MoE
# ---------------------------------------------------------------------------
def mla_spec(cfg: ModelConfig) -> attn.MLASpec:
    return attn.MLASpec(
        n_heads=cfg.n_heads, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope=cfg.qk_nope_head_dim, qk_rope=cfg.qk_rope_head_dim,
        v_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta, yarn=cfg.yarn,
        norm_eps=cfg.norm_eps, chunk=cfg.attn_chunk)


def held_spec(cfg: ModelConfig) -> moe.HeldSpec:
    return moe.HeldSpec(
        n_experts=cfg.n_experts, held=tuple(cfg.experts_held),
        top_k=cfg.top_k, d_model=cfg.d_model, d_ff=cfg.d_ff,
        d_shared_ff=cfg.d_shared_ff, act=cfg.act)


def init_latent_block(key, cfg: ModelConfig, dense: bool) -> Params:
    """One layer: latent attention, then a dense MLP of ``d_ff_dense``
    (``dense``) or the held-expert MoE."""
    ks = jax.random.split(key, 2)
    dt = _dtype(cfg)
    p: Params = {
        "norm1": layers.init_rms_norm(cfg.d_model),
        "norm2": layers.init_rms_norm(cfg.d_model),
        "attn": attn.init_mla(ks[0], cfg.d_model, mla_spec(cfg), dt),
    }
    if dense:
        p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff_dense, dt)
    else:
        p["moe"] = moe.init_held_moe(ks[1], held_spec(cfg), dt)
    return p


def init_latent(key, cfg: ModelConfig) -> Params:
    """``dense_blocks`` stacks the ``first_dense_layers`` leading layers,
    ``blocks`` the rest (scanned)."""
    if cfg.n_experts and not cfg.experts_held:
        raise ValueError("a latent MoE needs experts_held: the ids of the "
                         "experts this chip holds")
    ks = jax.random.split(key, 3)
    n_dense = cfg.first_dense_layers
    params = init_embed_head(ks[0], cfg)
    if n_dense:
        params["dense_blocks"] = jax.vmap(
            lambda k: init_latent_block(k, cfg, True))(
                jax.random.split(ks[1], n_dense))
    params["blocks"] = jax.vmap(lambda k: init_latent_block(k, cfg, False))(
        jax.random.split(ks[2], cfg.n_layers - n_dense))
    return params


def _latent_ffn(bp: Params, h: jax.Array, cfg: ModelConfig):
    """(the block's MLP or MoE output, rows routed to its held experts)."""
    if "moe" in bp:
        return moe.held_moe(bp["moe"], h, held_spec(cfg))
    return layers.mlp(bp["mlp"], h, cfg.act), jnp.zeros((), jnp.int32)


def _latent_layers(params: Params, x: jax.Array, carry, cfg: ModelConfig,
                   attend, remat: bool = False):
    """Run the leading dense layers, then scan the rest (rematerialised
    when ``remat``).  ``attend(ap, h, carry, layer)`` is one layer's
    attention over its normed input; it returns (output, carry, y).
    Returns (x, carry, rows routed to held experts summed over layers, the
    ys stacked over layers or None)."""

    def block(bp, x, carry, layer):
        h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
        o, carry, y = attend(bp["attn"], h, carry, layer)
        x = x + o
        h = layers.rms_norm(x, bp["norm2"], cfg.norm_eps)
        f, rows = _latent_ffn(bp, h, cfg)
        return x + f, carry, rows, y

    n_dense = cfg.first_dense_layers
    rows = jnp.zeros((), jnp.int32)
    ys = []
    for i in range(n_dense):
        bp = jax.tree_util.tree_map(lambda t: t[i], params["dense_blocks"])
        x, carry, r, y = block(bp, x, carry, i)
        rows = rows + r
        ys.append(y)

    def body(c, inp):
        x, carry, rows = c
        bp, g = inp
        x, carry, r, y = block(bp, x, carry, n_dense + g)
        return (x, carry, rows + r), y

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    n_scan = cfg.n_layers - n_dense
    (x, carry, rows), y_scan = _scan(
        body, (x, carry, rows),
        (params["blocks"], np.arange(n_scan, dtype=np.int32)), cfg)
    if y_scan is None:
        return x, carry, rows, None
    if ys:
        y_scan = jnp.concatenate([jnp.stack(ys), y_scan])
    return x, carry, rows, y_scan


def latent_forward(params: Params, batch: Params, cfg: ModelConfig,
                   want_cache: bool = False):
    """Full-sequence forward (training, prefill).  The cache is every
    layer's rows, ``{"latent": (L, 1, B, S, cache_dim)}``."""
    x = embed_inputs(params, batch, cfg)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    spec = mla_spec(cfg)

    def attend(ap, h, carry, layer):
        o, rows = attn.mla_full(ap, h, spec, positions)
        return o, carry, (rows if want_cache else None)

    x, _, _, rows = _latent_layers(params, x, None, cfg, attend,
                                   remat=cfg.remat and not want_cache)
    logits = project_logits(params, x, cfg)
    cache = None
    if want_cache:
        cache = {"latent": rows[:, None]}
    return logits, 0.0, cache


def latent_decode(params: Params, cache: Params, tokens: jax.Array,
                  pos: jax.Array, cfg: ModelConfig):
    """One decode step over a dense row cache (the static serve path)."""
    x = embed_inputs(params, {"tokens": tokens}, cfg)
    spec = mla_spec(cfg)
    lat = cache["latent"]                       # (L, 1, B, S, cache_dim)

    def attend(ap, h, carry, layer):
        o, c = attn.mla_decode(ap, h, carry[layer], pos, spec)
        return o, carry.at[layer].set(c), None

    x, lat0, _, _ = _latent_layers(params, x, lat[:, 0], cfg, attend)
    return project_logits(params, x, cfg), {"latent": lat0[:, None]}


def latent_init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    shape = (cfg.n_layers, 1, batch, max_len, mla_spec(cfg).cache_dim)
    return {"latent": jnp.zeros(shape, _dtype(cfg))}


def latent_cache_spec(cfg: ModelConfig) -> Params:
    """(L, 1, B, S, cache_dim) — batch 2, sequence 3."""
    return {"latent": cache_mod.CacheAxes(batch=2, seq=3)}


def latent_init_paged_pool(cfg: ModelConfig, n_pages: int,
                           page_size: int) -> Params:
    """Every layer's latent pages, ``{"latent": (L, 1, n_pages, page,
    cache_dim)}``: a page holds the normed latent and the roped key of
    each of its positions, in every layer; the block tables are shared as
    in :func:`transformer_init_paged_pool`.  Rows fill whole lane tiles
    and no unit axis follows the page axis, so the pool keeps one layout
    from the parameter through the layers' carry and is written in place.
    """
    shape = (cfg.n_layers, 1, n_pages, page_size, mla_spec(cfg).cache_dim)
    return {"latent": jnp.zeros(shape, _dtype(cfg))}


def latent_decode_paged(params: Params, pool: Params,
                        block_tables: jax.Array, tokens: jax.Array,
                        pos: jax.Array, cfg: ModelConfig,
                        valid_len: jax.Array | None = None):
    """One ragged decode step over the latent page pool (absorbed
    attention).  The pool rides in the layers' carry viewed as (L, n_pages,
    ...), as in :func:`_paged_layers`.  Returns (logits, pool, rows routed
    to held experts over every MoE layer)."""
    x = embed_inputs(params, {"tokens": tokens}, cfg)
    spec = mla_spec(cfg)
    shape = pool["latent"].shape
    flat = {"latent": pool["latent"].reshape((-1,) + shape[2:])}

    def attend(ap, h, pool, layer):
        o, pool = attn.mla_paged_decode(ap, h, pool, block_tables, pos, spec,
                                        valid_len=valid_len, layer=layer)
        return o, pool, None

    x, flat, rows, _ = _latent_layers(params, x, flat, cfg, attend)
    return (project_logits(params, x, cfg),
            {"latent": flat["latent"].reshape(shape)}, rows)


# ---------------------------------------------------------------------------
# HybridLM (zamba2): mamba stack + shared attention block
# ---------------------------------------------------------------------------
def init_hybrid(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 3)
    period = cfg.hybrid_attn_every
    n_groups = cfg.n_layers // period
    mspec = mamba_spec(cfg)
    keys = jax.random.split(ks[0], cfg.n_layers).reshape(n_groups, period, -1)
    mamba_blocks = jax.vmap(jax.vmap(
        lambda k: {"norm": layers.init_rms_norm(cfg.d_model),
                   "mamba": ssm.init_mamba(k, mspec, _dtype(cfg))}
    ))(keys)
    params = init_embed_head(ks[1], cfg)
    params["mamba_blocks"] = mamba_blocks
    params["shared_attn"] = init_attn_block(ks[2], cfg)
    return params


def hybrid_forward(params: Params, batch: Params, cfg: ModelConfig,
                   want_cache: bool = False):
    x = embed_inputs(params, batch, cfg)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    mspec = mamba_spec(cfg)
    period = cfg.hybrid_attn_every

    def group_body(x, gp):
        for j in range(period):
            bp = jax.tree_util.tree_map(lambda t: t[j], gp)
            h = layers.rms_norm(x, bp["norm"], cfg.norm_eps)
            x = x + ssm.mamba_forward(bp["mamba"], h, mspec)
        x, kv, _ = attn_block_full(
            params["shared_attn"], x, cfg, positions, None, want_cache)
        return x, kv

    body = group_body
    if cfg.remat and not want_cache:
        body = jax.checkpoint(group_body, prevent_cse=False)
    x, kv_stack = _scan(body, x, params["mamba_blocks"], cfg)
    logits = project_logits(params, x, cfg)
    cache = None
    if want_cache:
        # NOTE: mamba states for continuation decode are rebuilt by the serve
        # path via a short state-prefill; attention cache is exact.
        cache = {"k": kv_stack[0], "v": kv_stack[1]}
    return logits, 0.0, cache


def hybrid_decode(params: Params, cache: Params, tokens: jax.Array,
                  pos: jax.Array, cfg: ModelConfig):
    x = embed_inputs(params, {"tokens": tokens}, cfg)
    mspec = mamba_spec(cfg)
    period = cfg.hybrid_attn_every

    def group_body(x, inp):
        gp, ssm_c, conv_c, kc, vc = inp
        new_ssm, new_conv = [], []
        for j in range(period):
            bp = jax.tree_util.tree_map(lambda t: t[j], gp)
            cj = jax.tree_util.tree_map(lambda t: t[j], conv_c)
            h = layers.rms_norm(x, bp["norm"], cfg.norm_eps)
            mo, mc = ssm.mamba_decode_step(
                bp["mamba"], h, {"ssm": ssm_c[j], "conv": cj}, mspec)
            x = x + mo
            new_ssm.append(mc["ssm"])
            new_conv.append(mc["conv"])
        slot = {"k": kc, "v": vc}
        x, slot = attn_block_decode(params["shared_attn"], x, slot, pos,
                                    cfg, None)
        new_conv = jax.tree_util.tree_map(lambda *t: jnp.stack(t), *new_conv)
        return x, (jnp.stack(new_ssm), new_conv, slot["k"], slot["v"])

    x, (ssm_n, conv_n, kn, vn) = _scan(
        group_body, x,
        (params["mamba_blocks"], cache["ssm"], cache["conv"],
         cache["k"], cache["v"]), cfg)
    logits = project_logits(params, x, cfg)
    return logits, {"ssm": ssm_n, "conv": conv_n, "k": kn, "v": vn}


def hybrid_init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    period = cfg.hybrid_attn_every
    n_groups = cfg.n_layers // period
    mspec = mamba_spec(cfg)
    dt = _dtype(cfg)
    mcache = ssm.init_mamba_cache(batch, mspec, dt)
    return {
        "ssm": jnp.zeros((n_groups, period) + mcache["ssm"].shape,
                         jnp.float32),
        "conv": jax.tree_util.tree_map(
            lambda t: jnp.zeros((n_groups, period) + t.shape, t.dtype),
            mcache["conv"]),
        "k": jnp.zeros((n_groups, batch, max_len, cfg.n_kv_heads,
                        cfg.head_dim), dt),
        "v": jnp.zeros((n_groups, batch, max_len, cfg.n_kv_heads,
                        cfg.head_dim), dt),
    }


def hybrid_cache_spec(cfg: ModelConfig) -> Params:
    """Axis roles of :func:`hybrid_init_cache`: mamba state stacked under
    (G, P) leading dims, shared-attn KV under (G,) — and crucially the
    mamba leaves have NO sequence axis, which is exactly what the old
    shape-matching growth heuristic got wrong when an unrelated dim
    happened to equal the prompt length."""
    m_axes = cache_mod.shift_axes(ssm.mamba_cache_axes(), 2)
    kv = cache_mod.CacheAxes(batch=1, seq=2)
    return {"ssm": m_axes["ssm"], "conv": m_axes["conv"], "k": kv, "v": kv}


# ---------------------------------------------------------------------------
# XLSTMLM: (slstm_every-1) mLSTM + 1 sLSTM per group
# ---------------------------------------------------------------------------
def init_xlstm_lm(key, cfg: ModelConfig) -> Params:
    xs = xlstm_spec(cfg)
    period = cfg.slstm_every or cfg.n_layers
    n_m = period - 1 if cfg.slstm_every else cfg.n_layers
    n_groups = cfg.n_layers // period
    ks = jax.random.split(key, 3)
    mkeys = jax.random.split(ks[0], n_groups * n_m).reshape(n_groups, n_m, -1)
    mlstm_blocks = jax.vmap(jax.vmap(
        lambda k: {"norm": layers.init_rms_norm(cfg.d_model),
                   "cell": xlstm.init_mlstm(k, xs, _dtype(cfg))}
    ))(mkeys)
    params = init_embed_head(ks[1], cfg)
    params["mlstm_blocks"] = mlstm_blocks
    if cfg.slstm_every:
        skeys = jax.random.split(ks[2], n_groups)
        params["slstm_blocks"] = jax.vmap(
            lambda k: {"norm": layers.init_rms_norm(cfg.d_model),
                       "cell": xlstm.init_slstm(k, xs, _dtype(cfg))}
        )(skeys)
    return params


def xlstm_forward(params: Params, batch: Params, cfg: ModelConfig,
                  want_cache: bool = False):
    x = embed_inputs(params, batch, cfg)
    xs_spec = xlstm_spec(cfg)
    has_s = "slstm_blocks" in params

    def group_body(x, gp):
        mgp = gp[0]
        n_m = jax.tree_util.tree_leaves(mgp)[0].shape[0]
        for j in range(n_m):
            bp = jax.tree_util.tree_map(lambda t: t[j], mgp)
            h = layers.rms_norm(x, bp["norm"], cfg.norm_eps)
            mo, _ = xlstm.mlstm_block(bp["cell"], h, xs_spec)
            x = x + mo
        if has_s:
            sp = gp[1]
            h = layers.rms_norm(x, sp["norm"], cfg.norm_eps)
            so, _ = xlstm.slstm_scan(sp["cell"], h, xs_spec)
            x = x + so
        return x, None

    scan_xs = (params["mlstm_blocks"],)
    if has_s:
        scan_xs = (params["mlstm_blocks"], params["slstm_blocks"])
    body = group_body
    if cfg.remat and not want_cache:
        body = jax.checkpoint(group_body, prevent_cse=False)
    x, _ = _scan(body, x, scan_xs, cfg)
    logits = project_logits(params, x, cfg)
    return logits, 0.0, None   # recurrent caches built by serve-path prefill


def xlstm_decode(params: Params, cache: Params, tokens: jax.Array,
                 pos: jax.Array, cfg: ModelConfig):
    x = embed_inputs(params, {"tokens": tokens}, cfg)
    xs_spec = xlstm_spec(cfg)
    has_s = "slstm_blocks" in params

    def group_body(x, inp):
        if has_s:
            mgp, sp, mcache, scache = inp
        else:
            (mgp, mcache) = inp
        n_m = jax.tree_util.tree_leaves(mgp)[0].shape[0]
        new_m = []
        for j in range(n_m):
            bp = jax.tree_util.tree_map(lambda t: t[j], mgp)
            mc = jax.tree_util.tree_map(lambda t: t[j], mcache)
            h = layers.rms_norm(x, bp["norm"], cfg.norm_eps)
            mo, mc = xlstm.mlstm_block(bp["cell"], h, xs_spec,
                                       cache=mc, decode=True)
            x = x + mo
            new_m.append(mc)
        new_m = jax.tree_util.tree_map(lambda *t: jnp.stack(t), *new_m)
        if has_s:
            h = layers.rms_norm(x, sp["norm"], cfg.norm_eps)
            so, s_new = xlstm.slstm_scan(sp["cell"], h, xs_spec, state=scache)
            x = x + so
            return x, (new_m, s_new)
        return x, (new_m,)

    if has_s:
        xs_in = (params["mlstm_blocks"], params["slstm_blocks"],
                 cache["mlstm"], cache["slstm"])
    else:
        xs_in = (params["mlstm_blocks"], cache["mlstm"])
    x, ys = _scan(group_body, x, xs_in, cfg)
    logits = project_logits(params, x, cfg)
    new_cache = {"mlstm": ys[0]}
    if has_s:
        new_cache["slstm"] = ys[1]
    return logits, new_cache


def xlstm_cache_spec(cfg: ModelConfig) -> Params:
    """Axis roles of :func:`xlstm_init_cache`: mLSTM state stacked under
    (G, n_m), sLSTM state under (G,); all O(1) in sequence length."""
    spec = {"mlstm": cache_mod.shift_axes(xlstm.mlstm_cache_axes(), 2)}
    if cfg.slstm_every:
        spec["slstm"] = cache_mod.shift_axes(xlstm.slstm_cache_axes(), 1)
    return spec


def xlstm_init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    xs = xlstm_spec(cfg)
    period = cfg.slstm_every or cfg.n_layers
    n_m = period - 1 if cfg.slstm_every else cfg.n_layers
    n_groups = cfg.n_layers // period
    mc = xlstm.init_mlstm_cache(batch, xs, _dtype(cfg))
    cache = {
        "mlstm": jax.tree_util.tree_map(
            lambda t: jnp.broadcast_to(
                t, (n_groups, n_m) + t.shape).copy(), mc)
    }
    if cfg.slstm_every:
        sc = xlstm.init_slstm_cache(batch, xs)
        cache["slstm"] = jax.tree_util.tree_map(
            lambda t: jnp.broadcast_to(t, (n_groups,) + t.shape).copy(), sc)
    return cache
