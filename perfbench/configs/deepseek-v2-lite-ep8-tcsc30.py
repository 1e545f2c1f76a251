"""The pruned checkpoint and plain float32 reference of
deepseek-v2-lite-ep8-tcsc30.

DeepSeek-V2-Lite (arXiv:2405.04434) is a pre-norm decoder.  Attention is
multi-head latent attention (§2.1) without query compression: a token's
query is ``h W_Q``, ``qk_nope_head_dim`` un-roped and ``qk_rope_head_dim``
roped dims a head; its keys and values come from a latent ``c_KV``, the
first ``kv_lora_rank`` dims of ``h W_KV_A`` RMS-normed, up-projected by
``W_KV_B`` to every head's un-roped key and value, and its roped key is the
last ``qk_rope_head_dim`` dims of ``h W_KV_A``, one for all heads.  Rotary
embeddings use YaRN scaling (``rope_scaling``) and the logits are scaled by
``mscale(factor, mscale_all_dim)**2 / sqrt(qk_nope + qk_rope)``.  Layer
0's MLP is a dense SiLU-gated MLP of ``intermediate_size``; every later
layer is DeepSeekMoE (§2.2): a softmax router over ``router_experts``
experts picks ``num_experts_per_tok``, each weighted by its unnormalised
score (``norm_topk_prob`` false, ``routed_scaling_factor`` 1), plus
``n_shared_experts`` shared experts as one ungated MLP of their summed
width.  The output head is untied.

The configuration is one chip's share of eight-way expert parallelism:
the router keeps its published width and top-k, and only the experts this
chip holds (``experts_held``) add their part of the result; the reference
is given the same share.  The matrices are seeded here (:data:`ROLES`,
:func:`matrix_shape`; a routed expert's at (layer, expert id)); every
matrix the configuration lists in ``sparsity.matrices`` is magnitude-pruned
to its density as in ``internlm2-1.8b-tcsc30``, the others (the embedding,
``W_KV_A``, ``W_KV_B`` and the router) are not.  :func:`checkpoint_matrix`
makes the checkpoint from the seed, one matrix at a time.

The reference is written from that description in ``jax.numpy``: float32
activations and ``highest`` matmul precision throughout, latent attention
as the paper writes it (keys and values up-projected, not absorbed), no
kernel, cache or batching of the program, one layer at a time.  It routes
on its own float32 scores, so where a position's 6th and 7th scores lie
within the program's rounding the two may pick different experts:
:func:`gaps` reports, on standard error, the share of (position, MoE
layer) whose 6th-7th score margin is under ``NEAR_TIE``.

Departures from the published model, each a fixed relabelling of random
weights: rotary embeddings in rotate-half layout where the published
checkpoint interleaves the roped dims; the roped and un-roped query dims
are one matrix's columns in that order.

:func:`first_choices` runs the same decoder a step below the
configuration's bf16, every weight and every tensor a bf16 program stores
rounded through float8 e4m3: the control (``control.py --controls
fp8_reference``).
"""
from __future__ import annotations

import functools
import json
import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights

HEAD_BLOCK = 256          # positions per block of the output projection
E4M3_MAX = 448.0          # float8 e4m3's largest finite value
NEAR_TIE = 1e-3           # a 6th-7th router score margin reported as tied
# the order numbers each role's seeded key (weights.role_id): fixed
ROLES = ("embed", "head", "wq", "wkv_a", "wkv_b", "wo", "w_gate", "w_up",
         "w_down", "router", "shared_w_gate", "shared_w_up",
         "shared_w_down", "expert_w_gate", "expert_w_up", "expert_w_down")
EMBED_STD = 0.02


def matrix_shape(cfg: dict, role: str) -> tuple[int, int]:
    """(fan_in, fan_out) of a role's matrix under ``cfg`` (HF key names);
    ``w_*`` is layer 0's dense MLP, ``expert_w_*`` one routed expert."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    r, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    fs = fe * cfg["n_shared_experts"]
    return {"embed": (cfg["vocab_size"], d), "head": (d, cfg["vocab_size"]),
            "wq": (d, h * (nope + rope)), "wkv_a": (d, r + rope),
            "wkv_b": (r, h * (nope + v)), "wo": (h * v, d),
            "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
            "router": (d, cfg["router_experts"]),
            "shared_w_gate": (d, fs), "shared_w_up": (d, fs),
            "shared_w_down": (fs, d),
            "expert_w_gate": (d, fe), "expert_w_up": (d, fe),
            "expert_w_down": (fe, d)}[role]


def seeded_matrix(cfg: dict, seed: int, role: str, layer: int,
                  *index: int) -> jax.Array:
    """One matrix as initialised: linear maps N(0, 1/fan_in), the
    embedding N(0, 0.02^2), in bf16."""
    shape = matrix_shape(cfg, role)
    std = EMBED_STD if role == "embed" else shape[0] ** -0.5
    return weights.layer_matrix(seed, role, layer, *index, shape=shape,
                                std=std, numbered=ROLES)


def magnitude_prune(w: jax.Array, density: float) -> jax.Array:
    """Zero all but the weights whose |value| is at least the k-th largest
    (k = round(density * size)), found by bisection on the bit patterns of
    the bf16 magnitudes, which order as the values do."""
    k = max(int(round(w.size * density)), 1)
    bits = jax.lax.bitcast_convert_type(jnp.abs(w).astype(jnp.bfloat16),
                                        jnp.uint16).astype(jnp.int32)

    def body(_, lohi):
        lo, hi = lohi                  # count(bits >= lo) >= k > count(>= hi)
        mid = (lo + hi) // 2
        ok = jnp.sum(bits >= mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = jax.lax.fori_loop(0, 17, body, (jnp.int32(0), jnp.int32(1 << 16)))
    return jnp.where(bits >= lo, w, 0)


_prune = jax.jit(magnitude_prune, static_argnames="density")


def checkpoint_matrix(cfg: dict, seed: int, role: str, layer: int,
                      *index: int) -> jax.Array:
    """One matrix of the pruned bf16 checkpoint: a role the configuration
    packs (``sparsity.matrices``) is pruned to its density."""
    w = seeded_matrix(cfg, seed, role, layer, *index)
    sp = cfg["sparsity"]
    density = sp.get("density", 1.0)
    if role not in sp.get("matrices", ()) or density >= 1.0:
        return w
    return _prune(w, density=density)


def _fp8(x):
    """``x`` rounded through float8 e4m3, one scale a tensor (its largest
    magnitude onto e4m3's largest finite value), back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


_round = jax.jit(_fp8)


def _keep(x):
    return x


def _matrix(cfg, seed, role, layer, *index, fp8=False):
    w = checkpoint_matrix(cfg, seed, role, layer, *index).astype(jnp.float32)
    return _round(w) if fp8 else w


def _layer_weights(cfg: dict, seed: int, layer: int, fp8: bool) -> dict:
    """A layer's matrices: attention, then layer 0's dense MLP or the
    router, the shared experts and every held expert (stacked)."""
    m = functools.partial(_matrix, cfg, seed, layer=layer, fp8=fp8)
    w = {r: m(r) for r in ("wq", "wkv_a", "wkv_b", "wo")}
    if layer < cfg["first_k_dense_replace"]:
        w.update({r: m(r) for r in ("w_gate", "w_up", "w_down")})
        return w
    w["router"] = m("router")
    for r in ("w_gate", "w_up", "w_down"):
        w[f"shared_{r}"] = m(f"shared_{r}")
        w[f"expert_{r}"] = jnp.stack([
            _matrix(cfg, seed, f"expert_{r}", layer, e, fp8=fp8)
            for e in cfg["experts_held"]])
    return w


def _rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """YaRN's rotation frequencies of the roped dims (DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding``)."""
    rs, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base, orig = float(cfg["rope_theta"]), rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    return extra / rs["factor"] * ramp + extra * (1 - ramp)


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return m * m / math.sqrt(cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])


def _rope(x, inv_freq, mscale):
    """x (B, S, H, d), positions 0..S-1, rotate-half convention."""
    d = x.shape[-1]
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos = (jnp.cos(ang) * mscale)[None, :, None]
    sin = (jnp.sin(ang) * mscale)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mlp(h, wg, wu, wd, r):
    return r(r(jax.nn.silu(h @ wg) * (h @ wu)) @ wd)


@functools.partial(jax.jit, static_argnames=(
    "heads", "nope", "rope", "lora", "eps", "scale", "mscale", "top_k",
    "held", "fp8"))
def _block(x, w, inv_freq, *, heads, nope, rope, lora, eps, scale, mscale,
           top_k, held, fp8=False):
    """One layer; returns (x, each position's 6th-7th router score margin,
    or +inf in a dense layer).  ``fp8`` rounds what a bf16 program stores
    (the normed input of each projection, the query, the latent row, keys
    and values, the attention output, each MLP's hidden product and
    output, and the residual stream) through float8 e4m3."""
    r = _fp8 if fp8 else _keep
    b, s, _ = x.shape
    with jax.default_matmul_precision("highest"):
        h = r(_rms_norm(x, eps))
        q = (h @ w["wq"]).reshape(b, s, heads, nope + rope)
        q = r(jnp.concatenate(
            [q[..., :nope], _rope(q[..., nope:], inv_freq, mscale)], -1))
        kv = h @ w["wkv_a"]
        c = r(_rms_norm(kv[..., :lora], eps))
        k_pe = r(_rope(kv[:, :, None, lora:], inv_freq, mscale))
        kvb = r((c @ w["wkv_b"]).reshape(b, s, heads, -1))
        k = jnp.concatenate(
            [kvb[..., :nope], jnp.broadcast_to(k_pe, (b, s, heads, rope))],
            -1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = r(jnp.einsum("bhqk,bkhd->bqhd", p, kvb[..., nope:])
              .reshape(b, s, -1))
        x = r(x + o @ w["wo"])
        h = r(_rms_norm(x, eps))
        if "router" not in w:
            return r(x + _mlp(h, w["w_gate"], w["w_up"], w["w_down"], r)), \
                jnp.full((b, s), jnp.inf)
        probs = jax.nn.softmax(h @ w["router"], axis=-1)
        top = jax.lax.top_k(probs, top_k + 1)[0]
        margin = top[..., top_k - 1] - top[..., top_k]
        vals, ids = jax.lax.top_k(probs, top_k)
        out = _mlp(h, w["shared_w_gate"], w["shared_w_up"],
                   w["shared_w_down"], r)
        for i, e in enumerate(held):
            gate = jnp.sum(jnp.where(ids == e, vals, 0.0), -1)[..., None]
            out = out + gate * _mlp(h, w["expert_w_gate"][i],
                                    w["expert_w_up"][i],
                                    w["expert_w_down"][i], r)
        x = r(x + out)
    return x, margin


def _check(cfg: dict) -> None:
    if (cfg["scoring_func"] != "softmax" or cfg["topk_method"] != "greedy"
            or cfg["norm_topk_prob"] or cfg["routed_scaling_factor"] != 1
            or cfg["q_lora_rank"] is not None or cfg["moe_layer_freq"] != 1
            or cfg["hidden_act"] != "silu" or cfg["tie_word_embeddings"]
            or cfg["rope_scaling"]["type"] != "yarn"):
        raise ValueError("the reference is written for DeepSeek-V2-Lite's "
                         "routing, attention and head only")


def _hidden(cfg: dict, seed: int, tokens: np.ndarray, fp8: bool):
    """The decoder's normed last hidden state of ``tokens`` (B, S), and
    each (MoE layer, position)'s 6th-7th router score margin."""
    _check(cfg)
    eps = cfg["rms_norm_eps"]
    rs = cfg["rope_scaling"]
    inv_freq = jnp.asarray(yarn_inv_freq(cfg))
    mscale = (yarn_mscale(rs["factor"], rs["mscale"])
              / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    embed = _matrix(cfg, seed, "embed", 0, fp8=fp8)
    x = embed[jnp.asarray(tokens)]
    del embed
    margins = []
    for layer in range(cfg["num_hidden_layers"]):
        x, margin = _block(
            x, _layer_weights(cfg, seed, layer, fp8), inv_freq,
            heads=cfg["num_attention_heads"], nope=cfg["qk_nope_head_dim"],
            rope=cfg["qk_rope_head_dim"], lora=cfg["kv_lora_rank"], eps=eps,
            scale=softmax_scale(cfg), mscale=mscale,
            top_k=cfg["num_experts_per_tok"],
            held=tuple(cfg["experts_held"]), fp8=fp8)
        if layer >= cfg["first_k_dense_replace"]:
            margins.append(margin)
    h = _rms_norm(x, eps)
    return (_round(h) if fp8 else h), jnp.stack(margins)


@jax.jit
def _head_gaps(h, w_head, query):
    """For each position: the best logit less the logit of ``query``
    (-1: not asked, gives 0)."""
    with jax.default_matmul_precision("highest"):
        logits = h @ w_head
    got = jnp.take_along_axis(logits, jnp.maximum(query, 0)[..., None],
                              -1)[..., 0]
    return jnp.where(query >= 0, jnp.max(logits, -1) - got, 0.0)


def near_ties(margins, query: np.ndarray) -> dict:
    """Share of (MoE layer, position) up to each row's last asked position
    whose 6th-7th router score margin is under ``NEAR_TIE``."""
    asked = np.asarray(query) >= 0
    last = np.where(asked.any(1), asked.shape[1] - 1
                    - np.argmax(asked[:, ::-1], 1), -1)
    real = np.arange(asked.shape[1])[None, :] <= last[:, None]
    m = np.asarray(margins)[:, real]
    return {"router_near_ties": float(np.mean(m < NEAR_TIE)) if m.size
            else 0.0, "router_margins": int(m.size)}


def gaps(cfg: dict, seed: int, tokens: np.ndarray,
         query: np.ndarray) -> np.ndarray:
    """Reference logit gaps.

    ``tokens`` (B, S) are the sequences fed in (prompt, then served tokens);
    ``query[b, i]`` is the token asked about at position ``i``, the one
    predicted to follow ``tokens[b, :i + 1]``, or -1.  Returns, per
    position, by how much the reference's logit of that token lies below
    its best logit there: 0 where it is the reference's own first choice.
    The share of near-tied router margins goes to standard error.
    """
    h, margins = _hidden(cfg, seed, tokens, fp8=False)
    print(json.dumps(near_ties(margins, query)), file=sys.stderr, flush=True)
    w_head = _matrix(cfg, seed, "head", 0)
    out = np.zeros(query.shape, np.float32)
    for i in range(0, h.shape[1], HEAD_BLOCK):
        out[:, i:i + HEAD_BLOCK] = np.asarray(_head_gaps(
            h[:, i:i + HEAD_BLOCK], w_head,
            jnp.asarray(query[:, i:i + HEAD_BLOCK])))
    return out


@jax.jit
def _head_logits(h, w_head):
    with jax.default_matmul_precision("highest"):
        return h @ w_head


def logits(cfg: dict, seed: int, tokens: np.ndarray) -> np.ndarray:
    """The reference's logits at every position of ``tokens`` (B, S)."""
    h, _ = _hidden(cfg, seed, tokens, fp8=False)
    return np.asarray(_head_logits(h, _matrix(cfg, seed, "head", 0)))


@jax.jit
def _head_first(h, w_head):
    with jax.default_matmul_precision("highest"):
        return jnp.argmax(h @ w_head, -1).astype(jnp.int32)


def first_choices(cfg: dict, seed: int, tokens: np.ndarray) -> np.ndarray:
    """The token the decoder computed in float8 e4m3 puts first at every
    position of ``tokens`` (B, S): the control's query."""
    h, _ = _hidden(cfg, seed, tokens, fp8=True)
    w_head = _matrix(cfg, seed, "head", 0, fp8=True)
    return np.concatenate([np.asarray(_head_first(h[:, i:i + HEAD_BLOCK],
                                                  w_head))
                           for i in range(0, h.shape[1], HEAD_BLOCK)], 1)
