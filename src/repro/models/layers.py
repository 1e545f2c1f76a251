"""Shared building blocks: norms, RoPE, embeddings, MLPs — all SoD-aware.

Every weight matmul goes through :func:`repro.core.sod.apply`, so a layer
whose parameter leaf is a packed container (TiledCSC / BlockCSR) transparently
runs the Sparse-on-Dense datapath; dense leaves bypass decompression.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import sod

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype=jnp.bfloat16) -> jax.Array:
    scale = (1.0 / d_in) ** 0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.bfloat16) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + gamma.astype(jnp.float32))).astype(dtype)


def init_rms_norm(d: int, dtype=jnp.float32) -> jax.Array:
    return jnp.zeros((d,), dtype)   # gamma stored as (1 + g)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rope scaling (arXiv:2309.00071), as DeepSeek-V2 configures it:
    context stretched ``factor`` times past ``original_max_position``,
    frequencies blended between interpolated and original by a linear ramp
    over the dims whose rotations at ``original_max_position`` lie between
    ``beta_slow`` and ``beta_fast``."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


def yarn_mscale(factor: float, mscale: float) -> float:
    """``0.1 * mscale * ln(factor) + 1`` (1 for ``factor <= 1``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(head_dim: int, theta: float, yarn: Yarn) -> jax.Array:
    """Per-pair rotation frequencies under YaRN scaling, (head_dim/2,)."""
    def corr_dim(rotations: float) -> float:
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extra = rope_frequencies(head_dim, theta)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def yarn_softmax_scale(head_dim: int, yarn: Yarn | None) -> float:
    """Attention logit scale: ``head_dim**-0.5``, times
    ``yarn_mscale(factor, mscale_all_dim)**2`` under YaRN."""
    scale = head_dim ** -0.5
    if yarn is not None and yarn.mscale_all_dim:
        scale *= yarn_mscale(yarn.factor, yarn.mscale_all_dim) ** 2
    return scale


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn: Yarn | None = None) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).  Rotate-half
    layout; ``yarn`` scales the frequencies and cos/sin by
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``."""
    hd = x.shape[-1]
    if yarn is None:
        freqs = rope_frequencies(hd, theta)                   # (hd/2,)
    else:
        freqs = yarn_frequencies(hd, theta, yarn)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                              # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# activations / softcap
# ---------------------------------------------------------------------------
def activate(x: jax.Array, kind: str) -> jax.Array:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown activation {kind!r}")


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    """Gemma-2 logit soft-capping: cap * tanh(x / cap)."""
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU family)
# ---------------------------------------------------------------------------
def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.bfloat16) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, d_model, d_ff, dtype),
        "w_up": dense_init(k2, d_model, d_ff, dtype),
        "w_down": dense_init(k3, d_ff, d_model, dtype),
    }


def mlp(params: Params, x: jax.Array, act: str = "silu",
        spmd="auto", plans=None) -> jax.Array:
    """SwiGLU MLP.  ``spmd`` forwards to the packed-matmul dispatcher: under
    an active mesh the packed projections run shard_map-wrapped (an explicit
    :class:`repro.runtime.spmd.SpmdPlan` pins the partitioning; ``None``
    opts out).  ``plans`` maps projection names (``w_gate``/``w_up``/
    ``w_down``) to their :class:`repro.core.plan.PackPlan`, so each matmul
    dispatches with its layer's plan — absent entries fall back to the
    active :class:`~repro.core.plan.ModelPlan`'s layout lookup."""
    pl = (plans or {}).get
    gate = sod.apply(x, params["w_gate"], spmd=spmd, plan=pl("w_gate"))
    up = sod.apply(x, params["w_up"], spmd=spmd, plan=pl("w_up"))
    return sod.apply(activate(gate, act) * up, params["w_down"], spmd=spmd,
                     plan=pl("w_down"))


# ---------------------------------------------------------------------------
# embedding / LM head
# ---------------------------------------------------------------------------
def embed(table: jax.Array, tokens: jax.Array, scale: bool = False) -> jax.Array:
    x = jnp.take(table, tokens, axis=0)
    if scale:  # gemma-style sqrt(d) embedding scale
        x = x * jnp.asarray(x.shape[-1] ** 0.5, x.dtype)
    return x


def lm_head(x: jax.Array, table_or_w, tied: bool, cap: float | None = None,
            spmd="auto", plan=None):
    """Project to vocab logits in float32 (loss numerics).  ``plan`` is the
    head's :class:`repro.core.plan.PackPlan` (or None for active-plan /
    layout fallback)."""
    if tied:
        w = table_or_w.T if isinstance(table_or_w, jax.Array) else table_or_w
        logits = jnp.dot(x, w, preferred_element_type=jnp.float32)
    else:
        logits = sod.apply(x, table_or_w, out_dtype=jnp.float32, spmd=spmd,
                           plan=plan)
    return softcap(logits.astype(jnp.float32), cap)


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: jax.Array | None = None):
    """Mean token cross-entropy; logits (..., V) f32, targets (...) int."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
