"""The pruned checkpoint and plain float32 reference of internlm2-1.8b-tcsc30.

InternLM2 (arXiv:2403.17297) is a pre-norm decoder of the LLaMA design:
RMSNorm, grouped-query attention with rotary position embeddings (rotate
half, base ``rope_theta``), a SiLU-gated MLP and an untied output head.  The
configuration's checkpoint prunes every projection and the head to 30% of
its weights by magnitude, per matrix, keeping each weight whose |value| is
at least the k-th largest, k = round(0.3 * size): :func:`checkpoint_matrix`
makes it from the seed, one matrix at a time.  The system under test is
handed this checkpoint and packs it; the reference reads it again.

The reference is written from that description in ``jax.numpy``: float32
activations and ``highest`` matmul precision throughout, no kernel, cache or
batching of the program, one layer at a time, so it fits beside nothing
else on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights

HEAD_BLOCK = 256          # positions per block of the output projection


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


def checkpoint_matrix(cfg: dict, seed: int, role: str,
                      layer: int) -> jax.Array:
    """One matrix of the pruned bf16 checkpoint (the embedding is not
    pruned)."""
    w = weights.layer_matrix(seed, role, layer, cfg)
    if role == "embed":
        return w
    return _prune(w, density=cfg["sparsity"]["density"])


def _layer_weights(cfg: dict, seed: int, layer: int) -> dict:
    return {r: checkpoint_matrix(cfg, seed, r, layer).astype(jnp.float32)
            for r in weights.LAYER_ROLES}


def _rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, theta):
    """x (B, S, H, hd), positions 0..S-1, rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "hd",
                                             "eps", "theta"))
def _block(x, w, *, heads, kv_heads, hd, eps, theta):
    b, s, _ = x.shape
    with jax.default_matmul_precision("highest"):
        h = _rms_norm(x, eps)
        q = _rope((h @ w["wq"]).reshape(b, s, heads, hd), theta)
        k = _rope((h @ w["wk"]).reshape(b, s, kv_heads, hd), theta)
        v = (h @ w["wv"]).reshape(b, s, kv_heads, hd)
        g = heads // kv_heads                    # query head i reads kv i // g
        q = q.reshape(b, s, kv_heads, g, hd)
        scores = jnp.einsum("bqkgd,bckd->bkgqc", q, k) * hd ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgqc,bckd->bqkgd", p, v).reshape(b, s, heads * hd)
        x = x + o @ w["wo"]
        h = _rms_norm(x, eps)
        x = x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return x


@jax.jit
def _head_gaps(h, w_head, query):
    """For each position: the best logit less the logit of ``query``
    (-1: not asked, gives 0)."""
    with jax.default_matmul_precision("highest"):
        logits = h @ w_head
    got = jnp.take_along_axis(logits, jnp.maximum(query, 0)[..., None],
                              -1)[..., 0]
    return jnp.where(query >= 0, jnp.max(logits, -1) - got, 0.0)


def gaps(cfg: dict, seed: int, tokens: np.ndarray,
         query: np.ndarray) -> np.ndarray:
    """Reference logit gaps.

    ``tokens`` (B, S) are the sequences fed in (prompt, then served tokens);
    ``query[b, i]`` is the token asked about at position ``i``, the one
    predicted to follow ``tokens[b, :i + 1]``, or -1.  Returns, per
    position, by how much the reference's logit of that token lies below
    its best logit there: 0 where it is the reference's own first choice.
    """
    eps = cfg["rms_norm_eps"]
    embed = checkpoint_matrix(cfg, seed, "embed", 0).astype(jnp.float32)
    x = embed[jnp.asarray(tokens)]
    del embed
    for layer in range(cfg["num_hidden_layers"]):
        x = _block(x, _layer_weights(cfg, seed, layer),
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                   eps=eps, theta=float(cfg["rope_theta"]))
    h = _rms_norm(x, eps)
    w_head = checkpoint_matrix(cfg, seed, "head", 0).astype(jnp.float32)
    out = np.zeros(query.shape, np.float32)
    for i in range(0, h.shape[1], HEAD_BLOCK):
        out[:, i:i + HEAD_BLOCK] = np.asarray(_head_gaps(
            h[:, i:i + HEAD_BLOCK], w_head,
            jnp.asarray(query[:, i:i + HEAD_BLOCK])))
    return out
