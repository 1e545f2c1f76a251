"""The pruned checkpoint and plain float32 reference of internlm2-1.8b-tcsc30.

InternLM2 (arXiv:2403.17297) is a pre-norm decoder of the LLaMA design:
RMSNorm, grouped-query attention with rotary position embeddings (rotate
half, base ``rope_theta``), a SiLU-gated MLP and an untied output head.  Its
matrices are seeded here (:data:`ROLES`, :func:`matrix_shape`).  The
configuration's checkpoint prunes every projection and the head to its
``sparsity.density`` (30%) of weights by magnitude, per matrix, keeping
each weight whose |value| is at least the k-th largest, k = round(density
* size); a configuration that states no density, as internlm2-1.8b-dense,
which loads this module, prunes nothing.  :func:`checkpoint_matrix` makes
the checkpoint from the seed, one matrix at a time.  The system under test
is handed this checkpoint and packs it; the reference reads it again.

The reference is written from that description in ``jax.numpy``: float32
activations and ``highest`` matmul precision throughout, no kernel, cache or
batching of the program, one layer at a time, so it fits beside nothing
else on the chip.  :func:`first_choices` runs the same decoder a step below
the configuration's bf16, every weight and every tensor a bf16 program
stores rounded through float8 e4m3: the control of a configuration whose
program has no lower-precision path of its own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights

HEAD_BLOCK = 256          # positions per block of the output projection
E4M3_MAX = 448.0          # float8 e4m3's largest finite value
# the order numbers each role's seeded key (weights.role_id): fixed
ROLES = ("embed", "head", "wq", "wk", "wv", "wo", "w_gate", "w_up",
         "w_down")
LAYER_ROLES = ROLES[2:]
EMBED_STD = 0.02


def matrix_shape(cfg: dict, role: str) -> tuple[int, int]:
    """(fan_in, fan_out) of a role's matrix under ``cfg`` (HF key names)."""
    d = cfg["hidden_size"]
    hd = cfg["head_dim"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    f = cfg["intermediate_size"]
    v = cfg["vocab_size"]
    return {"embed": (v, d), "head": (d, v), "wq": (d, q), "wk": (d, kv),
            "wv": (d, kv), "wo": (q, d), "w_gate": (d, f), "w_up": (d, f),
            "w_down": (f, d)}[role]


def seeded_matrix(cfg: dict, seed: int, role: str, layer: int) -> jax.Array:
    """One matrix as initialised: linear maps N(0, 1/fan_in), the
    embedding N(0, 0.02^2), in bf16."""
    shape = matrix_shape(cfg, role)
    std = EMBED_STD if role == "embed" else shape[0] ** -0.5
    return weights.layer_matrix(seed, role, layer, shape=shape, std=std,
                                numbered=ROLES)


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
    w = seeded_matrix(cfg, seed, role, layer)
    density = cfg["sparsity"].get("density", 1.0)
    if role == "embed" or density >= 1.0:
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


def _matrix(cfg: dict, seed: int, role: str, layer: int, fp8: bool):
    w = checkpoint_matrix(cfg, seed, role, layer).astype(jnp.float32)
    return _round(w) if fp8 else w


def _layer_weights(cfg: dict, seed: int, layer: int,
                   fp8: bool = False) -> dict:
    return {r: _matrix(cfg, seed, r, layer, fp8) for r in LAYER_ROLES}


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
                                             "eps", "theta", "fp8"))
def _block(x, w, *, heads, kv_heads, hd, eps, theta, fp8=False):
    """One layer.  ``fp8`` rounds what a bf16 program stores (the
    normed input of each projection, q, k and v, the attention output, the
    MLP's hidden product and the residual stream) through float8 e4m3."""
    r = _fp8 if fp8 else _keep
    b, s, _ = x.shape
    with jax.default_matmul_precision("highest"):
        h = r(_rms_norm(x, eps))
        q = r(_rope((h @ w["wq"]).reshape(b, s, heads, hd), theta))
        k = r(_rope((h @ w["wk"]).reshape(b, s, kv_heads, hd), theta))
        v = r((h @ w["wv"]).reshape(b, s, kv_heads, hd))
        g = heads // kv_heads                    # query head i reads kv i // g
        q = q.reshape(b, s, kv_heads, g, hd)
        scores = jnp.einsum("bqkgd,bckd->bkgqc", q, k) * hd ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = r(jnp.einsum("bkgqc,bckd->bqkgd", p, v).reshape(b, s, heads * hd))
        x = r(x + o @ w["wo"])
        h = r(_rms_norm(x, eps))
        x = r(x + r(jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"]))
              @ w["w_down"])
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


def _hidden(cfg: dict, seed: int, tokens: np.ndarray, fp8: bool):
    """The decoder's normed last hidden state of ``tokens`` (B, S)."""
    eps = cfg["rms_norm_eps"]
    embed = _matrix(cfg, seed, "embed", 0, fp8)
    x = embed[jnp.asarray(tokens)]
    del embed
    for layer in range(cfg["num_hidden_layers"]):
        x = _block(x, _layer_weights(cfg, seed, layer, fp8),
                   heads=cfg["num_attention_heads"],
                   kv_heads=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                   eps=eps, theta=float(cfg["rope_theta"]), fp8=fp8)
    h = _rms_norm(x, eps)
    return _round(h) if fp8 else h


def gaps(cfg: dict, seed: int, tokens: np.ndarray,
         query: np.ndarray) -> np.ndarray:
    """Reference logit gaps.

    ``tokens`` (B, S) are the sequences fed in (prompt, then served tokens);
    ``query[b, i]`` is the token asked about at position ``i``, the one
    predicted to follow ``tokens[b, :i + 1]``, or -1.  Returns, per
    position, by how much the reference's logit of that token lies below
    its best logit there: 0 where it is the reference's own first choice.
    """
    h = _hidden(cfg, seed, tokens, fp8=False)
    w_head = _matrix(cfg, seed, "head", 0, fp8=False)
    out = np.zeros(query.shape, np.float32)
    for i in range(0, h.shape[1], HEAD_BLOCK):
        out[:, i:i + HEAD_BLOCK] = np.asarray(_head_gaps(
            h[:, i:i + HEAD_BLOCK], w_head,
            jnp.asarray(query[:, i:i + HEAD_BLOCK])))
    return out


@jax.jit
def _head_first(h, w_head):
    with jax.default_matmul_precision("highest"):
        return jnp.argmax(h @ w_head, -1).astype(jnp.int32)


def first_choices(cfg: dict, seed: int, tokens: np.ndarray) -> np.ndarray:
    """The token the decoder computed in float8 e4m3 puts first at every
    position of ``tokens`` (B, S): the control's query."""
    h = _hidden(cfg, seed, tokens, fp8=True)
    w_head = _matrix(cfg, seed, "head", 0, fp8=True)
    return np.concatenate([np.asarray(_head_first(h[:, i:i + HEAD_BLOCK],
                                                  w_head))
                           for i in range(0, h.shape[1], HEAD_BLOCK)], 1)
