"""Seeded weights of a configuration, in the published parameterisation.

Part of the yardstick: the harness feeds these values to the system under
test, and the reference regenerates them here, one layer at a time, to
check what the system served.  Nothing here imports the program.

Every matrix of layer ``l`` with role ``r`` is drawn from its own key,
``fold_in(fold_in(seed_key, ROLES.index(r)), l)``, so one layer's matrices
can be made without the others.  Values follow the usual random
initialisation: linear maps N(0, 1/fan_in), the embedding N(0, 0.02^2),
RMSNorm weights 1.  They are drawn in float32 and stored in the served
dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ROLES = ("embed", "head", "wq", "wk", "wv", "wo", "w_gate", "w_up",
         "w_down")
LAYER_ROLES = ROLES[2:]
EMBED_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, all of its bits used
    (``PRNGKey`` alone keeps only the low 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def role_key(seed: int, role: str) -> jax.Array:
    return jax.random.fold_in(seed_key(seed), ROLES.index(role))


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


def _std(role: str, fan_in: int) -> float:
    return EMBED_STD if role == "embed" else fan_in ** -0.5


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, *, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_matrix(seed: int, role: str, layer: int, cfg: dict,
                 dtype=jnp.bfloat16) -> jax.Array:
    """One layer's matrix of ``role`` (layer 0 for embed and head)."""
    shape = matrix_shape(cfg, role)
    key = jax.random.fold_in(role_key(seed, role), layer)
    return _normal(key, shape=shape, std=_std(role, shape[0]),
                   dtype=jnp.dtype(dtype))
