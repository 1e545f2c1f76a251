"""Seeded weights, in the published parameterisation.

Part of the yardstick: the harness feeds these values to the system under
test, and the reference regenerates them here, one layer at a time, to
check what the system served.  Nothing here imports the program, and
nothing here knows a model: a configuration's module names its matrices
and gives their shapes and scales.

Every matrix is drawn from its own key: the seed's, folded with the
matrix's role, then its layer, then any further index (an expert, say), so
one matrix can be made without the others.  A role's number is its place
in the configuration module's ``numbered`` roles where it is listed there,
so ``fold_in(fold_in(seed_key, numbered.index(role)), layer)``, and
otherwise a stable hash of its name, which adding another role cannot
change.  Values follow the usual random initialisation, drawn in float32
with the standard deviation the module gives and stored in the served
dtype.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed, all of its bits used
    (``PRNGKey`` alone keeps only the low 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def role_id(role: str, numbered: tuple[str, ...] = ()) -> int:
    """The number a role's key is folded with: its place in ``numbered``,
    else the CRC-32 of its name with the top bit set, so that it never
    meets a place."""
    if role in numbered:
        return numbered.index(role)
    return zlib.crc32(role.encode()) | 0x80000000


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, *, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def layer_matrix(seed: int, role: str, layer: int, *index: int,
                 shape: tuple[int, ...], std: float,
                 numbered: tuple[str, ...] = (),
                 dtype=jnp.bfloat16) -> jax.Array:
    """The matrix of ``role`` in ``layer`` (0 for one outside the layers)
    at further ``index``, N(0, std^2) of ``shape``."""
    key = jax.random.fold_in(
        jax.random.fold_in(seed_key(seed), role_id(role, numbered)), layer)
    for i in index:
        key = jax.random.fold_in(key, i)
    return _normal(key, shape=tuple(shape), std=float(std),
                   dtype=jnp.dtype(dtype))
