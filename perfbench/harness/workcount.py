"""Operations and bytes of the work a run asked for, from its own shapes.

Part of the yardstick.  A packed matmul ``x (M, K) @ W (K, N)`` needs
``2 * M * nnz`` operations, where ``nnz`` counts the non-zeros the packed
operand stores, and must move at least ``nnz * value_bytes`` of weights
plus its activations, ``M * K`` in and ``M * N`` out at two bytes each.
Index and padding bytes are left out, so the least time of a call is a
lower bound for any implementation of it, and a kernel's share of that
bound cannot exceed 100%.
"""
from __future__ import annotations

import dataclasses

ACT_BYTES = 2      # bf16 activations in and out


@dataclasses.dataclass(frozen=True)
class Matrix:
    """One packed weight matrix as the kernel sees it: logical (K, N), the
    non-zeros it stores, and the bytes of one stored value."""

    role: str
    k: int
    n: int
    nnz: int
    value_bytes: int


def call_flops(m: int, mat: Matrix) -> float:
    return 2.0 * m * mat.nnz


def call_bytes(m: int, mat: Matrix) -> float:
    return float(mat.nnz * mat.value_bytes + ACT_BYTES * m * (mat.k + mat.n))


def least_time_s(m: int, mat: Matrix, peaks: dict) -> float:
    """The least time the chip could take for one call at ``m`` rows."""
    return max(call_flops(m, mat) / peaks["bf16_flops"],
               call_bytes(m, mat) / peaks["hbm_bytes_per_s"])


def program_least_time_s(m: int, mats: list[Matrix], peaks: dict) -> float:
    """Least time of every packed matmul one forward program calls at
    ``m`` rows (each matrix once: per layer, and the head)."""
    return sum(least_time_s(m, mat, peaks) for mat in mats)


def token_ops(mats: list[Matrix], ctx: int, cfg: dict) -> float:
    """Model operations for one token at context length ``ctx`` (the
    positions it attends to): two per stored non-zero weight, plus the
    attention products ``4 * ctx * heads * head_dim`` in every layer."""
    weights = 2.0 * sum(m.nnz for m in mats)
    attn = (4.0 * ctx * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"])
    return weights + attn
