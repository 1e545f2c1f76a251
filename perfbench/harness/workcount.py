"""Operations and bytes of the work a run asked for, from its own shapes.

Part of the yardstick.  A matmul ``x (M, K) @ W (K, N)`` in which each of
the step's M rows reaches the matrix with probability ``share`` (1, unless
a router picks it) multiplies ``m = M * share`` rows: it needs ``2 * m *
nnz`` operations, where ``nnz`` counts the non-zeros the operand stores
(every value of a dense one), and must move at least ``nnz * value_bytes``
of weights plus its activations, ``m * K`` in and ``m * N`` out at two
bytes each.  Index and padding bytes are left out, so the least time of a
call is a lower bound for any implementation of it, and a kernel's share of
that bound cannot exceed 100%.  A token's attention reads each position it
attends to in every layer, at the operations per position the
architecture's adapter gives.
"""
from __future__ import annotations

import dataclasses

ACT_BYTES = 2      # bf16 activations in and out


@dataclasses.dataclass(frozen=True)
class Matrix:
    """One weight matrix as a step multiplies it: logical (K, N), the
    non-zeros it stores, the bytes of one stored value, what computes it
    (``pallas_fused``, ``pallas_block`` or ``xla_dot``), and the share of
    a step's rows it multiplies."""

    role: str
    k: int
    n: int
    nnz: int
    value_bytes: int
    kernel: str
    share: float = 1.0


@dataclasses.dataclass(frozen=True)
class Attention:
    """One layer's attention: operations per position a token attends
    to, and at most how many positions it attends to (None: every one
    before it)."""

    ops: float
    window: int | None = None


def call_flops(m: int, mat: Matrix) -> float:
    return 2.0 * m * mat.share * mat.nnz


def call_bytes(m: int, mat: Matrix) -> float:
    return float(mat.nnz * mat.value_bytes
                 + ACT_BYTES * m * mat.share * (mat.k + mat.n))


def least_time_s(m: int, mat: Matrix, peaks: dict) -> float:
    """The least time the chip could take for one call at ``m`` rows of
    the step."""
    return max(call_flops(m, mat) / peaks["bf16_flops"],
               call_bytes(m, mat) / peaks["hbm_bytes_per_s"])


def program_least_time_s(m: int, mats: list[Matrix], peaks: dict) -> float:
    """Least time of every matmul in ``mats`` that one forward program
    calls at ``m`` rows (each matrix once)."""
    return sum(least_time_s(m, mat, peaks) for mat in mats)


def weight_ops(mats: list[Matrix]) -> float:
    """Operations of one token through the weights it uses: two per
    stored non-zero, each matrix in the share of tokens that reach it."""
    return 2.0 * sum(m.nnz * m.share for m in mats)


def attention_ops(layers: list[Attention], ctx: int) -> float:
    """Attention operations of one token at context length ``ctx`` (the
    positions before it and its own)."""
    return sum(a.ops * (ctx if a.window is None else min(ctx, a.window))
               for a in layers)


def prompt_attention_ops(layers: list[Attention], plen: int) -> float:
    """Attention operations of a prompt's ``plen`` tokens, the i-th
    attending to i positions (at most its layer's window)."""
    total = 0.0
    for a in layers:
        w = plen if a.window is None else min(plen, a.window)
        total += a.ops * (w * (w + 1) / 2 + (plen - w) * w)
    return total

