"""Standalone decompression-unit kernel (paper Fig. 4, Steps 1-5).

Turns a :class:`TiledCSC` operand into its dense matrix, one (bk, bn) tile
per grid step.  This is the paper's decompression unit in isolation — used by
tests, by the micro-benchmarks that measure decompression cost, and by the
SoD-FSDP path when a weight must be re-densified once per step outside a
matmul (e.g. before an einsum XLA fuses itself).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import TiledCSC
from repro.kernels.sod_matmul import (
    _decompress_tile,
    quant_side_inputs,
    resolve_interpret,
)

__all__ = ["decompress_pallas"]


def _decompress_kernel(vals_ref, rows_ref, *refs, bk, slot_chunk, qmode):
    """One (bk, bn) tile per grid step; dequant fused as in the matmul."""
    o_ref = refs[-1]
    q_ref = refs[0] if qmode != "none" else None
    tile = _decompress_tile(vals_ref, rows_ref, bk, slot_chunk,
                            cb_ref=q_ref if qmode == "codebook" else None)
    if qmode in ("int8", "fp8"):
        tile = tile * q_ref[pl.program_id(0), pl.program_id(1)]
    o_ref[...] = tile.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("slot_chunk", "interpret", "out_dtype"))
def decompress_pallas(
    packed: TiledCSC,
    *,
    slot_chunk: int = 8,
    interpret: bool | None = None,
    out_dtype=None,
):
    """Dense (Kp, Np) matrix from a TiledCSC operand (padded shape).

    Quantized operands dequantize in-kernel; their default output dtype is
    float32 (the stored value dtype is the code, not a value).
    ``interpret=None`` compiles for a TPU backend and interprets elsewhere.
    """
    qmode = packed.qmode
    out_dtype = out_dtype or (
        jnp.float32 if qmode != "none" else packed.vals.dtype)
    kt, nt = packed.grid
    bk, bn = packed.tile
    cap = packed.cap
    if cap % slot_chunk:
        raise ValueError(f"cap={cap} not a multiple of slot_chunk={slot_chunk}")

    idx_bytes = packed.rows.dtype.itemsize
    cost = pl.CostEstimate(
        flops=0,
        bytes_accessed=(
            packed.vals.size * (packed.vals.dtype.itemsize + idx_bytes)
            + kt * bk * nt * bn * jnp.dtype(out_dtype).itemsize
        ),
        transcendentals=0,
    )
    extra_in, extra_specs = quant_side_inputs(packed)
    kernel = functools.partial(_decompress_kernel, bk=bk,
                               slot_chunk=slot_chunk, qmode=qmode)
    out = pl.pallas_call(
        kernel,
        grid=(kt, nt),
        in_specs=[
            pl.BlockSpec((1, 1, cap, bn), lambda k, n: (k, n, 0, 0)),
            pl.BlockSpec((1, 1, cap, bn), lambda k, n: (k, n, 0, 0)),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda k, n: (k, n)),
        out_shape=jax.ShapeDtypeStruct((kt * bk, nt * bn), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        cost_estimate=cost,
        interpret=resolve_interpret(interpret),
    )(packed.vals, packed.rows, *extra_in)
    return out
