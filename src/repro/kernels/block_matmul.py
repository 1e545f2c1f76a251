"""VREG-block Sparse-on-Dense matmul with zero-macro-tile skipping.

The TPU-native adaptation of the paper's insight (DESIGN.md §2): the natural
decompression granule on a TPU is the (8, 128) vector register, not a single
element.  Decompression of a (bk, bn) macro tile is then a short loop of
whole-register adds into a VMEM tile at each block's row offset — near line
rate on the VPU — and macro
tiles whose ``tile_nnz == 0`` skip their MXU dot entirely (a *compute* win
the paper's always-dense array cannot realize; the paper's structured-sparsity
"bypass" mode, Section V-A, taken one step further).

``tile_nnz`` and ``block_ids`` ride in SMEM via scalar prefetch so they can
steer control flow before the tile data arrives.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import BlockCSR
from repro.kernels.sod_matmul import (
    _dequant_codes,
    quant_side_inputs,
    resolve_interpret,
)

__all__ = ["block_matmul_pallas"]


def _block_matmul_kernel(
    nnz_ref,     # SMEM (Kt, Nt) int32
    ids_ref,     # SMEM (Kt, Nt, bcap) int32, -1 = padding
    x_ref,       # (bm, bk)
    bvals_ref,   # (1, 1, bcap, br, bn)
    *refs,       # [q_ref: SMEM scale (Kt, Nt) | SMEM codebook (1, ncodes)],
                 # o_ref, slab_ref, acc_ref, tile_ref
    kt_total: int,
    bk: int,
    br: int,
    bcap: int,
    qmode: str = "none",
):
    o_ref, slab_ref, acc_ref, tile_ref = refs[-4:]
    q_ref = refs[0] if qmode != "none" else None
    n = pl.program_id(0)
    m = pl.program_id(1)
    k = pl.program_id(2)
    nnz = nnz_ref[k, n]

    @pl.when(jnp.logical_and(m == 0, nnz > 0))
    def _decompress():
        # Blocks accumulate in an f32 VMEM tile (quantized codes dequantize
        # per block; the shared per-tile scale multiplies the finished tile
        # once).
        tile_ref[...] = jnp.zeros_like(tile_ref)

        def body(s, carry):
            bid = ids_ref[k, n, s]
            # Padding (bid == -1) contributes zeros added at offset 0 — a
            # no-op because real block ids are unique and values are 0
            # (codebook entry 0 is pinned to 0.0 for the same reason).
            off = pl.multiple_of(jnp.maximum(bid, 0) * br, br)
            blk = bvals_ref[0, 0, s]
            if qmode == "codebook":
                blk = _dequant_codes(blk.astype(jnp.int32), q_ref)
            else:
                blk = blk.astype(jnp.float32)
            tile_ref[pl.ds(off, br), :] += blk
            return carry

        jax.lax.fori_loop(0, bcap, body, 0)
        tile = tile_ref[...]
        if qmode in ("int8", "fp8"):
            tile = tile * q_ref[k, n]
        slab_ref[k] = tile.astype(slab_ref.dtype)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(nnz > 0)
    def _dot():
        acc_ref[...] += jnp.dot(
            x_ref[...], slab_ref[k], preferred_element_type=jnp.float32
        )

    @pl.when(k == kt_total - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "interpret", "out_dtype")
)
def block_matmul_pallas(
    x: jax.Array,
    packed: BlockCSR,
    *,
    bm: int = 128,
    interpret: bool | None = None,
    out_dtype=None,
):
    """``x @ decompress(packed)`` with zero-macro-tile skip, 2-D ``x``.

    ``interpret=None`` compiles for a TPU backend and interprets elsewhere.
    """
    out_dtype = out_dtype or x.dtype
    kt, nt = packed.grid
    bk, bn = packed.tile
    br = packed.br
    bcap = packed.bcap
    m_dim = x.shape[0]
    if x.shape[1] != kt * bk:
        raise ValueError(f"x K dim {x.shape[1]} != packed padded K {kt * bk}")
    if m_dim % bm:
        raise ValueError(f"M={m_dim} not a multiple of bm={bm}")
    mt = m_dim // bm

    # Effective FLOPs scale with the non-zero macro-tile fraction.
    nz_tiles = int(jnp.count_nonzero(packed.tile_nnz)) if not isinstance(
        packed.tile_nnz, jax.core.Tracer
    ) else kt * nt
    cost = pl.CostEstimate(
        flops=2 * m_dim * bk * bn * max(nz_tiles, 1),
        bytes_accessed=(
            x.size * x.dtype.itemsize
            + packed.block_vals.size * packed.block_vals.dtype.itemsize
            + packed.block_ids.size * 2
            + m_dim * nt * bn * jnp.dtype(out_dtype).itemsize
        ),
        transcendentals=0,
    )

    qmode = packed.qmode
    extra_in, extra_specs = quant_side_inputs(packed)

    kernel = functools.partial(
        _block_matmul_kernel, kt_total=kt, bk=bk, br=br, bcap=bcap,
        qmode=qmode,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nt, mt, kt),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda n, m, k, *_: (m, k)),
            pl.BlockSpec(
                (1, 1, bcap, br, bn), lambda n, m, k, *_: (k, n, 0, 0, 0)
            ),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda n, m, k, *_: (m, n)),
        scratch_shapes=[
            pltpu.VMEM((kt, bk, bn), x.dtype),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bk, bn), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_dim, nt * bn), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        cost_estimate=cost,
        interpret=resolve_interpret(interpret),
    )(packed.tile_nnz, packed.block_ids, x, packed.block_vals, *extra_in)
