"""Flagship Sparse-on-Dense Pallas kernel: fused decompress + dense matmul.

This is the TPU realization of the paper's datapath (Fig. 2): compressed
weights stream HBM→VMEM (the "global buffer → decompression unit" hop), a
VPU decompression loop re-densifies each (bk, bn) tile *once per K-slab
residency*, and the MXU consumes the dense tile for every M block — the
weight-stationary reuse that amortizes decompression exactly as the paper's
dataflow amortizes its decompression-unit latency.

Memory traffic for weights is ``≈ (value_bytes + index_byte) · nnz`` instead
of ``2 · K · N`` — the paper's 1.5·density ratio (16-bit value + 8-bit index).

Grid: ``(Nt, Mt, Kt)``, K innermost.
  * decompression of tile (k, n) happens only at ``m == 0``; the dense slab
    (Kt, bk, bn) persists in VMEM scratch across the whole M sweep;
  * a float32 accumulator carries partial sums across K;
  * output (m, n) is written once at ``k == Kt-1`` (consecutive revisits).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.formats import TiledCSC

__all__ = ["sod_matmul_pallas", "resolve_interpret"]


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` means: compile for the chip when JAX's default
    backend is a TPU, run the Pallas interpreter anywhere else."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _dequant_codes(idx: jax.Array, cb_ref) -> jax.Array:
    """Codebook dequant: unrolled compare-select over the (small, static)
    shared-value table held in SMEM — same VPU idiom as the row-index
    compare-accumulate, no gather needed."""
    out = jnp.zeros(idx.shape, jnp.float32)
    for code in range(cb_ref.shape[-1]):
        out += jnp.where(idx == code, cb_ref[0, code], 0.0)
    return out


def _decompress_tile(
    vals_ref,  # (1, 1, cap, bn) block of stored values / codes
    rows_ref,  # (1, 1, cap, bn) block of row indices, -1 = padding
    bk: int,
    slot_chunk: int,
    cb_ref=None,  # SMEM (1, ncodes) for qmode='codebook'
) -> jax.Array:
    """Compare-accumulate decompression of one (bk, bn) tile (VPU loop).

    Each step reads one ``slot_chunk`` of slots straight from the refs and
    adds every slot's value into the tile row it names.  Accumulates in
    float32 — for quantized operands the values are raw codes; codebook
    indices dequantize per chunk here, while int8/fp8 codes sum raw and the
    caller applies the per-tile scale once to the finished tile
    (``Σ qᵢ·s = s·Σ qᵢ``), keeping dequant off the inner loop.
    """
    cap, bn = vals_ref.shape[-2:]
    iota = jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0)

    def body(c, acc):
        start = pl.multiple_of(c * slot_chunk, slot_chunk)
        r = rows_ref[0, 0, pl.ds(start, slot_chunk), :].astype(jnp.int32)
        v = vals_ref[0, 0, pl.ds(start, slot_chunk), :]
        if cb_ref is None:
            vf = v.astype(jnp.float32)
        else:
            vf = _dequant_codes(v.astype(jnp.int32), cb_ref)
        for j in range(slot_chunk):
            acc += jnp.where(iota == r[j:j + 1, :], vf[j:j + 1, :], 0.0)
        return acc

    n_chunks = cap // slot_chunk
    tile = jax.lax.fori_loop(
        0, n_chunks, body, jnp.zeros((bk, bn), jnp.float32)
    )
    return tile


def _sod_matmul_kernel(
    x_ref,      # (bm, bk)
    vals_ref,   # (1, 1, cap, bn)
    rows_ref,   # (1, 1, cap, bn)
    *refs,      # [q_ref: SMEM scale (Kt, Nt) | SMEM codebook (1, ncodes)],
                # o_ref, slab_ref, acc_ref
    kt_total: int,
    bk: int,
    slot_chunk: int,
    slab_len: int,
    qmode: str = "none",
):
    o_ref, slab_ref, acc_ref = refs[-3:]
    q_ref = refs[0] if qmode != "none" else None
    n = pl.program_id(0)
    m = pl.program_id(1)
    k = pl.program_id(2)
    resident = slab_len >= kt_total
    slot = k if resident else jax.lax.rem(k, slab_len)

    # Resident slab: decompress each (k, n) tile once, at m == 0, and reuse
    # it across the whole M sweep (the paper's weight-stationary reuse).
    # Non-resident slab (slab_len < Kt — the VMEM-constrained k_slab tuning
    # point): re-decompress on every visit, trading VPU work for VMEM.
    # Dequantization fuses here too — the scale rides the same residency,
    # so quantized operands cost zero extra HBM round trips.
    def _decompress():
        cb_ref = q_ref if qmode == "codebook" else None
        tile = _decompress_tile(vals_ref, rows_ref, bk, slot_chunk,
                                cb_ref=cb_ref)
        if qmode in ("int8", "fp8"):
            tile = tile * q_ref[k, n]
        slab_ref[slot] = tile.astype(slab_ref.dtype)

    if resident:
        pl.when(m == 0)(_decompress)
    else:
        _decompress()

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], slab_ref[slot], preferred_element_type=jnp.float32
    )

    @pl.when(k == kt_total - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_side_inputs(packed) -> tuple[list, list]:
    """Extra (inputs, specs) a quantized operand appends to a kernel call.

    The per-tile scale (int8/fp8) and the shared-value codebook both ride
    whole in SMEM: the kernel reads the scale of tile ``(k, n)`` as a
    scalar, and the codebook's entries as compare-select constants."""
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    if packed.qmode in ("int8", "fp8"):
        return [packed.scale.astype(jnp.float32)], [smem]
    if packed.qmode == "codebook":
        return [packed.codebook.reshape(1, -1).astype(jnp.float32)], [smem]
    return [], []


@functools.partial(
    jax.jit,
    static_argnames=("bm", "slot_chunk", "k_slab", "interpret", "out_dtype"),
)
def sod_matmul_pallas(
    x: jax.Array,
    packed: TiledCSC,
    *,
    bm: int = 128,
    slot_chunk: int = 8,
    k_slab: int = 0,
    interpret: bool | None = None,
    out_dtype=None,
):
    """``x @ decompress(packed)`` fused, for 2-D ``x`` of shape (M, Kp).

    ``x`` must already be padded to the packed operand's padded K
    (``packed.grid[0] * bk``) and to an M multiple of ``bm``; use
    :func:`repro.kernels.ops.sod_matmul` for the general wrapper.

    ``k_slab`` bounds the VMEM scratch holding the decompressed K-slab:
    0 (default) keeps all ``Kt`` tiles resident and decompresses each once;
    ``0 < k_slab < Kt`` keeps only ``k_slab`` tiles and re-decompresses per
    M-block — the autotuner's knob for weights whose full slab exceeds VMEM.
    ``interpret=None`` compiles for a TPU backend and interprets elsewhere.
    """
    out_dtype = out_dtype or x.dtype
    kt, nt = packed.grid
    bk, bn = packed.tile
    cap = packed.cap
    slab_len = kt if k_slab <= 0 else min(k_slab, kt)
    m_dim = x.shape[0]
    if x.shape[1] != kt * bk:
        raise ValueError(f"x K dim {x.shape[1]} != packed padded K {kt * bk}")
    if m_dim % bm:
        raise ValueError(f"M={m_dim} not a multiple of bm={bm}")
    if cap % slot_chunk:
        raise ValueError(f"cap={cap} not a multiple of slot_chunk={slot_chunk}")
    mt = m_dim // bm

    # Compressed-traffic cost estimate: this is what the roofline reads —
    # quantized operands stream fewer value bytes (itemsize shrinks).
    idx_bytes = packed.rows.dtype.itemsize
    val_bytes = packed.vals.dtype.itemsize
    cost = pl.CostEstimate(
        flops=2 * m_dim * kt * bk * nt * bn,
        bytes_accessed=(
            x.size * x.dtype.itemsize
            + packed.vals.size * (val_bytes + idx_bytes)
            + m_dim * nt * bn * jnp.dtype(out_dtype).itemsize
        ),
        transcendentals=0,
    )

    qmode = packed.qmode
    extra_in, extra_specs = quant_side_inputs(packed)
    kernel = functools.partial(
        _sod_matmul_kernel, kt_total=kt, bk=bk, slot_chunk=slot_chunk,
        slab_len=slab_len, qmode=qmode,
    )
    return pl.pallas_call(
        kernel,
        grid=(nt, mt, kt),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda n, m, k: (m, k)),
            pl.BlockSpec((1, 1, cap, bn), lambda n, m, k: (k, n, 0, 0)),
            pl.BlockSpec((1, 1, cap, bn), lambda n, m, k: (k, n, 0, 0)),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda n, m, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((m_dim, nt * bn), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((slab_len, bk, bn), x.dtype),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ),
        cost_estimate=cost,
        interpret=resolve_interpret(interpret),
    )(x, packed.vals, packed.rows, *extra_in)
