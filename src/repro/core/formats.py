"""Sparse data formats for Sparse-on-Dense.

The paper stores non-zero data in the global buffer in CSC form (16-bit
values, 8-bit row indices, column pointers) and re-densifies tiles on the fly
in a decompression unit placed between the buffer and the dense PE array.

XLA/Pallas need static shapes, so the executable TPU formats are *padded*
variants with a static per-column (or per-tile) capacity:

  * :class:`TiledCSC`  — element-granular, paper-faithful.  The matrix is cut
    into (bk, bn) tiles; each tile column stores up to ``cap`` non-zeros as
    (value, in-tile row index).  Lossless when ``cap`` >= the max column
    non-zero count over all tiles (the default).
  * :class:`BlockCSR`  — TPU-native adaptation.  (br, bc) = (8, 128)
    VREG-shaped sub-blocks; decompression is whole-register gather and
    all-zero MXU macro-tiles can be skipped.
  * :class:`Bitmap`    — SIGMA-style bitmap + packed values (used for
    footprint comparisons and as a third executable format).
  * :func:`pack_csc` / :func:`unpack_csc` — classic pointer CSC (numpy),
    used by the cost model for exact footprint accounting.

All executable formats are registered as JAX pytrees, are differentiable
through ``to_dense`` (scatter-add ⇒ gather gradient onto the fixed mask —
this is what makes fixed-mask sparse *training* work for free), and carry
byte-accounting helpers that honour the paper's 16-bit value / 8-bit index
assumption as well as the TPU bf16/int8 layout.

Quantized value storage (``qmode``)
-----------------------------------

On top of sparsity, the packed value buffers can be stored quantized
(EIE-style weight sharing taken to the SoD formats).  Both executable
formats carry a ``qmode`` axis:

  * ``"none"``     — values stay in the pack dtype (fp32/bf16); default.
  * ``"int8"``     — symmetric int8 with one fp scale per (bk, bn) tile.
  * ``"fp8"``      — float8_e4m3 values with one fp scale per tile.
  * ``"codebook"`` — EIE-style weight sharing: a per-matrix table of
    ``CODEBOOK_SIZE`` shared fp values (entry 0 reserved for 0.0) and a
    narrow per-slot index into it.

Quantization happens at pack time (:func:`quantize_packed`, called by the
packers); dequantization is fused into the Pallas decompress loops and
into ``to_dense`` so every consumer — oracle, VJP, SPMD gather — sees the
dequantized weight.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "TiledCSC",
    "BlockCSR",
    "Bitmap",
    "pack_tiled_csc",
    "pack_block_csr",
    "pack_bitmap",
    "pack_csc",
    "unpack_csc",
    "density",
    "padded_shape",
    "observed_tiled_cap",
    "observed_block_cap",
    "quantize_packed",
    "qvalue_bits",
    "fp8_dtype",
    "QMODES",
    "CODEBOOK_SIZE",
]

# -- quantized value storage -------------------------------------------------
# The accounting constants are shared with the (jax-free) plan layer so the
# planner's compressed_bytes can never drift from the packed containers'.
from repro.core.plan import (  # noqa: E402
    CODEBOOK_SIZE, QMODES, QVALUE_BITS, SCALE_BITS)


def fp8_dtype():
    """The fp8 value dtype (``float8_e4m3fn``), or ``None`` when this jax
    build has no fp8 support — callers gate the ``"fp8"`` qmode on it."""
    return getattr(jnp, "float8_e4m3fn", None)


def qvalue_bits(qmode: str, ncodes: int = CODEBOOK_SIZE) -> int:
    """Paper-accounting bits per stored value slot under ``qmode``.

    ``"none"`` keeps the paper's 16-bit value assumption; int8/fp8 store one
    byte; codebook stores only the index into the shared table
    (``ceil(log2(ncodes))``, 4 bits at the default table size).
    """
    if qmode == "codebook":
        return max(int(np.ceil(np.log2(max(ncodes, 2)))), 1)
    if qmode in (None, "none"):
        qmode = "none"
    if qmode not in QVALUE_BITS:
        raise ValueError(
            f"unknown qmode {qmode!r} (expected one of {QMODES})")
    return QVALUE_BITS[qmode]


def _check_qmode(qmode: str) -> str:
    qmode = qmode or "none"
    if qmode not in QMODES:
        raise ValueError(f"unknown qmode {qmode!r} (expected one of {QMODES})")
    if qmode == "fp8" and fp8_dtype() is None:
        raise ValueError("qmode='fp8' needs jnp.float8_e4m3fn, which this "
                         "jax build does not provide")
    return qmode


def density(x) -> float:
    """Fraction of non-zero elements."""
    x = np.asarray(x)
    if x.size == 0:
        return 0.0
    return float(np.count_nonzero(x)) / float(x.size)


def padded_shape(shape: tuple[int, int], tile: tuple[int, int]) -> tuple[int, int]:
    """Round ``shape`` up to whole multiples of ``tile``."""
    bk, bn = tile
    k, n = shape
    return ((k + bk - 1) // bk * bk, (n + bn - 1) // bn * bn)


def _pad_to_tiles(w: jax.Array, tile: tuple[int, int]) -> jax.Array:
    k, n = w.shape
    kp, np_ = padded_shape((k, n), tile)
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    return w


def observed_tiled_cap(w, tile: tuple[int, int]) -> int:
    """Max per-tile-column non-zero count over a (possibly stacked) matrix —
    the data-dependent capacity :func:`pack_tiled_csc` uses (unaligned).

    The single source of truth for this number: the packer's stacked branch
    and the planner's observed-cap pass both call it, so planned capacities
    can never drift from what a lossless global pack would choose.
    """
    w = jnp.asarray(w)
    if not w.size:
        return 0
    return int(_max_column_nnz(w.reshape((-1,) + w.shape[-2:]),
                               tile=tuple(tile)))


@functools.partial(jax.jit, static_argnames="tile")
def _max_column_nnz(flat, *, tile):
    bk, bn = tile
    wp = jax.vmap(lambda m: _pad_to_tiles(m, tile))(flat)
    kp, np_ = wp.shape[-2:]
    t = wp.reshape(flat.shape[0], kp // bk, bk, np_ // bn, bn)
    return jnp.max(jnp.sum(t != 0, axis=2))


def observed_block_cap(w, tile: tuple[int, int], br: int) -> int:
    """Max non-zero (br, bn) sub-block count per macro tile over a (possibly
    stacked) matrix — the data-dependent bcap :func:`pack_block_csr` uses."""
    w = jnp.asarray(w)
    if not w.size:
        return 0
    bk, bn = tile
    flat = w.reshape((-1,) + w.shape[-2:])
    wp = jax.vmap(lambda m: _pad_to_tiles(m, tile))(flat)
    kp, np_ = wp.shape[-2:]
    blk = wp.reshape(flat.shape[0], kp // bk, bk // br, br, np_ // bn, bn)
    nz = jnp.any(blk != 0, axis=(3, 5))
    return int(jnp.max(jnp.sum(nz, axis=2)))


# ---------------------------------------------------------------------------
# Quantization helpers shared by both executable formats
# ---------------------------------------------------------------------------
def _fit_codebook(x: np.ndarray, ncodes: int) -> np.ndarray:
    """EIE-style shared-value table via 1-D Lloyd k-means (deterministic).

    Entry 0 is reserved for exactly 0.0 so padding slots (and pruned
    positions inside stored blocks) round-trip to zero; the remaining
    ``ncodes - 1`` centroids are quantile-initialised over the non-zero
    values and refined for a few Lloyd iterations.
    """
    book = np.zeros((ncodes,), np.float32)
    nz = np.asarray(x, np.float32).ravel()
    nz = nz[nz != 0]
    if nz.size == 0:
        return book
    k = ncodes - 1
    cent = np.quantile(nz, np.linspace(0.0, 1.0, k))
    # collapsed quantiles (few distinct values) would alias centroids;
    # nudge them apart so argmin assignment stays well defined
    cent = cent + np.arange(k) * 1e-12
    for _ in range(8):
        assign = np.argmin(np.abs(nz[:, None] - cent[None, :]), axis=1)
        for i in range(k):
            sel = assign == i
            if sel.any():
                cent[i] = nz[sel].mean()
    book[1:] = np.sort(cent)
    return book


def _dequant_values(vals, scale, codebook, qmode: str, nval_dims: int):
    """Dequantize a packed value buffer back to float32.

    ``vals`` is ``(*lead, Kt, Nt, *value_dims)`` with ``nval_dims`` trailing
    value dims (2 for TiledCSC's ``(cap, bn)``, 3 for BlockCSR's
    ``(bcap, br, bn)``); ``scale`` is ``(*lead, Kt, Nt)``; ``codebook`` is
    ``(*lead, ncodes)``.  Differentiable in ``scale`` / ``codebook``, which
    is what routes training gradients into the quantization parameters.
    """
    if qmode in (None, "none"):
        return vals
    if qmode in ("int8", "fp8"):
        s = scale.reshape(scale.shape + (1,) * nval_dims)
        return vals.astype(jnp.float32) * s
    if qmode == "codebook":
        lead_ndim = vals.ndim - 2 - nval_dims
        idx = vals.astype(jnp.int32).reshape(vals.shape[:lead_ndim] + (-1,))
        out = jnp.take_along_axis(codebook.astype(jnp.float32), idx, axis=-1)
        return out.reshape(vals.shape)
    raise ValueError(f"unknown qmode {qmode!r}")


def _quantize_values(vals, qmode: str, nval_dims: int, ncodes: int):
    """Quantize a packed fp value buffer; returns ``(qvals, scale, codebook)``.

    Shapes as in :func:`_dequant_values`.  Padding slots hold value 0 and
    map to quantized 0 (int8/fp8) or codebook entry 0 in every mode, so the
    sentinel-row/-id masking downstream keeps working unchanged.
    """
    qmode = _check_qmode(qmode)
    if qmode == "none":
        return vals, None, None
    tile_axes = tuple(range(vals.ndim - nval_dims, vals.ndim))
    absmax = jnp.max(jnp.abs(vals.astype(jnp.float32)), axis=tile_axes)
    if qmode == "int8":
        scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
        s = scale.reshape(scale.shape + (1,) * nval_dims)
        q = jnp.clip(jnp.round(vals.astype(jnp.float32) / s), -127, 127)
        return q.astype(jnp.int8), scale.astype(jnp.float32), None
    if qmode == "fp8":
        scale = jnp.where(absmax > 0, absmax / 448.0, 1.0)
        s = scale.reshape(scale.shape + (1,) * nval_dims)
        q = (vals.astype(jnp.float32) / s).astype(fp8_dtype())
        return q, scale.astype(jnp.float32), None
    # codebook: fit one shared-value table per lead slice (host-side numpy —
    # packing is an eager, concrete-weights operation)
    if isinstance(vals, jax.core.Tracer):
        raise ValueError("qmode='codebook' needs concrete weights at pack "
                         "time (the shared-value table is fit with numpy)")
    lead = vals.shape[:vals.ndim - 2 - nval_dims]
    v_np = np.asarray(vals, np.float32).reshape((-1,) + vals.shape[len(lead):])
    books = np.stack([_fit_codebook(v_np[i], ncodes)
                      for i in range(v_np.shape[0])])
    idx = np.empty(v_np.shape, np.int8)
    for i in range(v_np.shape[0]):
        idx[i] = np.argmin(
            np.abs(v_np[i][..., None] - books[i]), axis=-1).astype(np.int8)
    codebook = jnp.asarray(books.reshape(lead + (ncodes,)), jnp.float32)
    return jnp.asarray(idx.reshape(vals.shape)), None, codebook


def quantize_packed(packed, qmode: str, ncodes: int = CODEBOOK_SIZE):
    """Quantize the value buffer of a packed operand (TiledCSC/BlockCSR).

    Returns a new container with ``qmode`` set and ``vals``/``block_vals``
    replaced by the quantized representation plus the ``scale`` /
    ``codebook`` side bands.  ``qmode='none'`` (or quantizing an already
    quantized operand with the same mode) is the identity.
    """
    qmode = _check_qmode(qmode)
    if qmode == getattr(packed, "qmode", "none"):
        return packed
    if getattr(packed, "qmode", "none") != "none":
        raise ValueError(f"operand is already quantized ({packed.qmode}); "
                         "re-pack from dense to change qmode")
    if isinstance(packed, TiledCSC):
        q, scale, codebook = _quantize_values(packed.vals, qmode, 2, ncodes)
        return dataclasses.replace(packed, vals=q, scale=scale,
                                   codebook=codebook, qmode=qmode)
    if isinstance(packed, BlockCSR):
        q, scale, codebook = _quantize_values(
            packed.block_vals, qmode, 3, ncodes)
        return dataclasses.replace(packed, block_vals=q, scale=scale,
                                   codebook=codebook, qmode=qmode)
    raise TypeError(f"cannot quantize {type(packed).__name__}")


# ---------------------------------------------------------------------------
# TiledCSC — element-granular, paper-faithful static-shape CSC
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TiledCSC:
    """Per-(bk, bn)-tile padded CSC.

    ``vals[kt, nt, s, j]`` is the s-th non-zero of column ``j`` of tile
    ``(kt, nt)``; ``rows[kt, nt, s, j]`` its in-tile row index.  Padding slots
    carry ``val == 0`` and sentinel ``row == -1``: compare-accumulate never
    matches them and scatter-add drops them (``mode='drop'``), which also
    guarantees *exactly zero* gradient flow into padding slots — fixed-mask
    sparse training stays on the mask.

    Under a quantized ``qmode``, ``vals`` holds the quantized representation
    (int8 / fp8 codes, or int8 codebook indices) and ``scale`` / ``codebook``
    carry the dequantization side band; padding slots quantize to 0 (or
    codebook entry 0 == 0.0), so the sentinel logic is qmode-oblivious.
    """

    vals: jax.Array   # (*lead, Kt, Nt, cap, bn) — lead = layer-stack/expert dims
    rows: jax.Array   # same shape, int8 (bk <= 128) or int32
    shape: tuple[int, int]          # logical (K, N) before tile padding
    tile: tuple[int, int]
    scale: Any = None      # (*lead, Kt, Nt) f32, int8/fp8 modes only
    codebook: Any = None   # (*lead, ncodes) f32, codebook mode only
    qmode: str = "none"

    # -- pytree plumbing ----------------------------------------------------
    def tree_flatten(self):
        """Flatten into (array children, static aux) for jax pytrees."""
        return (self.vals, self.rows, self.scale, self.codebook), (
            self.shape, self.tile, self.qmode)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild from :meth:`tree_flatten` output."""
        vals, rows, scale, codebook = children
        shape, tile, qmode = aux
        return cls(vals=vals, rows=rows, shape=shape, tile=tile,
                   scale=scale, codebook=codebook, qmode=qmode)

    # -- views --------------------------------------------------------------
    @property
    def cap(self) -> int:
        """Padded slot count per tile column (trailing value dim)."""
        return self.vals.shape[-2]

    @property
    def grid(self) -> tuple[int, int]:
        """``(Kt, Nt)`` tile-grid extents."""
        return self.vals.shape[-4], self.vals.shape[-3]

    @property
    def lead(self) -> tuple[int, ...]:
        """Leading stack dims (layer groups / experts), ahead of the grid."""
        return tuple(self.vals.shape[:-4])

    @property
    def dtype(self):
        """Stored value dtype: fp, int8 codes, or fp8."""
        return self.vals.dtype

    def nbytes_compressed(self, value_bits: int | None = None,
                          index_bits: int = 8) -> int:
        """Footprint under the paper's encoding (value + index per slot).

        ``value_bits=None`` uses the ``qmode``'s width (16 unquantized, 8
        for int8/fp8, index width for codebook) plus the side-band cost:
        one 16-bit scale per tile, or 16 bits per codebook entry.
        """
        side = 0
        if value_bits is None:
            ncodes = (self.codebook.shape[-1] if self.codebook is not None
                      else CODEBOOK_SIZE)
            value_bits = qvalue_bits(self.qmode, ncodes)
            if self.scale is not None:
                side += int(np.prod(self.scale.shape)) * SCALE_BITS // 8
            if self.codebook is not None:
                side += int(np.prod(self.codebook.shape)) * SCALE_BITS // 8
        slots = int(np.prod(self.vals.shape))
        return slots * (value_bits + index_bits) // 8 + side

    def nbytes_dense(self, value_bits: int = 16) -> int:
        """Dense-equivalent bytes at ``value_bits`` (lead dims included)."""
        # nbytes_compressed counts the stacked (layer-group / expert) lead
        # dims via vals.shape; the dense equivalent must too, or stacked
        # leaves report a compression ratio off by prod(lead)
        kp, np_ = padded_shape(self.shape, self.tile)
        return int(np.prod(self.lead, dtype=np.int64)) * kp * np_ \
            * value_bits // 8

    def compression_ratio(self) -> float:
        """``nbytes_compressed / nbytes_dense`` — below 1 when packing pays."""
        return self.nbytes_compressed() / max(self.nbytes_dense(), 1)

    def dequantize(self) -> "TiledCSC":
        """The equivalent unquantized (``qmode='none'``) operand, values
        dequantized to float32.  Identity when already unquantized."""
        if self.qmode == "none":
            return self
        vals = _dequant_values(self.vals, self.scale, self.codebook,
                               self.qmode, 2)
        return TiledCSC(vals=vals, rows=self.rows, shape=self.shape,
                        tile=self.tile)

    def to_dense(self) -> jax.Array:
        """Differentiable scatter-add decompression (the jnp 'oracle').

        Leading (layer-stack / expert) dims are vmapped; returns
        ``(*lead, K, N)``.  Quantized operands dequantize first (float32
        output), which keeps gradients flowing into ``scale``/``codebook``.
        """
        if self.qmode != "none":
            return self.dequantize().to_dense()
        if self.lead:
            flat = TiledCSC(
                vals=self.vals.reshape((-1,) + self.vals.shape[-4:]),
                rows=self.rows.reshape((-1,) + self.rows.shape[-4:]),
                shape=self.shape, tile=self.tile)
            dense = jax.vmap(
                lambda v, r: TiledCSC(v, r, self.shape, self.tile).to_dense()
            )(flat.vals, flat.rows)
            return dense.reshape(self.lead + dense.shape[-2:])
        kt_n, nt_n = self.grid
        bk, bn = self.tile
        kt = jnp.arange(kt_n)[:, None, None, None]
        nt = jnp.arange(nt_n)[None, :, None, None]
        jn = jnp.arange(bn)[None, None, None, :]
        rows = self.rows.astype(jnp.int32)
        # Mask padding explicitly: keeps decompression exact even if padding
        # values are polluted and gives exactly-zero cotangents at padding.
        vals = jnp.where(rows >= 0, self.vals, 0)
        dense = jnp.zeros((kt_n, nt_n, bk, bn), self.vals.dtype)
        dense = dense.at[
            jnp.broadcast_to(kt, rows.shape),
            jnp.broadcast_to(nt, rows.shape),
            rows,
            jnp.broadcast_to(jn, rows.shape),
        ].add(vals, mode="drop")
        dense = dense.transpose(0, 2, 1, 3).reshape(kt_n * bk, nt_n * bn)
        return dense[: self.shape[0], : self.shape[1]]


def pack_tiled_csc(
    w: jax.Array,
    tile: tuple[int, int] = (128, 128),
    cap: int | None = None,
    index_dtype=None,
    qmode: str = "none",
    ncodes: int = CODEBOOK_SIZE,
) -> TiledCSC:
    """Pack a dense matrix into :class:`TiledCSC`.

    ``cap=None`` chooses the exact max column non-zero count over all tiles
    (lossless).  A smaller ``cap`` keeps the ``cap`` largest-magnitude entries
    per tile column (lossy, ESE-style load-capping).  ``qmode`` quantizes the
    value buffer after packing (:func:`quantize_packed`).

    Leading dims (layer stacks / experts) are packed with a *shared* cap so
    the result slices homogeneously under ``lax.scan``.  One compiled
    program packs every matrix of the stack in turn.
    """
    w = jnp.asarray(w)
    if w.ndim < 2:
        raise ValueError(f"expected >=2-D matrix, got {w.shape}")
    bk, bn = tile
    lossless = cap is None
    if cap is None:
        cap = max((observed_tiled_cap(w, tile) + 7) // 8 * 8, 8)
    if index_dtype is None:
        index_dtype = jnp.int8 if bk <= 128 else jnp.int32
    lead, shape = w.shape[:-2], tuple(w.shape[-2:])
    vals, rows = _pack_tiles(w.reshape((-1,) + shape), tile=(bk, bn),
                             cap=int(cap), lossless=lossless,
                             index_dtype=jnp.dtype(index_dtype))
    return quantize_packed(
        TiledCSC(vals=vals.reshape(lead + vals.shape[1:]),
                 rows=rows.reshape(lead + rows.shape[1:]), shape=shape,
                 tile=(bk, bn)),
        qmode, ncodes)


@functools.partial(jax.jit,
                   static_argnames=("tile", "cap", "lossless", "index_dtype"))
def _pack_tiles(flat, *, tile, cap, lossless, index_dtype):
    """``(vals, rows)`` of a stack of matrices ``(B, K, N)``, each
    ``(Kt, Nt, cap, bn)``: per tile column, the rows it keeps, a slot that
    holds no non-zero given row -1 and value 0.

    With ``cap >= bk`` a column keeps every row, the non-zeros first, each
    group in row order.  With a ``lossless`` cap it keeps every non-zero
    and the first ``cap - count`` zero rows, in row order.  With any
    other cap it keeps the ``cap`` largest magnitudes, ties to the lower
    row, in row order.  A stable sort of the column's rows, carrying
    their values, picks them: no gather."""
    bk, bn = tile

    def one(m):
        m = _pad_to_tiles(m, tile)
        kp, np_ = m.shape
        # (Kt, Nt, bn, bk): each tile column's rows on the minor axis
        cols = m.reshape(kp // bk, bk, np_ // bn, bn).transpose(0, 2, 3, 1)
        nz = cols != 0
        row = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 3)
        if cap >= bk:
            key = (~nz).astype(jnp.int32)
        elif lossless:
            zeros_before = jnp.cumsum(~nz, axis=3, dtype=jnp.int32) - ~nz
            free = cap - jnp.sum(nz, axis=3, keepdims=True, dtype=jnp.int32)
            key = (~(nz | (zeros_before < free))).astype(jnp.int32)
        else:
            key = jnp.where(nz, -jnp.abs(cols.astype(jnp.float32)), jnp.inf)
        _, rows, vals = jax.lax.sort((key, row, cols), dimension=3,
                                     is_stable=True, num_keys=1)
        rows, vals = rows[..., :cap], vals[..., :cap]
        if cap < bk and not lossless:
            rows, vals = jax.lax.sort((rows, vals), dimension=3, num_keys=1)
        valid = vals != 0
        vals = jnp.where(valid, vals, 0).astype(m.dtype)
        rows = jnp.where(valid, rows, -1).astype(index_dtype)
        if cap > bk:  # degenerate: more slots than rows
            pad = ((0, 0), (0, 0), (0, 0), (0, cap - bk))
            vals = jnp.pad(vals, pad)
            rows = jnp.pad(rows, pad, constant_values=-1)
        return vals.transpose(0, 1, 3, 2), rows.transpose(0, 1, 3, 2)

    return jax.lax.map(one, flat)


# ---------------------------------------------------------------------------
# BlockCSR — (8, 128) VREG blocks, macro-tile skip list
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BlockCSR:
    """Block-compressed rows of MXU macro-tiles.

    The matrix is cut into (bk, bn) macro tiles; each macro tile is further
    cut along K into (br, bn) VREG-shaped sub-blocks (br = 8 by default).
    Per macro tile we store up to ``bcap`` non-zero sub-blocks and their
    in-tile block indices (padding id = -1, dropped on scatter).  ``tile_nnz``
    counts non-zero sub-blocks per macro tile; a macro tile with 0 can be
    skipped entirely by the matmul kernel (compute win).

    ``qmode``/``scale``/``codebook`` quantize the ``block_vals`` buffer the
    same way :class:`TiledCSC` quantizes ``vals`` (scale per macro tile).
    """

    block_vals: jax.Array  # (Kt, Nt, bcap, br, bn)
    block_ids: jax.Array   # (Kt, Nt, bcap) int32, in-tile sub-block index
    tile_nnz: jax.Array    # (Kt, Nt) int32
    shape: tuple[int, int]
    tile: tuple[int, int]  # (bk, bn) macro tile
    br: int                # sub-block rows
    scale: Any = None      # (*lead, Kt, Nt) f32, int8/fp8 modes only
    codebook: Any = None   # (*lead, ncodes) f32, codebook mode only
    qmode: str = "none"

    def tree_flatten(self):
        """Flatten into (array children, static aux) for jax pytrees."""
        return (self.block_vals, self.block_ids, self.tile_nnz,
                self.scale, self.codebook), (
            self.shape,
            self.tile,
            self.br,
            self.qmode,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild from :meth:`tree_flatten` output."""
        block_vals, block_ids, tile_nnz, scale, codebook = children
        shape, tile, br, qmode = aux
        return cls(block_vals, block_ids, tile_nnz, shape, tile, br,
                   scale=scale, codebook=codebook, qmode=qmode)

    @property
    def bcap(self) -> int:
        """Stored sub-blocks per tile (trailing block dim)."""
        return self.block_vals.shape[-3]

    @property
    def grid(self) -> tuple[int, int]:
        """``(Kt, Nt)`` tile-grid extents."""
        return self.block_vals.shape[-5], self.block_vals.shape[-4]

    @property
    def lead(self) -> tuple[int, ...]:
        """Leading stack dims (layer groups / experts), ahead of the grid."""
        return tuple(self.block_vals.shape[:-5])

    @property
    def dtype(self):
        """Stored value dtype: fp, int8 codes, or fp8."""
        return self.block_vals.dtype

    def nbytes_compressed(self, value_bits: int | None = None,
                          index_bits: int = 16) -> int:
        """Footprint: stored sub-block values + block ids (+ quant side
        band under a quantized ``qmode``, as in :class:`TiledCSC`)."""
        side = 0
        if value_bits is None:
            ncodes = (self.codebook.shape[-1] if self.codebook is not None
                      else CODEBOOK_SIZE)
            value_bits = qvalue_bits(self.qmode, ncodes)
            if self.scale is not None:
                side += int(np.prod(self.scale.shape)) * SCALE_BITS // 8
            if self.codebook is not None:
                side += int(np.prod(self.codebook.shape)) * SCALE_BITS // 8
        v = int(np.prod(self.block_vals.shape)) * value_bits // 8
        i = int(np.prod(self.block_ids.shape)) * index_bits // 8
        return v + i + side

    def nbytes_dense(self, value_bits: int = 16) -> int:
        """Dense-equivalent bytes at ``value_bits`` (lead dims included)."""
        # see TiledCSC.nbytes_dense: the lead dims count on both sides
        kp, np_ = padded_shape(self.shape, self.tile)
        return int(np.prod(self.lead, dtype=np.int64)) * kp * np_ \
            * value_bits // 8

    def dequantize(self) -> "BlockCSR":
        """The equivalent unquantized operand (cf. ``TiledCSC.dequantize``)."""
        if self.qmode == "none":
            return self
        bvals = _dequant_values(self.block_vals, self.scale, self.codebook,
                                self.qmode, 3)
        return BlockCSR(block_vals=bvals, block_ids=self.block_ids,
                        tile_nnz=self.tile_nnz, shape=self.shape,
                        tile=self.tile, br=self.br)

    def to_dense(self) -> jax.Array:
        """Differentiable scatter-add decompression to ``(*lead, K, N)``."""
        if self.qmode != "none":
            return self.dequantize().to_dense()
        if self.lead:
            bv = self.block_vals.reshape((-1,) + self.block_vals.shape[-5:])
            bi = self.block_ids.reshape((-1,) + self.block_ids.shape[-3:])
            tn = self.tile_nnz.reshape((-1,) + self.tile_nnz.shape[-2:])
            dense = jax.vmap(
                lambda v, i, n: BlockCSR(v, i, n, self.shape, self.tile,
                                         self.br).to_dense()
            )(bv, bi, tn)
            return dense.reshape(self.lead + dense.shape[-2:])
        kt_n, nt_n = self.grid
        bk, bn = self.tile
        br = self.br
        nb = bk // br
        bcap = self.bcap
        kt = jnp.arange(kt_n)[:, None, None]
        nt = jnp.arange(nt_n)[None, :, None]
        ids = self.block_ids
        bvals = jnp.where((ids >= 0)[:, :, :, None, None], self.block_vals, 0)
        dense = jnp.zeros((kt_n, nt_n, nb, br, bn), self.block_vals.dtype)
        dense = dense.at[
            jnp.broadcast_to(kt, ids.shape),
            jnp.broadcast_to(nt, ids.shape),
            ids,
        ].add(bvals, mode="drop")
        dense = dense.reshape(kt_n, nt_n, bk, bn).transpose(0, 2, 1, 3)
        dense = dense.reshape(kt_n * bk, nt_n * bn)
        return dense[: self.shape[0], : self.shape[1]]


def pack_block_csr(
    w: jax.Array,
    tile: tuple[int, int] = (128, 128),
    br: int = 8,
    bcap: int | None = None,
    qmode: str = "none",
    ncodes: int = CODEBOOK_SIZE,
) -> BlockCSR:
    """Pack a dense matrix into :class:`BlockCSR` (lossless for bcap=None).

    ``qmode`` quantizes ``block_vals`` after packing (:func:`quantize_packed`).
    """
    bk, bn = tile
    if bk % br:
        raise ValueError(f"tile rows {bk} not divisible by block rows {br}")
    w = jnp.asarray(w)
    if w.ndim > 2:
        lead = w.shape[:-2]
        flat = w.reshape((-1,) + w.shape[-2:])
        if bcap is None:
            bcap = max(observed_block_cap(w, tile, br), 1)
        packed = [pack_block_csr(flat[i], tile, br, bcap)
                  for i in range(flat.shape[0])]
        return quantize_packed(BlockCSR(
            block_vals=jnp.stack([p.block_vals for p in packed]).reshape(
                lead + packed[0].block_vals.shape),
            block_ids=jnp.stack([p.block_ids for p in packed]).reshape(
                lead + packed[0].block_ids.shape),
            tile_nnz=jnp.stack([p.tile_nnz for p in packed]).reshape(
                lead + packed[0].tile_nnz.shape),
            shape=tuple(w.shape[-2:]), tile=tile, br=br), qmode, ncodes)
    shape = tuple(w.shape)
    w = _pad_to_tiles(w, tile)
    kp, np_ = w.shape
    kt_n, nt_n = kp // bk, np_ // bn
    nb = bk // br
    blocks = w.reshape(kt_n, nb, br, nt_n, bn).transpose(0, 3, 1, 2, 4)
    # (Kt, Nt, nb, br, bn)
    nz = jnp.any(blocks != 0, axis=(3, 4))  # (Kt, Nt, nb)
    tile_nnz = jnp.sum(nz, axis=2).astype(jnp.int32)
    if bcap is None:
        bcap = max(int(jnp.max(tile_nnz)) if w.size else 0, 1)
    else:
        # an explicit (plan-provided) bcap may truncate; tile_nnz must
        # count the *stored* sub-blocks, not the pre-truncation ones
        tile_nnz = jnp.minimum(tile_nnz, bcap)
    # Keep the largest-L2 sub-blocks when bcap truncates (ESE-style load
    # capping, mirroring pack_tiled_csc's lossy path), then restore
    # ascending block-index order within the kept set — so the lossless
    # case (bcap ≥ every tile's count) lays out exactly as a plain
    # valid-first index-ordered pack.
    norms = jnp.sum(blocks.astype(jnp.float32) ** 2, axis=(3, 4))
    sel = jnp.argsort(jnp.where(nz, -norms, jnp.inf), axis=2,
                      stable=True)[:, :, :bcap]              # (Kt, Nt, bcap)
    sel_valid = jnp.take_along_axis(nz, sel, axis=2)
    asc = jnp.argsort(jnp.where(sel_valid, sel, nb), axis=2, stable=True)
    order = jnp.take_along_axis(sel, asc, axis=2)
    valid = jnp.take_along_axis(sel_valid, asc, axis=2)
    block_vals = jnp.take_along_axis(
        blocks, order[:, :, :, None, None], axis=2
    )
    block_vals = jnp.where(valid[:, :, :, None, None], block_vals, 0).astype(w.dtype)
    block_ids = jnp.where(valid, order, -1).astype(jnp.int32)
    return quantize_packed(BlockCSR(
        block_vals=block_vals,
        block_ids=block_ids,
        tile_nnz=tile_nnz,
        shape=shape,
        tile=(bk, bn),
        br=br,
    ), qmode, ncodes)


# ---------------------------------------------------------------------------
# Bitmap — SIGMA-style
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Bitmap:
    """Bitmap + row-major packed non-zero values (padded to ``cap``)."""

    mask: jax.Array   # (K, N) bool
    vals: jax.Array   # (cap,) packed row-major non-zeros
    shape: tuple[int, int]

    def tree_flatten(self):
        """Flatten into (array children, static aux) for jax pytrees."""
        return (self.mask, self.vals), (self.shape,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        """Rebuild from :meth:`tree_flatten` output."""
        mask, vals = children
        return cls(mask, vals, aux[0])

    def nbytes_compressed(self, value_bits: int = 16) -> int:
        """Bitmap bytes (1 bit/element) plus the stored value list."""
        bits = int(np.prod(self.mask.shape))  # 1 bit/element bitmap
        return bits // 8 + self.vals.shape[0] * value_bits // 8

    def nbytes_dense(self, value_bits: int = 16) -> int:
        """Dense-equivalent bytes at ``value_bits``."""
        return int(np.prod(self.shape)) * value_bits // 8

    def to_dense(self) -> jax.Array:
        """Reconstruct the dense matrix (bitmap-guided scatter)."""
        flat_mask = self.mask.reshape(-1)
        pos = jnp.cumsum(flat_mask) - 1
        gathered = self.vals[jnp.clip(pos, 0, self.vals.shape[0] - 1)]
        out = jnp.where(flat_mask, gathered, 0)
        return out.reshape(self.shape).astype(self.vals.dtype)


def pack_bitmap(w: jax.Array, cap: int | None = None) -> Bitmap:
    """Pack into :class:`Bitmap`: 1-bit mask + row-major value list."""
    w = jnp.asarray(w)
    mask = w != 0
    flat = w.reshape(-1)
    flat_mask = mask.reshape(-1)
    if cap is None:
        cap = max(int(jnp.sum(flat_mask)), 1)
    order = jnp.argsort(~flat_mask, stable=True)[:cap]
    vals = jnp.where(flat_mask[order], flat[order], 0)
    return Bitmap(mask=mask, vals=vals, shape=tuple(w.shape))


# ---------------------------------------------------------------------------
# Classic pointer CSC (numpy) — exact footprint accounting for the cost model
# ---------------------------------------------------------------------------
def pack_csc(w: np.ndarray) -> dict[str, np.ndarray]:
    """Classic CSC: values, row indices, column pointers (numpy, exact)."""
    w = np.asarray(w)
    k, n = w.shape
    cols = []
    rows = []
    vals = []
    ptr = [0]
    for j in range(n):
        nz = np.nonzero(w[:, j])[0]
        rows.append(nz)
        vals.append(w[nz, j])
        ptr.append(ptr[-1] + len(nz))
    return {
        "values": np.concatenate(vals) if vals else np.zeros((0,), w.dtype),
        "row_indices": np.concatenate(rows).astype(np.int32)
        if rows
        else np.zeros((0,), np.int32),
        "col_pointers": np.asarray(ptr, np.int64),
        "shape": np.asarray([k, n]),
    }


def unpack_csc(csc: dict[str, np.ndarray]) -> np.ndarray:
    """Reconstruct the dense matrix from a :func:`pack_csc` dict."""
    k, n = (int(x) for x in csc["shape"])
    out = np.zeros((k, n), csc["values"].dtype)
    ptr = csc["col_pointers"]
    for j in range(n):
        lo, hi = int(ptr[j]), int(ptr[j + 1])
        out[csc["row_indices"][lo:hi], j] = csc["values"][lo:hi]
    return out


def csc_nbytes(csc: dict[str, np.ndarray], value_bits: int = 16,
               index_bits: int = 8, pointer_bits: int = 32) -> int:
    """Byte footprint of a pointer-CSC dict at the given bit widths."""
    nnz = csc["values"].shape[0]
    ncols = csc["col_pointers"].shape[0]
    return (nnz * (value_bits + index_bits) + ncols * pointer_bits) // 8
