"""Format round-trips, byte accounting, gradients — incl. hypothesis sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # CI installs hypothesis; bare
    from _hypothesis_stub import given, settings, st  # noqa: E501  envs skip the property tests


from repro.core import formats, pruning


def _rand_sparse(seed, shape, density, dtype=jnp.float32):
    return pruning.random_sparse(jax.random.PRNGKey(seed), shape, density,
                                 dtype)


# ---------------------------------------------------------------------------
# TiledCSC
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,tile,density", [
    ((128, 128), (128, 128), 0.3),
    ((300, 260), (128, 128), 0.15),
    ((64, 200), (64, 128), 0.5),
    ((513, 129), (128, 128), 0.05),
])
def test_tiled_csc_roundtrip(shape, tile, density):
    w = _rand_sparse(0, shape, density)
    p = formats.pack_tiled_csc(w, tile=tile)
    np.testing.assert_allclose(np.asarray(p.to_dense()), np.asarray(w))


@settings(max_examples=25, deadline=None)
@given(
    k=st.integers(8, 200), n=st.integers(8, 200),
    density=st.floats(0.02, 0.95), seed=st.integers(0, 2**16),
)
def test_tiled_csc_roundtrip_hypothesis(k, n, density, seed):
    w = _rand_sparse(seed, (k, n), density)
    p = formats.pack_tiled_csc(w, tile=(128, 128))
    np.testing.assert_allclose(np.asarray(p.to_dense()), np.asarray(w))


def test_tiled_csc_leading_dims():
    w = _rand_sparse(1, (3, 2, 200, 130), 0.25)
    p = formats.pack_tiled_csc(w, tile=(128, 128))
    assert p.lead == (3, 2)
    np.testing.assert_allclose(np.asarray(p.to_dense()), np.asarray(w))
    # tree_map slicing (what lax.scan does) stays consistent
    p1 = jax.tree_util.tree_map(lambda t: t[1], p)
    np.testing.assert_allclose(np.asarray(p1.to_dense()), np.asarray(w[1]))


def test_tiled_csc_lossy_cap_keeps_largest():
    w = _rand_sparse(2, (128, 128), 0.9)
    p = formats.pack_tiled_csc(w, cap=16)
    d = np.asarray(p.to_dense())
    assert (np.count_nonzero(d, axis=0) <= 16).all()
    # kept entries are a subset of the original with the largest magnitudes
    col = 0
    orig = np.asarray(w)[:, col]
    kept = np.nonzero(d[:, col])[0]
    dropped = np.setdiff1d(np.nonzero(orig)[0], kept)
    if len(dropped) and len(kept):
        assert np.abs(orig[kept]).min() >= np.abs(orig[dropped]).max() - 1e-6


def _slots_by_hand(w, tile, cap, lossless):
    """Each tile column's (values, rows), written out column by column:
    with ``cap >= bk`` the non-zeros first, then the zero rows; with a
    lossless cap every non-zero and the first zero rows that fill ``cap``,
    in row order; otherwise the ``cap`` largest magnitudes, ties to the
    lower row, in row order.  A slot without a non-zero holds (0, -1)."""
    bk, bn = tile
    kp, np_ = formats.padded_shape(w.shape, tile)
    w = np.pad(w, ((0, kp - w.shape[0]), (0, np_ - w.shape[1])))
    vals = np.zeros((kp // bk, np_ // bn, max(cap, bk), bn), w.dtype)
    rows = np.full(vals.shape, -1)
    for kt in range(kp // bk):
        for c in range(np_):
            col = w[kt * bk:(kt + 1) * bk, c]
            nz = [r for r in range(bk) if col[r] != 0]
            zero = [r for r in range(bk) if col[r] == 0]
            if cap >= bk:
                keep = nz + zero
            elif lossless:
                keep = sorted(nz + zero[:cap - len(nz)])
            else:
                keep = sorted(sorted(nz, key=lambda r: -abs(col[r]))[:cap]
                              + zero[:max(cap - len(nz), 0)])
            for s, r in enumerate(keep):
                if col[r] != 0:
                    vals[kt, c // bn, s, c % bn] = col[r]
                    rows[kt, c // bn, s, c % bn] = r
    return vals[:, :, :cap], rows[:, :, :cap]


@pytest.mark.parametrize("cap", [None, 2, 4, 8, 16])
def test_tiled_csc_slot_layout(cap):
    """The packed slots, bit for bit, in each capacity regime, integer
    values making magnitude ties common."""
    rng = np.random.default_rng(5)
    w = np.round(rng.standard_normal((2, 20, 13)) * 2).astype(np.float32)
    w[0, :, 3] = 0.0
    p = formats.pack_tiled_csc(jnp.asarray(w), tile=(8, 8), cap=cap)
    for i in range(2):
        obs = int(np.max(np.count_nonzero(
            np.pad(w, ((0, 0), (0, 4), (0, 3))).reshape(2, 3, 8, 2, 8),
            axis=2)))
        c = max((obs + 7) // 8 * 8, 8) if cap is None else cap
        vals, rows = _slots_by_hand(w[i], (8, 8), c, cap is None)
        np.testing.assert_array_equal(np.asarray(p.vals[i]), vals)
        np.testing.assert_array_equal(np.asarray(p.rows[i]), rows)


def test_tiled_csc_grad_exact_on_mask():
    w = _rand_sparse(3, (256, 128), 0.3)
    p = formats.pack_tiled_csc(w)
    g = jax.grad(lambda q: jnp.sum(q.to_dense() ** 2), allow_int=True)(p)
    np.testing.assert_allclose(np.asarray(g.vals), 2 * np.asarray(p.vals),
                               rtol=1e-5, atol=1e-6)


def test_tiled_csc_bytes_paper_encoding():
    w = _rand_sparse(4, (256, 256), 0.25)
    p = formats.pack_tiled_csc(w)
    # 16-bit value + 8-bit index per slot
    assert p.nbytes_compressed() == p.vals.size * 3
    assert p.nbytes_dense() == 256 * 256 * 2
    assert p.compression_ratio() < 1.0


# ---------------------------------------------------------------------------
# BlockCSR
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("density", [0.1, 0.4, 0.8])
def test_block_csr_roundtrip(density):
    w = pruning.block_prune(_rand_sparse(5, (300, 260), 0.8), density)
    p = formats.pack_block_csr(w)
    np.testing.assert_allclose(np.asarray(p.to_dense()), np.asarray(w))
    nz_frac = float(jnp.count_nonzero(p.tile_nnz)) / p.tile_nnz.size
    assert nz_frac <= 1.0


def test_block_csr_leading_dims():
    w = pruning.block_prune(_rand_sparse(6, (256, 128), 0.9), 0.5)
    ws = jnp.stack([w, w * 2.0])
    p = formats.pack_block_csr(ws)
    np.testing.assert_allclose(np.asarray(p.to_dense()), np.asarray(ws))


# ---------------------------------------------------------------------------
# Bitmap + pointer CSC
# ---------------------------------------------------------------------------
@settings(max_examples=15, deadline=None)
@given(k=st.integers(4, 100), n=st.integers(4, 100),
       density=st.floats(0.05, 0.9), seed=st.integers(0, 2**16))
def test_bitmap_roundtrip(k, n, density, seed):
    w = _rand_sparse(seed, (k, n), density)
    b = formats.pack_bitmap(w)
    np.testing.assert_allclose(np.asarray(b.to_dense()), np.asarray(w))


def test_csc_pointer_roundtrip_and_bytes():
    w = np.asarray(_rand_sparse(7, (120, 80), 0.2))
    csc = formats.pack_csc(w)
    np.testing.assert_allclose(formats.unpack_csc(csc), w)
    nnz = csc["values"].shape[0]
    assert formats.csc_nbytes(csc) == (nnz * 24 + 81 * 32) // 8
    # compressed beats dense below the paper's breakeven (~2/3 density)
    assert formats.csc_nbytes(csc) < w.size * 2


def test_density_helper():
    assert formats.density(np.zeros((4, 4))) == 0.0
    assert formats.density(np.ones((4, 4))) == 1.0


# ---------------------------------------------------------------------------
# Quantized value storage (int8 / fp8 / codebook)
# ---------------------------------------------------------------------------
QMODES = [m for m in ("int8", "fp8", "codebook")
          if m != "fp8" or formats.fp8_dtype() is not None]


def _pack(fmt, w, qmode="none"):
    if fmt == "tiled_csc":
        return formats.pack_tiled_csc(w, qmode=qmode)
    return formats.pack_block_csr(w, qmode=qmode)


@pytest.mark.parametrize("qmode", QMODES)
@pytest.mark.parametrize("fmt", ["tiled_csc", "block_csr"])
def test_quantized_pack_preserves_sparsity_and_shrinks(fmt, qmode):
    """Quantized packs keep the zero pattern exactly, bound the value
    error, and strictly shrink the byte footprint vs the fp pack."""
    w = _rand_sparse(11, (256, 300), 0.3)
    if fmt == "block_csr":
        w = pruning.block_prune(_rand_sparse(11, (256, 300), 0.8), 0.3)
    fp = _pack(fmt, w)
    q = _pack(fmt, w, qmode=qmode)
    dq = np.asarray(q.to_dense())
    dense = np.asarray(w)
    # zeros stay exactly zero (padding + pruned slots map to code 0)
    assert (dq[dense == 0] == 0).all()
    absmax = np.abs(dense).max()
    err = np.abs(dq - dense).max()
    if qmode == "int8":
        assert err <= absmax / 253  # half-step of absmax/127 per-tile scale
    elif qmode == "fp8":
        # e4m3: 3 mantissa bits -> half-ulp rel err 2^-4, plus granularity
        assert err <= 0.07 * absmax
    else:  # codebook: values snap to the 16-entry shared table
        book = np.asarray(q.codebook).ravel()
        nz = dq[dense != 0]
        assert np.isin(nz, book).all()
        rel = np.linalg.norm(dq - dense) / np.linalg.norm(dense)
        assert rel < 0.5
    assert q.nbytes_compressed() < fp.nbytes_compressed()
    assert q.qmode == qmode


@settings(max_examples=20, deadline=None)
@given(k=st.integers(16, 160), n=st.integers(16, 160),
       density=st.floats(0.05, 0.9), seed=st.integers(0, 2**16))
def test_int8_quant_roundtrip_error_bound_hypothesis(k, n, density, seed):
    """Property: per-tile int8 scaling bounds elementwise error by half a
    quantization step of the tile's absmax, at any shape/density."""
    w = _rand_sparse(seed, (k, n), density)
    p = formats.pack_tiled_csc(w, qmode="int8")
    dq = np.asarray(p.to_dense())
    dense = np.asarray(w)
    assert np.abs(dq - dense).max() <= max(np.abs(dense).max(), 1e-30) / 253
    assert (dq[dense == 0] == 0).all()


@pytest.mark.parametrize("qmode", QMODES)
def test_quantized_stacked_lead_dims(qmode):
    """Stacked (lead-dim) packs quantize per slice and slice consistently
    under tree_map — scale is per (slice, tile), codebook per slice."""
    w = _rand_sparse(12, (3, 128, 130), 0.25)
    p = formats.pack_tiled_csc(w, qmode=qmode)
    p1 = jax.tree_util.tree_map(lambda t: t[1], p)
    np.testing.assert_allclose(np.asarray(p1.to_dense()),
                               np.asarray(p.to_dense())[1])


def test_quantize_packed_identity_and_double_quant_rejected():
    w = _rand_sparse(13, (128, 128), 0.3)
    p = formats.pack_tiled_csc(w)
    assert formats.quantize_packed(p, "none") is p
    q = formats.quantize_packed(p, "int8")
    assert formats.quantize_packed(q, "int8") is q
    with pytest.raises(ValueError, match="already quantized"):
        formats.quantize_packed(q, "codebook")


def test_quantized_grad_flows_into_scale():
    """Training gradients reach the quantization side bands: d/dscale of a
    loss over to_dense() is the chain-rule sum over the tile's codes."""
    w = _rand_sparse(14, (128, 128), 0.3)
    q = formats.pack_tiled_csc(w, qmode="int8")
    g = jax.grad(lambda c: jnp.sum(c.to_dense()), allow_int=True)(q)
    codes = np.asarray(q.vals, np.float32)
    np.testing.assert_allclose(np.asarray(g.scale),
                               codes.sum(axis=(-2, -1)), rtol=1e-5)
