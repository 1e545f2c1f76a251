"""GQA attention: chunked (flash-style) training/prefill + cached decode.

Supports the assigned-architecture feature set: grouped KV heads, local
(sliding-window) vs global layers (gemma-2 alternation), attention logit
soft-capping, RoPE, and arbitrary-position cached decoding.

The full-sequence path is chunked with an online-softmax scan over KV blocks
(O(S) memory — required for the 32k prefill cells).  Sliding-window layers
scan only the ``window//chunk + 1`` KV blocks that can intersect the window
(O(S·W) compute instead of O(S²)).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import sod
from repro.models import layers

Params = dict[str, Any]

NEG_INF = -2.0e38


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Shape/behaviour spec for one attention layer: head geometry, RoPE
    base, logit scaling/soft-capping, and the flash-chunk sizes."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    scale: float | None = None      # default 1/sqrt(head_dim)
    softcap: float | None = None
    chunk_q: int = 512
    chunk_k: int = 512

    @property
    def q_scale(self) -> float:
        """Query scaling applied to logits (``scale`` or 1/sqrt(hd))."""
        return self.scale if self.scale is not None else self.head_dim**-0.5


def init_attention(key, d_model: int, spec: AttnSpec, dtype=jnp.bfloat16) -> Params:
    """Initialize the q/k/v/o projection weights for one attention layer."""
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": layers.dense_init(kq, d_model, spec.n_heads * spec.head_dim, dtype),
        "wk": layers.dense_init(kk, d_model, spec.n_kv_heads * spec.head_dim, dtype),
        "wv": layers.dense_init(kv, d_model, spec.n_kv_heads * spec.head_dim, dtype),
        "wo": layers.dense_init(ko, spec.n_heads * spec.head_dim, d_model, dtype),
    }


def _project_qkv(params: Params, x: jax.Array, spec: AttnSpec,
                 positions: jax.Array):
    b, s, _ = x.shape
    q = sod.apply(x, params["wq"]).reshape(b, s, spec.n_heads, spec.head_dim)
    k = sod.apply(x, params["wk"]).reshape(b, s, spec.n_kv_heads, spec.head_dim)
    v = sod.apply(x, params["wv"]).reshape(b, s, spec.n_kv_heads, spec.head_dim)
    q = layers.apply_rope(q, positions, spec.rope_theta)
    k = layers.apply_rope(k, positions, spec.rope_theta)
    return q, k, v


def _block_scores(q, k, spec: AttnSpec):
    """q (B,Cq,KV,G,hd) × k (B,Ck,KV,hd) → (B,KV,G,Cq,Ck) float32."""
    s = jnp.einsum(
        "bqkgh,bckh->bkgqc", q, k, preferred_element_type=jnp.float32
    )
    s = s * spec.q_scale
    if spec.softcap is not None:
        s = spec.softcap * jnp.tanh(s / spec.softcap)
    return s


def _online_block(carry, scores, v_blk, mask):
    """One online-softmax update.  scores (B,KV,G,Cq,Ck) f32."""
    m_prev, l_prev, acc_prev = carry
    scores = jnp.where(mask, scores, NEG_INF)
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m_prev, m_blk)
    # guard fully-masked rows
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(scores - safe_m[..., None])
    p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(jnp.where(jnp.isfinite(m_prev), m_prev - safe_m, NEG_INF))
    l_new = l_prev * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum(
        "bkgqc,bckh->bkgqh", p.astype(v_blk.dtype), v_blk,
        preferred_element_type=jnp.float32,
    )
    acc_new = acc_prev * corr[..., None] + pv
    return m_new, l_new, acc_new


def chunked_attention(
    q: jax.Array,             # (B, S, H, hd)
    k: jax.Array,             # (B, S, KV, hd)
    v: jax.Array,             # (B, S, KV, hv)
    spec: AttnSpec,
    window: int | None = None,
) -> jax.Array:
    """Causal (optionally sliding-window) attention, O(S) memory.
    Returns (B, S, H, hv)."""
    b, s, h, hd = q.shape
    kvh = k.shape[2]
    hv = v.shape[-1]          # value width (latent attention: < hd)
    g = h // kvh
    cq = min(spec.chunk_q, s)
    ck = min(spec.chunk_k, s)
    if s % cq or s % ck:
        raise ValueError(f"seq {s} not divisible by chunks ({cq},{ck})")
    nq, nk = s // cq, s // ck
    qc = q.reshape(b, nq, cq, kvh, g, hd)

    if window is not None:
        # only blocks intersecting [q_start - window, q_end] matter
        n_rel = (window + cq) // ck + 1
    else:
        n_rel = None

    def q_chunk_body(i):
        qi = qc[:, i]
        q_pos = i * cq + jnp.arange(cq)

        def kv_step(carry, c):
            if window is not None:
                raw = i * cq + cq - (n_rel - c) * ck
                start = jnp.clip(raw, 0, s - ck)
            else:
                raw = start = c * ck
            k_blk = jax.lax.dynamic_slice(k, (0, start, 0, 0), (b, ck, kvh, hd))
            v_blk = jax.lax.dynamic_slice(v, (0, start, 0, 0), (b, ck, kvh, hv))
            k_pos = start + jnp.arange(ck)
            mask = k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                mask &= k_pos[None, :] > q_pos[:, None] - window
                # clipping can re-slice keys a neighbouring step also covers;
                # only this step's raw range [raw, raw+ck) may contribute
                in_range = (k_pos >= raw) & (k_pos < raw + ck)
                mask &= in_range[None, :]
            mask = mask[None, None, None]  # (1,1,1,Cq,Ck)
            scores = _block_scores(qi, k_blk, spec)
            return _online_block(carry, scores, v_blk, mask), None

        init = (
            jnp.full((b, kvh, g, cq), NEG_INF, jnp.float32),
            jnp.zeros((b, kvh, g, cq), jnp.float32),
            jnp.zeros((b, kvh, g, cq, hv), jnp.float32),
        )
        n_steps = n_rel if window is not None else nk
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(n_steps))
        out = acc / jnp.maximum(l, 1e-30)[..., None]        # (B,KV,G,Cq,hv)
        return out.transpose(0, 3, 1, 2, 4).reshape(b, cq, h, hv)

    out = jax.lax.map(q_chunk_body, jnp.arange(nq))
    # (nq, B, Cq, H, hv) → (B, S, H, hv)
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hv)
    return out.astype(q.dtype)


def full_attention(
    params: Params,
    x: jax.Array,
    spec: AttnSpec,
    positions: jax.Array,
    window: int | None = None,
) -> jax.Array:
    """Training / prefill self-attention over a full sequence."""
    q, k, v = _project_qkv(params, x, spec, positions)
    out = chunked_attention(q, k, v, spec, window=window)
    b, s = x.shape[:2]
    return sod.apply(out.reshape(b, s, -1), params["wo"])


# ---------------------------------------------------------------------------
# cached decode
# ---------------------------------------------------------------------------
def init_cache(batch: int, max_len: int, spec: AttnSpec,
               dtype=jnp.bfloat16) -> Params:
    """Allocate a zeroed dense per-slot KV cache of ``max_len`` positions."""
    shape = (batch, max_len, spec.n_kv_heads, spec.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _decode_positions(pos: jax.Array, b: int) -> jax.Array:
    """(B, 1) RoPE positions from a scalar or per-sequence ``pos``."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        return jnp.full((b, 1), pos, jnp.int32)
    return pos.reshape(b, 1)


def _attend_cached(q, k_cache, v_cache, pos, spec: AttnSpec,
                   window: int | None):
    """One-token attention over a position-ordered KV cache.

    q (B,1,H,hd); caches (B,L,KV,hd); ``pos`` scalar or (B,).  Keys at
    positions beyond each row's ``pos`` (or outside its sliding window)
    are masked per row.
    """
    b = q.shape[0]
    s_max = k_cache.shape[1]
    kvh = spec.n_kv_heads
    g = spec.n_heads // kvh
    qh = q.reshape(b, 1, kvh, g, spec.head_dim)
    scores = _block_scores(qh, k_cache, spec)   # (B,KV,G,1,Smax)
    k_pos = jnp.arange(s_max)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
    mask = k_pos[None, :] <= pos_b[:, None]
    if window is not None:
        mask &= k_pos[None, :] > pos_b[:, None] - window
    scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgqc,bckh->bqkgh", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, spec.n_heads * spec.head_dim)


def decode_attention(
    params: Params,
    x: jax.Array,             # (B, 1, D)
    cache: Params,
    pos: jax.Array,           # current position: scalar, or (B,) per row
    spec: AttnSpec,
    window: int | None = None,
):
    """One decode step: update cache at ``pos``, attend to the prefix.

    ``pos`` may be a scalar (every row at the same position — the static
    serve path) or a ``(B,)`` vector (ragged continuous batching: each
    row writes its new KV at its own position and gets its own causal /
    window mask).
    """
    b = x.shape[0]
    pos = jnp.asarray(pos)
    q, k_new, v_new = _project_qkv(params, x, spec, _decode_positions(pos, b))
    if pos.ndim == 0:
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k_new, (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v_new, (0, pos, 0, 0))
    else:
        upd = jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (p, 0, 0)))
        k_cache = upd(cache["k"], k_new, pos)
        v_cache = upd(cache["v"], v_new, pos)
    out = _attend_cached(q, k_cache, v_cache, pos, spec, window)
    out = out.astype(x.dtype)
    return sod.apply(out, params["wo"]), {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# paged decode (continuous-batching engine)
# ---------------------------------------------------------------------------
def init_paged_pool(n_pages: int, page_size: int, spec: AttnSpec,
                    dtype=jnp.bfloat16) -> Params:
    """A pool of fixed-size KV pages shared by all running sequences.

    Page 0 is conventionally the trash page: inactive engine slots point
    their whole block table at it, so their (ignored) writes never touch
    a live sequence.  The allocator in :mod:`repro.serving.pool` never
    hands it out.
    """
    shape = (n_pages, page_size, spec.n_kv_heads, spec.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def paged_kv(pool: Params, layer, page: jax.Array, off: jax.Array,
             k_new: jax.Array, v_new: jax.Array, block_tables: jax.Array):
    """Write one layer's new KV rows into the pool, then gather the layer's
    rows back position-ordered.  Every paged attention call touches the
    pool through here.

    ``pool`` arrays are (..., n_pages, page, KV, hd): one layer's pool, or
    a stack of every layer's pool (the transformer's).  The leading layer
    axes fold into the page axis by a reshape (a bitcast: only major axes
    merge, the heads axis never moves) and ``layer`` — the flat layer index
    over them, 0 for a single layer — offsets every page id.  So neither the
    scatter nor the gather slices the layer's pool out, and a pool carried
    through a layer scan (and donated by the program) is updated in place.

    ``page``/``off`` name the N written rows; ``k_new``/``v_new`` are
    (N, KV, hd).  Returns (pool, k_cache, v_cache), the caches
    (B, max_pages·page, KV, hd).
    """
    pool, cache = paged_update(pool, layer, page, off,
                               {"k": k_new, "v": v_new}, block_tables)
    return pool, cache["k"], cache["v"]


def paged_update(pool: Params, layer, page: jax.Array, off: jax.Array,
                 new: Params, block_tables: jax.Array):
    """:func:`paged_kv` for any sides of a pool, each (..., n_pages, page,
    *row): write each side's new rows ``new[name]`` (N, *row) at
    ``page``/``off``, then gather the side's rows back position-ordered,
    (B, max_pages·page, *row).  Returns (pool, caches), both keyed as
    ``new``."""
    first = next(iter(new))
    n_pages = pool[first].shape[-new[first].ndim - 1]
    page = layer * n_pages + page
    rows = layer * n_pages + block_tables
    b = block_tables.shape[0]

    def update(a, new):
        flat = a.reshape((-1,) + a.shape[-new.ndim:])
        flat = flat.at[page, off].set(new)
        cache = flat[rows].reshape(b, -1, *a.shape[1 - new.ndim:])
        return flat.reshape(a.shape), cache

    out, caches = dict(pool), {}
    for name, rows_new in new.items():
        out[name], caches[name] = update(pool[name], rows_new)
    return out, caches


def paged_prefill_attention(
    params: Params,
    x: jax.Array,              # (B, C, D) — one chunk of prompt tokens
    pool: Params,              # {"k","v"}: (n_pages, page, KV, hd)
    block_tables: jax.Array,   # (B, max_pages) page ids per logical block
    start: jax.Array,          # scalar: first position in this chunk
    valid_len: jax.Array,      # scalar: prompt length (pad cutoff)
    spec: AttnSpec,
    window: int | None = None,
    layer=0,
):
    """Chunked-prefill attention: C prompt positions against the pool.

    The chunk covers positions ``[start, start + C)``; its KV is scattered
    into the pages named by each position's block-table entry (positions
    at or beyond ``valid_len`` — final-chunk padding — are redirected to
    the trash page so they can never dirty a live page), then the whole
    table is gathered back position-ordered and each query row attends
    under its own causal / sliding-window mask.

    Numerics mirror :func:`chunked_attention` exactly for prompts the
    reference computes in a single online-softmax block (``plen <=
    attn_chunk`` — the same regime the engine's page-bucketed full prefill
    already relies on): one :func:`_online_block` update over the gathered
    keys, where positions outside a row's mask contribute exact zeros.
    Rows are position-independent, so the chunk split itself never changes
    a token.  ``pool`` and ``layer`` are as in :func:`paged_kv`; the whole
    pool comes back.
    """
    b, c, _ = x.shape
    start = jnp.asarray(start, jnp.int32)
    valid_len = jnp.asarray(valid_len, jnp.int32)
    idx = start + jnp.arange(c, dtype=jnp.int32)            # (C,)
    positions = jnp.broadcast_to(idx, (b, c))
    q, k_new, v_new = _project_qkv(params, x, spec, positions)
    page_size = pool["k"].shape[-3]
    kvh = spec.n_kv_heads
    g = spec.n_heads // kvh
    hd = spec.head_dim

    page = jnp.take_along_axis(
        block_tables, jnp.broadcast_to(idx // page_size, (b, c)), axis=1)
    page = jnp.where((idx < valid_len)[None, :], page, 0)   # pad → trash
    off = jnp.broadcast_to(idx % page_size, (b, c))
    pool, k_cache, v_cache = paged_kv(
        pool, layer, page.reshape(-1), off.reshape(-1),
        k_new.reshape(b * c, kvh, hd), v_new.reshape(b * c, kvh, hd),
        block_tables)
    s_max = k_cache.shape[1]

    qh = q.reshape(b, c, kvh, g, hd)
    scores = _block_scores(qh, k_cache, spec)   # (B,KV,G,C,Smax)
    k_pos = jnp.arange(s_max)
    mask = k_pos[None, :] <= idx[:, None]
    if window is not None:
        mask &= k_pos[None, :] > idx[:, None] - window
    mask = mask[None, None, None]               # (1,1,1,C,Smax)
    init = (
        jnp.full((b, kvh, g, c), NEG_INF, jnp.float32),
        jnp.zeros((b, kvh, g, c), jnp.float32),
        jnp.zeros((b, kvh, g, c, hd), jnp.float32),
    )
    _, l, acc = _online_block(init, scores, v_cache, mask)
    out = acc / jnp.maximum(l, 1e-30)[..., None]            # (B,KV,G,C,hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, c, spec.n_heads * hd)
    out = out.astype(x.dtype)
    return out, pool


def paged_verify_attention(
    params: Params,
    x: jax.Array,              # (B, C, D) — per-row speculative window
    pool: Params,              # {"k","v"}: (n_pages, page, KV, hd)
    block_tables: jax.Array,   # (B, max_pages) page ids per logical block
    start: jax.Array,          # (B,) first window position per row
    valid_len: jax.Array,      # (B,) per-row write cutoff (seq end)
    spec: AttnSpec,
    window: int | None = None,
    layer=0,
):
    """Speculative-decoding verification: C positions per row, decode
    numerics.

    Row ``b`` scores window positions ``[start[b], start[b] + C)`` against
    its paged cache — the scatter/gather plumbing of
    :func:`paged_prefill_attention` (positions at or beyond ``valid_len[b]``
    redirect to the trash page so an over-long window can never dirty a
    live page) combined with the attention core of :func:`_attend_cached`
    generalized to C query rows.  That core choice is the whole point: the
    decode path normalizes scores with a float32 softmax *before* the
    bf16 value einsum, while the prefill path casts unnormalized
    online-softmax probabilities — so only this shape is bitwise identical
    to running :func:`paged_decode_attention` sequentially over the same
    tokens, which is what makes accepted speculative tokens exactly the
    greedy sequence.  ``pool`` and ``layer`` are as in :func:`paged_kv`.
    """
    b, c, _ = x.shape
    start = jnp.asarray(start, jnp.int32).reshape(b)
    valid_len = jnp.asarray(valid_len, jnp.int32).reshape(b)
    idx = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]  # (B,C)
    q, k_new, v_new = _project_qkv(params, x, spec, idx)
    page_size = pool["k"].shape[-3]
    kvh = spec.n_kv_heads
    g = spec.n_heads // kvh
    hd = spec.head_dim

    page = jnp.take_along_axis(block_tables, idx // page_size, axis=1)
    page = jnp.where(idx < valid_len[:, None], page, 0)     # overflow → trash
    off = idx % page_size
    pool, k_cache, v_cache = paged_kv(
        pool, layer, page.reshape(-1), off.reshape(-1),
        k_new.reshape(b * c, kvh, hd), v_new.reshape(b * c, kvh, hd),
        block_tables)
    s_max = k_cache.shape[1]

    qh = q.reshape(b, c, kvh, g, hd)
    scores = _block_scores(qh, k_cache, spec)   # (B,KV,G,C,Smax)
    k_pos = jnp.arange(s_max)
    mask = k_pos[None, None, :] <= idx[:, :, None]          # (B,C,Smax)
    if window is not None:
        mask &= k_pos[None, None, :] > idx[:, :, None] - window
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgqc,bckh->bqkgh", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    out = out.reshape(b, c, spec.n_heads * hd).astype(x.dtype)
    return sod.apply(out, params["wo"]), pool


def paged_decode_attention(
    params: Params,
    x: jax.Array,              # (B, 1, D)
    pool: Params,              # {"k","v"}: (n_pages, page, KV, hd)
    block_tables: jax.Array,   # (B, max_pages) page ids per logical block
    pos: jax.Array,            # (B,) per-sequence positions
    spec: AttnSpec,
    window: int | None = None,
    valid_len: jax.Array | None = None,
    layer=0,
):
    """One decode step against the paged KV pool.

    Row ``b``'s logical position ``p`` lives in page
    ``block_tables[b, p // page]`` at offset ``p % page``; the new token's
    KV is scattered there, then the row's pages are gathered back into
    position order and attended with the same per-row mask as the dense
    vector-``pos`` path — so paged and dense decode are exactly
    interchangeable for equal cache contents.

    ``valid_len`` (optional, (B,)) is a per-row write cutoff: rows whose
    ``pos`` is at or beyond it redirect their KV write to the trash page.
    The engine uses it to run one batched step over a mix of decoding and
    prefilling/idle slots (cutoff 0) without copying block tables on the
    host, and to keep draft steps probing past a sequence's end from
    dirtying a live page.  Reads are unaffected — the attention mask
    already scopes each row to ``<= pos``.

    ``pool`` and ``layer`` are as in :func:`paged_kv`; the whole pool
    comes back.
    """
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, spec, _decode_positions(pos, b))
    page_size = pool["k"].shape[-3]
    page = jnp.take_along_axis(
        block_tables, (pos // page_size)[:, None], axis=1)[:, 0]
    if valid_len is not None:
        valid_len = jnp.asarray(valid_len, jnp.int32).reshape(b)
        page = jnp.where(pos < valid_len, page, 0)      # overflow → trash
    off = pos % page_size
    pool, k_cache, v_cache = paged_kv(pool, layer, page, off, k_new[:, 0],
                                      v_new[:, 0], block_tables)
    out = _attend_cached(q, k_cache, v_cache, pos, spec, window)
    out = out.astype(x.dtype)
    return sod.apply(out, params["wo"]), pool


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MLASpec:
    """Latent attention without a query compression (``q_lora_rank``
    null): queries are ``qk_nope`` un-roped plus ``qk_rope`` roped dims a
    head; keys and values are up-projected from a ``kv_lora_rank`` latent
    shared by all heads, plus one roped key of ``qk_rope`` dims shared by
    all heads."""

    n_heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    rope_theta: float = 10000.0
    yarn: Any = None                # layers.Yarn, or None
    norm_eps: float = 1e-6
    chunk: int = 512

    @property
    def row_dim(self) -> int:
        """Width of one cached row: the normed latent, then the roped key."""
        return self.kv_lora_rank + self.qk_rope

    @property
    def cache_dim(self) -> int:
        """A cached row padded with zeros to whole 128-lane tiles (576 →
        640): with a row that tiles the chip's lanes the pool keeps its
        position-major layout, where an unpadded one is laid out
        position-minor by the compiler and copied whole to be written."""
        return -(-self.row_dim // 128) * 128

    @property
    def scale(self) -> float:
        return layers.yarn_softmax_scale(self.qk_nope + self.qk_rope,
                                         self.yarn)

    def full_spec(self) -> AttnSpec:
        """The prefill's attention: every head its own up-projected keys
        (``qk_nope + qk_rope`` wide) and values (``v_dim``)."""
        return AttnSpec(n_heads=self.n_heads, n_kv_heads=self.n_heads,
                        head_dim=self.qk_nope + self.qk_rope,
                        scale=self.scale, chunk_q=self.chunk,
                        chunk_k=self.chunk)


def init_mla(key, d_model: int, spec: MLASpec,
             dtype=jnp.bfloat16) -> Params:
    """``wq`` (the query), ``wkv_a`` (down to the latent and the shared
    roped key), the latent's RMSNorm gain ``kv_norm``, ``wkv_b`` (the
    latent up to every head's un-roped key and value) and ``wo``."""
    kq, ka, kb, ko = jax.random.split(key, 4)
    h = spec.n_heads
    return {
        "wq": layers.dense_init(kq, d_model,
                                h * (spec.qk_nope + spec.qk_rope), dtype),
        "wkv_a": layers.dense_init(ka, d_model, spec.row_dim, dtype),
        "kv_norm": layers.init_rms_norm(spec.kv_lora_rank),
        "wkv_b": layers.dense_init(kb, spec.kv_lora_rank,
                                   h * (spec.qk_nope + spec.v_dim), dtype),
        "wo": layers.dense_init(ko, h * spec.v_dim, d_model, dtype),
    }


def _mla_query(params: Params, x: jax.Array, spec: MLASpec,
               positions: jax.Array):
    """(q_nope (B,S,H,qk_nope), q_pe (B,S,H,qk_rope) roped)."""
    b, s, _ = x.shape
    q = sod.apply(x, params["wq"]).reshape(b, s, spec.n_heads, -1)
    q_pe = layers.apply_rope(q[..., spec.qk_nope:], positions,
                             spec.rope_theta, spec.yarn)
    return q[..., :spec.qk_nope], q_pe


def mla_rows(params: Params, x: jax.Array, spec: MLASpec,
             positions: jax.Array) -> jax.Array:
    """Each position's cached row (B, S, cache_dim): the normed latent
    ``c_KV`` and the roped shared key ``k_R`` (the paper's eqs. 9 and
    14), all that latent attention keeps of a position, then zeros."""
    kv = sod.apply(x, params["wkv_a"])
    r = spec.kv_lora_rank
    c = layers.rms_norm(kv[..., :r], params["kv_norm"], spec.norm_eps)
    k_pe = layers.apply_rope(kv[..., None, r:], positions, spec.rope_theta,
                             spec.yarn)[..., 0, :]
    pad = jnp.zeros(c.shape[:-1] + (spec.cache_dim - spec.row_dim,), c.dtype)
    return jnp.concatenate([c, k_pe, pad], axis=-1)


def mla_full(params: Params, x: jax.Array, spec: MLASpec,
             positions: jax.Array):
    """Full-sequence latent attention as the paper writes it: keys and
    values up-projected from the latent for every head, causal attention
    over them.  Returns (output (B,S,D), cached rows (B,S,cache_dim))."""
    b, s, _ = x.shape
    h, nope, r = spec.n_heads, spec.qk_nope, spec.kv_lora_rank
    q_nope, q_pe = _mla_query(params, x, spec, positions)
    rows = mla_rows(params, x, spec, positions)
    kv = sod.apply(rows[..., :r], params["wkv_b"]).reshape(b, s, h, -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(rows[:, :, None, r:spec.row_dim],
                                          (b, s, h, spec.qk_rope))], -1)
    q = jnp.concatenate([q_nope, q_pe], -1)
    out = chunked_attention(q, k, kv[..., nope:], spec.full_spec())
    return sod.apply(out.reshape(b, s, -1), params["wo"]), rows


def _mla_absorbed(params: Params, q_nope: jax.Array, q_pe: jax.Array,
                  cache: jax.Array, mask: jax.Array, spec: MLASpec):
    """Attention over cached rows with ``W_UK`` absorbed into the query and
    ``W_UV`` applied after: one key head of ``row_dim`` (latent, then roped
    key; the zero padding adds nothing) and one value head of the latent,
    shared by every query head.  q_nope (B,C,H,qk_nope), q_pe
    (B,C,H,qk_rope), cache (B,L,cache_dim), mask (B,C,L).  Returns (B, C,
    H·v_dim)."""
    h, nope, r = spec.n_heads, spec.qk_nope, spec.kv_lora_rank
    w = params["wkv_b"].reshape(r, h, nope + spec.v_dim)
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bchd,rhd->bchr", q_nope, w[..., :nope],
                           preferred_element_type=jnp.float32)
        pad = jnp.zeros(q_pe.shape[:-1] + (spec.cache_dim - spec.row_dim,),
                        cache.dtype)
        q_abs = jnp.concatenate([q_lat.astype(cache.dtype),
                                 q_pe.astype(cache.dtype), pad], -1)
        scores = jnp.einsum("bchl,bsl->bhcs", q_abs, cache,
                            preferred_element_type=jnp.float32) * spec.scale
        scores = jnp.where(mask[:, None], scores, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum("bhcs,bsr->bhcr", p.astype(cache.dtype),
                           cache[..., :r], preferred_element_type=jnp.float32)
        out = jnp.einsum("bhcr,rhv->bchv", o_lat.astype(cache.dtype),
                         w[..., nope:], preferred_element_type=jnp.float32)
    return out.reshape(*out.shape[:2], -1).astype(q_nope.dtype)


def mla_decode(params: Params, x: jax.Array, cache: jax.Array,
               pos: jax.Array, spec: MLASpec):
    """One decode step against a dense per-slot row cache (B, L, cache_dim):
    write the new row at ``pos`` (scalar or (B,)), attend absorbed.
    Returns (output (B,1,D), cache)."""
    b = x.shape[0]
    pos = jnp.asarray(pos)
    positions = _decode_positions(pos, b)
    q_nope, q_pe = _mla_query(params, x, spec, positions)
    new = mla_rows(params, x, spec, positions)                # (B,1,R)
    if pos.ndim == 0:
        cache = jax.lax.dynamic_update_slice(cache, new, (0, pos, 0))
    else:
        cache = jax.vmap(
            lambda c, n, p: jax.lax.dynamic_update_slice(c, n, (p, 0))
        )(cache, new, pos)
    pos_b = jnp.broadcast_to(pos, (b,))
    mask = jnp.arange(cache.shape[1])[None, :] <= pos_b[:, None]
    out = _mla_absorbed(params, q_nope, q_pe, cache, mask[:, None], spec)
    return sod.apply(out, params["wo"]), cache


def mla_paged_decode(params: Params, x: jax.Array, pool: Params,
                     block_tables: jax.Array, pos: jax.Array, spec: MLASpec,
                     valid_len: jax.Array | None = None, layer=0):
    """One decode step against the latent page pool ``{"latent": (...,
    n_pages, page, cache_dim)}``: the new row is written through
    :func:`paged_update` (rows at or beyond ``valid_len`` go to the trash
    page), the row's pages are gathered back and attended absorbed.
    ``pool`` and ``layer`` are as in :func:`paged_kv`; the whole pool comes
    back."""
    b = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = _decode_positions(pos, b)
    q_nope, q_pe = _mla_query(params, x, spec, positions)
    new = mla_rows(params, x, spec, positions)[:, 0]          # (B,R)
    page_size = pool["latent"].shape[-2]
    page = jnp.take_along_axis(
        block_tables, (pos // page_size)[:, None], axis=1)[:, 0]
    if valid_len is not None:
        valid_len = jnp.asarray(valid_len, jnp.int32).reshape(b)
        page = jnp.where(pos < valid_len, page, 0)      # overflow → trash
    pool, cache = paged_update(pool, layer, page, pos % page_size,
                               {"latent": new}, block_tables)
    rows = cache["latent"]                                   # (B,L,P)
    mask = jnp.arange(rows.shape[1])[None, :] <= pos[:, None]
    out = _mla_absorbed(params, q_nope, q_pe, rows, mask[:, None], spec)
    return sod.apply(out, params["wo"]), pool
