"""Autotuner for the kernel registry, with a persistent tuning cache.

Tuning happens in two stages, mirroring how the paper's design-space sweeps
work (tiling/buffering sweeps in SCNN/EIE): an **analytical prior** from the
same traffic model as :mod:`repro.core.cost_model` ranks every (impl, params)
candidate for a problem, then the top few are **measured** and the winner is
persisted.  Dispatch at trace time (inside ``jit``) only ever *reads* the
cache — measurement is strictly an outside-of-trace operation driven by
:func:`tune` / :func:`warmup_params` (the launch scripts' ``--autotune``).

Cache file format (JSON, one file per machine):

.. code-block:: json

    {
      "version": 1,
      "kernel_hash": "<sha256 prefix over src/repro/kernels/*.py>",
      "entries": {
        "tiled_csc|m=128|k=512|n=512|d=0.312|f32|interpret": {
          "impl": "pallas_fused",
          "params": {"bm": 128, "slot_chunk": 8, "k_slab": 0},
          "us": 1234.5,
          "source": "measured"
        }
      }
    }

The file lives at ``~/.cache/repro/tuning_cache.json`` unless the
``REPRO_TUNING_CACHE`` environment variable points elsewhere.  Editing any
kernel source changes ``kernel_hash`` and invalidates every entry; the
backend is part of each entry key, so one cache file serves CPU and TPU runs
of the same checkout.
"""
from __future__ import annotations

import json
import os
import pathlib
import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.plan import QVALUE_BITS
from repro.core.topology import chip_for_kind
from repro.kernels import registry
from repro.kernels.registry import KernelImpl, ProblemKey

__all__ = [
    "TuningCache",
    "default_cache_path",
    "get_cache",
    "set_cache",
    "key_str",
    "predict_us",
    "rank_candidates",
    "tune",
    "lookup",
    "warmup_params",
]

CACHE_VERSION = 1

# crude throughput constants for the prior off the TPU (the prior only
# needs to *order* candidates; measurement fixes the magnitudes).  TPU keys
# take the chip's published peaks from core.topology instead.
_PEAK_FLOPS = {"cpu": 5e10, "gpu": 1e13, "interpret": 5e10}
_MEM_BW = {"cpu": 2e10, "gpu": 1e12, "interpret": 2e10}
# the Pallas interpreter executes the kernel body in Python per grid step —
# orders of magnitude slower than compiled jnp; the prior must know that so
# a cold cache on CPU never routes the hot path through the interpreter.
_INTERPRET_OVERHEAD_US_PER_STEP = 300.0


def default_cache_path() -> pathlib.Path:
    """Cache file location: $REPRO_TUNING_CACHE, else the user cache dir."""
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env:
        return pathlib.Path(env).expanduser()
    return pathlib.Path("~/.cache/repro/tuning_cache.json").expanduser()


def tpu_device_kind() -> str:
    """``device_kind`` of the TPU this process drives (ranking a TPU key
    needs the chip's peaks, and there is no default chip)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"ranking a TPU problem key needs a TPU device; the default "
            f"device is {dev.platform!r}")
    return dev.device_kind


def _peaks(backend: str) -> tuple[float, float]:
    """(peak FLOP/s, memory bytes/s) the prior assumes for a backend."""
    if backend == "tpu":
        chip = chip_for_kind(tpu_device_kind())
        return chip.peak_bf16_flops, chip.hbm_bandwidth
    return _PEAK_FLOPS.get(backend, 5e10), _MEM_BW.get(backend, 2e10)


def key_str(key: ProblemKey) -> str:
    """Stable string form of a :class:`ProblemKey` — the cache-entry key."""
    # tile/cap are part of the key: two packs of the same logical (K, N)
    # with different tile geometry have different param spaces and winners,
    # and must not collide on one cache entry.  The mesh signature is
    # appended only when set (SPMD dispatch): shapes are then per-local-
    # shard, and a tile tuned for the (m/dp, k, n/tp) shard must not be
    # served to an unsharded run of the same global shape (or to a
    # different mesh).
    d = f"{key.density:.3f}"
    bk, bn = key.tile
    s = (f"{key.fmt}|m={key.m}|k={key.k}|n={key.n}|d={d}"
         f"|t={bk}x{bn}|cap={key.cap}|{key.dtype}|{key.backend}")
    if key.qmode != "none":
        # appended only when quantized: pre-qmode cache entries stay valid,
        # and int8 codes vs codebook indices (same int8 dtype, different
        # dequant inner loop) cannot collide on one entry
        s += f"|q={key.qmode}"
    if key.mesh:
        s += f"|mesh={key.mesh}"
    return s


class TuningCache:
    """Persistent (impl, params) winners, versioned by the kernel sources."""

    def __init__(self, path: pathlib.Path | str | None = None):
        self.path = pathlib.Path(path) if path else default_cache_path()
        self.kernel_hash = registry.kernel_hash()
        self.entries: dict[str, dict] = {}
        self._load()

    def _load(self) -> None:
        try:
            raw = json.loads(self.path.read_text())
        except (OSError, ValueError):
            return
        if (raw.get("version") != CACHE_VERSION
                or raw.get("kernel_hash") != self.kernel_hash):
            return  # stale: kernels changed since these were measured
        entries = raw.get("entries")
        if isinstance(entries, dict):
            self.entries = entries

    def save(self) -> None:
        """Atomically persist entries (tmp-file + rename), stamped with the
        kernel-source hash so stale measurements self-invalidate."""
        self.path.parent.mkdir(parents=True, exist_ok=True)
        payload = {
            "version": CACHE_VERSION,
            "kernel_hash": self.kernel_hash,
            "entries": self.entries,
        }
        tmp = self.path.with_suffix(".tmp")
        tmp.write_text(json.dumps(payload, indent=1, sort_keys=True))
        tmp.replace(self.path)

    def get(self, key: ProblemKey) -> dict | None:
        """Cached winner for the problem, or None on a cold key."""
        return self.entries.get(key_str(key))

    def put(self, key: ProblemKey, impl: str, params: dict, us: float,
            source: str = "measured") -> None:
        """Record a winner (impl + params + measured microseconds) for the
        problem; ``source`` distinguishes measured from prior-seeded."""
        self.entries[key_str(key)] = {
            "impl": impl, "params": params, "us": us, "source": source,
        }

    def __len__(self) -> int:
        return len(self.entries)


_CACHE: TuningCache | None = None
_CACHE_PINNED = False       # set_cache() pins; env changes then can't evict


def get_cache() -> TuningCache:
    """Process-wide cache singleton (lazy; honours REPRO_TUNING_CACHE).

    A cache installed with :func:`set_cache` (e.g. the launch scripts'
    ``--tuning-cache``) is pinned: it keeps serving dispatch lookups even
    though its path differs from the env default.
    """
    global _CACHE
    if _CACHE is None or (not _CACHE_PINNED
                          and _CACHE.path != default_cache_path()):
        _CACHE = TuningCache()
    return _CACHE


def set_cache(cache: TuningCache | None) -> None:
    """Install (and pin) the process-wide cache; None unpins and reverts
    to the env-default path on next :func:`get_cache`."""
    global _CACHE, _CACHE_PINNED
    _CACHE = cache
    _CACHE_PINNED = cache is not None


def install_cache(path: str | pathlib.Path | None) -> TuningCache:
    """Resolve a cache for an explicit ``--tuning-cache`` argument.

    With a path: load that cache and pin it as the process-wide cache so
    *dispatch* reads the same file the caller tunes into.  Without: the
    default singleton.  One helper so every CLI (serve/train/bench) shares
    the pinning semantics.
    """
    if path:
        cache = TuningCache(path)
        set_cache(cache)
        return cache
    return get_cache()


def lookup(key: ProblemKey) -> dict | None:
    """Trace-safe cache read used by the dispatcher."""
    return get_cache().get(key)


# ---------------------------------------------------------------------------
# analytical prior
# ---------------------------------------------------------------------------
def predict_us(key: ProblemKey, impl: KernelImpl, params: dict) -> float:
    """Cost-model-style prediction of one candidate's runtime (µs).

    Same traffic reasoning as :mod:`repro.core.cost_model`: compute term =
    dense FLOPs at peak, memory term = bytes moved at peak bandwidth, where
    packed operands move ≈1.5·density of their dense bytes (16-bit value +
    8-bit index) and a non-resident K-slab (k_slab > 0 and < Kt) pays its
    decompression once per M-block instead of once.
    """
    m, k, n = key.m, key.k, key.n
    itemsize = jnp.dtype(key.dtype).itemsize
    flops = 2.0 * m * k * n
    x_bytes = m * k * itemsize
    out_bytes = m * n * itemsize
    dense_w_bytes = k * n * itemsize

    backend = key.backend
    peak, bw = _peaks(backend)

    if impl.name == "jnp_oracle":
        # scatter-decompress materializes the dense matrix, then a dense dot
        w_bytes = dense_w_bytes * 2          # write dense + read it back
        decompress_flops = key.density * k * n * 4
        us = max(flops / peak, (x_bytes + w_bytes + out_bytes) / bw) * 1e6
        us += decompress_flops / peak * 1e6
        return us

    if impl.name == "dense_ref":
        return max(flops / peak,
                   (x_bytes + dense_w_bytes + out_bytes) / bw) * 1e6

    # pallas impls: compressed traffic.  Value bytes per slot follow the
    # qmode (16-bit unquantized, 8-bit int8/fp8, 4-bit codebook index) over
    # a 1-byte row index and 2-byte dense elements — 1.5·density unquantized,
    # less when the values are stored quantized.
    vbytes = QVALUE_BITS.get(key.qmode, 16) / 8.0
    w_bytes = key.density * dense_w_bytes * ((vbytes + 1.0) / 2.0)
    bm = params.get("bm", 128)
    mt = max(-(-m // max(bm, 1)), 1)
    bk, bn = key.tile
    decomp_elems = key.kt * (n / bn) * key.cap * bn   # slots touched once
    slot_chunk = max(params.get("slot_chunk", 8), 1)
    decomp_cost = decomp_elems * (1.0 + 8.0 / slot_chunk)  # loop overhead
    if key.qmode == "codebook":
        decomp_cost *= 2.0   # compare-select over the shared-value table
    k_slab = params.get("k_slab", 0)
    if 0 < k_slab < key.kt:
        decomp_cost *= mt                    # re-decompress per M-block
    us = max(flops / peak, (x_bytes + w_bytes + out_bytes) / bw) * 1e6
    us += decomp_cost / peak * 1e6
    if backend != "tpu":
        # off-TPU the pallas kernels run through the interpreter
        grid_steps = mt * key.kt * max(-(-n // bn), 1)
        us += grid_steps * _INTERPRET_OVERHEAD_US_PER_STEP
    return us


def rank_candidates(key: ProblemKey) -> list[tuple[float, KernelImpl, dict]]:
    """All capable (impl, params) candidates, cheapest-predicted first."""
    out = []
    for impl in registry.candidates(key):
        for params in impl.param_grid(key):
            out.append((predict_us(key, impl, params), impl, params))
    out.sort(key=lambda t: (t[0], -t[1].priority))
    return out


# ---------------------------------------------------------------------------
# measurement
# ---------------------------------------------------------------------------
def _measure(fn: Callable[[], jax.Array], iters: int = 3) -> float:
    jax.block_until_ready(fn())          # compile
    jax.block_until_ready(fn())          # warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6                    # min-of-N: robust to host noise


def tune(
    x: jax.Array,
    w,
    *,
    backend: str | None = None,
    mesh: str = "",
    cache: TuningCache | None = None,
    top_k: int = 4,
    iters: int = 3,
    measure_fn: Callable | None = None,
    force: bool = False,
    trials_out: list | None = None,
) -> dict:
    """Measure the best candidates for ``x @ w`` and persist the winner.

    ``x`` must be a concrete 2-D array (never call this inside ``jit``).
    Returns the cache entry.  A warm cache returns immediately without
    measuring unless ``force``; ``measure_fn(fn) -> us`` is injectable for
    tests; when ``trials_out`` is a list it receives every measured
    ``(impl_name, params, us)`` (the benchmark sweep reads the default
    config's time out of it — same measurement session as the winner's).

    ``mesh`` is an SPMD mesh signature (:func:`repro.runtime.spmd.mesh_key`
    + plan): ``x``/``w`` must then be the per-device *local* shard shapes —
    single-device measurement of the local problem is exactly what the
    shard_map body will execute per chip — and the entry lands under the
    mesh-qualified cache key the SPMD dispatcher reads.
    """
    cache = get_cache() if cache is None else cache
    key = registry.problem_key(w, m=x.shape[0], backend=backend, mesh=mesh)
    hit = cache.get(key)
    if hit is not None and not force:
        return hit
    measure = measure_fn or (lambda fn: _measure(fn, iters=iters))
    ranked = rank_candidates(key)
    if not ranked:
        raise ValueError(f"no kernel impl supports {key}")
    # prior top-k, plus every capable impl's default params — the status quo
    # is always measured, so a tuned choice can never lose to it silently.
    # Trials are deduplicated (and persisted) on *canonical* params: what
    # the runner will actually execute for this M (bm clamping, slot_chunk
    # sanitizing, k_slab residency), so the same effective kernel is never
    # measured twice and the cache records what really ran.
    m = x.shape[0]
    trials: list[tuple[KernelImpl, dict]] = []
    seen: set = set()
    for _, impl, params in ranked[:max(top_k, 1)]:
        canon = impl.canonical_params(key, params, m)
        sig = (impl.name, tuple(sorted(canon.items())))
        if sig not in seen:
            trials.append((impl, canon))
            seen.add(sig)
    for impl in registry.candidates(key):
        canon = impl.canonical_params(key, impl.default_params(key), m)
        sig = (impl.name, tuple(sorted(canon.items())))
        if sig not in seen:
            trials.append((impl, canon))
            seen.add(sig)
    best: tuple[float, KernelImpl, dict] | None = None
    tracer = obs.get_tracer()
    for impl, params in trials:
        with tracer.span(f"measure:{impl.name}", track="autotune",
                         key=key_str(key), params=str(params)):
            us = float(measure(
                lambda impl=impl, params=params: impl.run(
                    x, w, backend=key.backend, **params)
            ))
        if trials_out is not None:
            trials_out.append((impl.name, dict(params), us))
        if best is None or us < best[0]:
            best = (us, impl, params)
    us, impl, params = best
    cache.put(key, impl.name, params, us)
    cache.save()
    return cache.get(key)


# ---------------------------------------------------------------------------
# model-level warmup (what launch --autotune calls)
# ---------------------------------------------------------------------------
def warmup_params(
    params,
    m_values: tuple[int, ...],
    *,
    backend: str | None = None,
    cache: TuningCache | None = None,
    iters: int = 1,
    seed: int = 0,
) -> dict:
    """Tune every distinct packed-weight shape in a param pytree.

    Walks the tree, collects unique (format, K, N, cap, dtype) layouts —
    stacked layers/experts share one entry per layout — and tunes each at
    every requested M.  Returns ``{"tuned": n_measured, "cached": n_hits}``.
    """
    from repro.core.formats import BlockCSR, TiledCSC

    cache = get_cache() if cache is None else cache
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda l: isinstance(l, (TiledCSC, BlockCSR)))
    seen: dict[tuple, object] = {}
    for leaf in leaves:
        def _slice0(a, tail):
            # first per-matrix slice of a stacked side array (None stays None)
            return None if a is None else a.reshape((-1,) + a.shape[-tail:])[0]

        if isinstance(leaf, TiledCSC):
            if leaf.lead:
                # Stacked (scan/expert) layouts: the model's scan body
                # slices lead dims off before sod.apply (lax.scan slicing +
                # tree_map(t[j])), so dispatch sees the per-layer slice —
                # tune that slice and the keys line up exactly.
                leaf = TiledCSC(_slice0(leaf.vals, 4), _slice0(leaf.rows, 4),
                                leaf.shape, leaf.tile,
                                scale=_slice0(leaf.scale, 2),
                                codebook=_slice0(leaf.codebook, 1),
                                qmode=leaf.qmode)
            sig = ("tiled_csc", leaf.shape, leaf.cap, str(leaf.dtype),
                   leaf.tile, leaf.qmode)
        elif isinstance(leaf, BlockCSR):
            if leaf.lead:
                leaf = BlockCSR(_slice0(leaf.block_vals, 5),
                                _slice0(leaf.block_ids, 3),
                                _slice0(leaf.tile_nnz, 2),
                                leaf.shape, leaf.tile, leaf.br,
                                scale=_slice0(leaf.scale, 2),
                                codebook=_slice0(leaf.codebook, 1),
                                qmode=leaf.qmode)
            sig = ("block_csr", leaf.shape, leaf.bcap, str(leaf.dtype),
                   leaf.tile, leaf.br, leaf.qmode)
        else:
            continue
        seen.setdefault(sig, leaf)

    stats = {"tuned": 0, "cached": 0}
    key_rng = jax.random.PRNGKey(seed)
    for sig, leaf in seen.items():
        for m in dict.fromkeys(int(v) for v in m_values):
            pk = registry.problem_key(leaf, m=m, backend=backend)
            if cache.get(pk) is not None:
                stats["cached"] += 1
                continue
            x = jax.random.normal(
                jax.random.fold_in(key_rng, hash(sig) % (2**31) + m),
                (m, leaf.shape[0]), jnp.float32,
            ).astype(leaf.dtype if jnp.issubdtype(
                jnp.dtype(leaf.dtype), jnp.floating) else jnp.float32)
            tune(x, leaf, backend=backend, cache=cache, iters=iters)
            stats["tuned"] += 1
    return stats
