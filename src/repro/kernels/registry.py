"""Kernel registry: every matmul implementation, with its capabilities.

The Sparse-on-Dense datapath has several realizations — the fused
decompress+matmul Pallas kernel, the VREG-block kernel with zero-macro-tile
skip, the differentiable jnp scatter oracle, the dense bypass — and which one
is fastest depends on the backend, the operand format, the problem shape and
the density.  Instead of a static if/else, each implementation registers
itself here with

  * a **capability predicate** (``supports``): which backends/formats/shapes
    it can run at all;
  * a **tunable-parameter space** (``param_space``): the (bm, slot_chunk,
    k_slab, …) grid the autotuner may sweep;
  * a **runner** that takes an un-padded 2-D ``x`` and the packed operand and
    owns its own padding/slicing.

:mod:`repro.kernels.autotune` consumes the registry to benchmark candidates
and persist the winners; :func:`repro.kernels.ops.sod_matmul` consults it at
trace time (pure Python on static shapes — never measures inside a trace).

Backends are the strings ``cpu`` / ``gpu`` / ``tpu`` / ``interpret``, where
``interpret`` means "TPU semantics emulated via the Pallas interpreter" — the
way the kernels run in CI and on developer machines without a TPU.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import os
import pathlib
from typing import Callable

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.formats import BlockCSR, TiledCSC, fp8_dtype

__all__ = [
    "KernelImpl",
    "ProblemKey",
    "register",
    "get_impl",
    "all_impls",
    "candidates",
    "choose",
    "problem_key",
    "format_of",
    "static_density",
    "current_backend",
    "set_backend_override",
    "unwrapped_may_be_sharded",
    "kernel_hash",
    "record_dispatches",
    "note_dispatch",
    "dispatch_summary",
    "dispatch_counts",
]

BACKENDS = ("cpu", "gpu", "tpu", "interpret")

# VMEM budget for the resident decompressed K-slab (bytes); beyond this the
# fused kernel must fall back to per-use decompression (k_slab=1).
VMEM_SLAB_BUDGET = 12 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class ProblemKey:
    """Static description of one matmul problem — everything the dispatcher
    may depend on at trace time (shapes/dtypes are static under jit; weight
    *values* are not, so density is a pack-time proxy, see
    :func:`static_density`)."""

    fmt: str                 # tiled_csc | block_csr | dense
    m: int
    k: int
    n: int
    density: float           # static proxy (cap/bk fill ratio), NOT data nnz
    dtype: str
    backend: str

    # format-specific static layout facts the param spaces need
    tile: tuple[int, int] = (128, 128)
    cap: int = 0             # TiledCSC slot capacity / BlockCSR bcap*br
    kt: int = 1              # K-tile grid size
    # value quantization mode of the packed operand (none|int8|fp8|codebook).
    # Distinct from dtype: int8 codes and codebook indices share the int8
    # storage dtype but need different dequant work in the kernel.
    qmode: str = "none"

    # Non-empty when dispatching *inside* the SPMD execution layer
    # (repro.runtime.spmd): a signature like "data=4,model=2|dp" naming the
    # mesh shape and partition plan.  Shapes in the key are then per-local-
    # shard, so tuned tiles are per-shard winners, and choose() knows the
    # Pallas impls are mesh-legal (shard_map gives them per-device traces).
    mesh: str = ""


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """One registered implementation."""

    name: str
    formats: tuple[str, ...]
    backends: tuple[str, ...]
    differentiable: bool
    # True when the impl is legal inside a pjit-sharded model step on a
    # mesh — either natively (plain jnp ops XLA/GSPMD can partition;
    # ``mesh_axes == ()``) or via the shard_map wrappers in
    # :mod:`repro.runtime.spmd` (``mesh_axes`` names the axis roles the
    # wrapper supports).  pallas_call still has no GSPMD partitioning rule,
    # so a Pallas impl traced *directly* under pjit (dispatch with an empty
    # ``ProblemKey.mesh`` in a multi-device process) remains off-limits on
    # a cold TPU cache — see unwrapped_may_be_sharded().
    spmd_partitionable: bool
    priority: int            # tie-break when the prior can't separate
    param_space: Callable[[ProblemKey], dict[str, tuple]]
    run: Callable[..., jax.Array]   # run(x2, w, out_dtype=?, backend=?, **params)
    # maps requested params to what the runner will actually execute for a
    # concrete M (bm clamping, slot_chunk sanitizing, k_slab residency) —
    # the autotuner dedups trials on this so it never measures the same
    # effective kernel twice; None = params are already canonical
    canonicalize: Callable[[ProblemKey, dict, int], dict] | None = None
    # mesh-axis *roles* the SPMD layer may shard this impl over inside its
    # shard_map wrapper ("data" = M-sharding, "model" = N/K tensor
    # parallelism).  Empty = natively partitionable, no wrapper needed.
    mesh_axes: tuple[str, ...] = ()
    # value-quantization modes this impl can dequantize (capability
    # predicate for the qmode axis; fp8 is additionally gated on the jax
    # build actually providing an fp8 dtype — see supports()).
    qmodes: tuple[str, ...] = ("none", "int8", "fp8", "codebook")

    @property
    def requires_shard_map(self) -> bool:
        """Mesh-legal only through the repro.runtime.spmd wrapper."""
        return self.spmd_partitionable and bool(self.mesh_axes)

    def supports(self, key: ProblemKey) -> bool:
        """Whether this impl can run the problem (format, backend, and the
        operand's value-quantization mode)."""
        if key.fmt not in self.formats or key.backend not in self.backends:
            return False
        if key.qmode not in self.qmodes:
            return False
        if key.qmode == "fp8" and fp8_dtype() is None:
            return False
        return True

    def canonical_params(self, key: ProblemKey, params: dict, m: int) -> dict:
        """Params as the runner will actually execute them for concrete
        ``m`` (clamping/sanitizing via ``canonicalize`` when defined) —
        the autotuner dedups trials on this."""
        if self.canonicalize is None:
            return dict(params)
        return self.canonicalize(key, params, m)

    def default_params(self, key: ProblemKey) -> dict:
        """First element of every axis of the param space = the hard-coded
        defaults the seed shipped with (kept first on purpose, so the tuner
        always measures the status quo as one of its candidates)."""
        return {k: v[0] for k, v in self.param_space(key).items()}

    def param_grid(self, key: ProblemKey) -> list[dict]:
        """Cartesian product of the impl's param space — the autotuner's
        candidate list for this problem."""
        space = self.param_space(key)
        grid: list[dict] = [{}]
        for name, values in space.items():
            grid = [dict(g, **{name: v}) for g in grid for v in values]
        return grid


_REGISTRY: dict[str, KernelImpl] = {}
_BACKEND_OVERRIDE: str | None = None


def register(impl: KernelImpl) -> KernelImpl:
    """Add an impl to the global registry (returns it, decorator-style)."""
    _REGISTRY[impl.name] = impl
    return impl


def get_impl(name: str) -> KernelImpl:
    """Look up a registered impl by name; KeyError lists what exists."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no kernel impl {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def all_impls() -> dict[str, KernelImpl]:
    """Snapshot of the registry (name → impl)."""
    return dict(_REGISTRY)


def candidates(key: ProblemKey) -> list[KernelImpl]:
    """All implementations able to run this problem, best-priority first."""
    out = [i for i in _REGISTRY.values() if i.supports(key)]
    return sorted(out, key=lambda i: -i.priority)


def current_backend() -> str:
    """Dispatch backend: override > env REPRO_SOD_BACKEND > jax backend."""
    if _BACKEND_OVERRIDE is not None:
        return _BACKEND_OVERRIDE
    env = os.environ.get("REPRO_SOD_BACKEND")
    if env:
        return env
    return jax.default_backend()


def set_backend_override(backend: str | None) -> None:
    """Force the dispatch backend (tests / launch flags).  None resets."""
    global _BACKEND_OVERRIDE
    if backend is not None and backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; want one of {BACKENDS}")
    _BACKEND_OVERRIDE = backend


def format_of(w) -> str:
    """Operand's packed format name: tiled_csc, block_csr, or dense."""
    if isinstance(w, TiledCSC):
        return "tiled_csc"
    if isinstance(w, BlockCSR):
        return "block_csr"
    return "dense"


def static_density(w) -> float:
    """Trace-safe density proxy from the packed container's static layout.

    For TiledCSC the per-column slot capacity bounds the fill; for BlockCSR
    the block capacity does.  Dense is 1.0.  Rounded to 1/32 so nearby packs
    share a tuning-cache entry.
    """
    if isinstance(w, TiledCSC):
        d = min(w.cap / w.tile[0], 1.0)
    elif isinstance(w, BlockCSR):
        d = min(w.bcap * w.br / w.tile[0], 1.0)
    else:
        return 1.0
    return round(d * 32) / 32


def _m_bucket(m: int) -> int:
    """Bucket M to the next power of two (≥8) so decode (m≈1) and prefill
    (m≈batch·seq) tune separately but nearby batch sizes share entries."""
    b = 8
    while b < m:
        b *= 2
    return b


def problem_key(w, m: int, backend: str | None = None,
                mesh: str = "") -> ProblemKey:
    """The dispatch/tuning identity of one packed matmul: operand layout
    (format, K/N, static density, dtype) × bucketed M × backend × mesh
    signature.  Everything the cache keys on, nothing value-dependent."""
    fmt = format_of(w)
    backend = backend or current_backend()
    if fmt == "dense":
        k, n = int(w.shape[-2]), int(w.shape[-1])
        return ProblemKey(fmt, _m_bucket(m), k, n, 1.0,
                          str(jnp.result_type(w)), backend, mesh=mesh)
    k, n = w.shape
    if fmt == "tiled_csc":
        cap, kt = w.cap, w.grid[0]
    else:
        cap, kt = w.bcap * w.br, w.grid[0]
    return ProblemKey(
        fmt, _m_bucket(m), int(k), int(n), static_density(w),
        str(jnp.dtype(w.dtype)), backend,
        tile=tuple(w.tile), cap=int(cap), kt=int(kt),
        qmode=getattr(w, "qmode", "none"), mesh=mesh,
    )


def unwrapped_may_be_sharded(key: ProblemKey) -> bool:
    """Whether a TPU dispatch could sit inside a GSPMD-sharded step.

    ``pallas_call`` has no GSPMD partitioning rule, so a Pallas kernel traced
    directly into a pjit step whose weights are sharded across chips would
    not partition.  Inside the :mod:`repro.runtime.spmd` shard_map wrapper
    the key carries a mesh signature and each device traces its own shard,
    so that is safe; so is a process that sees one device, where nothing can
    be sharded.  What remains is an unwrapped TPU dispatch in a process that
    sees several devices.
    """
    return key.backend == "tpu" and not key.mesh and jax.device_count() > 1


def choose(key: ProblemKey, tuned: dict | None = None
           ) -> tuple[KernelImpl, dict]:
    """Resolve (impl, params) for a problem.

    ``tuned`` is an autotune cache entry ``{"impl": ..., "params": ...}``;
    when absent (cold cache inside a trace — we never measure there) the
    impl and params the cost-model prior in :mod:`autotune` ranks cheapest
    run, restricted to natively partitionable impls where
    :func:`unwrapped_may_be_sharded` says a Pallas kernel could be traced
    under GSPMD.  Explicitly tuned entries may still promote the Pallas
    kernels there (tuning runs per-host, outside pjit, so the operator
    opted in knowingly).
    """
    if tuned is not None:
        impl = _REGISTRY.get(tuned.get("impl", ""))
        if impl is not None and impl.supports(key):
            params = dict(impl.default_params(key))
            params.update(tuned.get("params") or {})
            note_dispatch(key, impl, params, "tuned")
            return impl, params
    # cold cache: cheapest candidate under the analytical prior (deferred
    # import — autotune imports this module at top level).
    from repro.kernels import autotune

    ranked = autotune.rank_candidates(key)
    if unwrapped_may_be_sharded(key):
        safe = [t for t in ranked
                if t[1].spmd_partitionable and not t[1].requires_shard_map]
        ranked = safe or ranked
    if not ranked:
        raise ValueError(f"no kernel impl supports {key}")
    _, impl, params = ranked[0]
    note_dispatch(key, impl, params, "prior")
    return impl, params


# ---------------------------------------------------------------------------
# dispatch observability: what actually ran?
# ---------------------------------------------------------------------------
# Dispatch happens at trace time (pure Python), so a recording context
# wrapped around a jit/lower call captures every registry resolution the
# traced computation made — this is how the launch drivers and demos report
# which impl a mesh step really used instead of silently falling back.
_DISPATCH_LOGS: list[list] = []


@contextlib.contextmanager
def record_dispatches(log: list | None = None):
    """Collect ``{"key", "impl", "params", "source"}`` dicts for every
    dispatch resolved while the context is active (source is ``tuned`` /
    ``prior`` / ``forced``)."""
    log = [] if log is None else log
    _DISPATCH_LOGS.append(log)
    try:
        yield log
    finally:
        # identity, not equality: content-equal nested logs must not
        # remove each other
        for i, entry in enumerate(_DISPATCH_LOGS):
            if entry is log:
                del _DISPATCH_LOGS[i]
                break


def note_dispatch(key: ProblemKey, impl: KernelImpl, params: dict,
                  source: str) -> None:
    """Record one dispatch decision into every active
    :func:`log_dispatches` capture (no-op outside any) and, when tracing
    is on, emit it as an instant event on the ``kernels`` trace track so
    tuned-vs-prior dispatches are visible on the timeline."""
    for log in _DISPATCH_LOGS:
        log.append({"key": key, "impl": impl.name, "params": dict(params),
                    "source": source})
    tr = obs.get_tracer()
    if tr.enabled:
        tr.instant(f"{impl.name}[{source}]", track="kernels", cat="dispatch",
                   fmt=key.fmt, m=key.m, k=key.k, n=key.n,
                   backend=key.backend, source=source)


def amend_last_dispatch(key: ProblemKey, impl: KernelImpl,
                        params: dict) -> None:
    """Rewrite the params of the dispatch just noted — callers that apply
    overrides on top of the chosen params (ops.resolve) use this so the
    recorded entry shows what actually ran."""
    for log in _DISPATCH_LOGS:
        if log and log[-1]["key"] == key and log[-1]["impl"] == impl.name:
            log[-1]["params"] = dict(params)


def dispatch_summary(log: list) -> list[str]:
    """Human-readable one-liners, deduplicated, for a recorded log."""
    seen: dict[str, int] = {}
    lines: list[str] = []
    for rec in log:
        k = rec["key"]
        desc = (f"{rec['impl']}[{rec['source']}] "
                f"{k.fmt} m={k.m} k={k.k} n={k.n} {k.backend}"
                + (f" mesh={k.mesh}" if k.mesh else "")
                + (f" params={rec['params']}" if rec["params"] else ""))
        if desc not in seen:
            seen[desc] = len(lines)
            lines.append(desc)
    return lines


def dispatch_counts(log: list) -> dict[str, int]:
    """Dispatch totals per ``impl[source]`` for a recorded log — the
    compact tuned-cache-coverage view the serve/dryrun reports surface
    (e.g. ``{"pallas_fused[tuned]": 12, "dense[prior]": 2}``)."""
    out: dict[str, int] = {}
    for rec in log:
        k = f"{rec['impl']}[{rec['source']}]"
        out[k] = out.get(k, 0) + 1
    return out


def kernel_hash() -> str:
    """Short content hash over the kernel sources — versions the tuning
    cache: edit any kernel and every persisted measurement is invalidated."""
    h = hashlib.sha256()
    pkg = pathlib.Path(__file__).parent
    for p in sorted(pkg.glob("*.py")):
        h.update(p.name.encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# built-in implementations
# ---------------------------------------------------------------------------
def _sanitize_slot_chunk(cap: int, slot_chunk: int) -> int:
    """Largest slot chunk at most the request (at least 8) that divides
    ``cap`` and is a multiple of 8: the kernel loads each chunk of slots at
    offset ``c * slot_chunk``, and Mosaic accepts the load only where it can
    prove that offset sublane (8) aligned.  A ``cap`` that is not a
    multiple of 8 (interpreter only) takes its largest divisor."""
    for c in range(min(max(slot_chunk, 8), cap) // 8 * 8, 0, -8):
        if cap % c == 0:
            return c
    slot_chunk = max(min(slot_chunk, cap), 1)
    while cap % slot_chunk:
        slot_chunk -= 1
    return slot_chunk


def _dtype_name(out_dtype) -> str | None:
    return jnp.dtype(out_dtype).name if out_dtype is not None else None


def _interpret(backend: str | None) -> bool:
    """Pallas kernels compile for the chip on the ``tpu`` backend and run
    in the interpreter on every other (``None`` = the current backend)."""
    return (backend or current_backend()) != "tpu"


def _run_pallas_fused(x2, w, *, out_dtype=None, backend=None,
                      bm=128, slot_chunk=8, k_slab=0):
    from repro.kernels import vjp

    fn = vjp.fused_matmul(
        vjp.pick_bm(x2.shape[0], bm),
        _sanitize_slot_chunk(w.cap, slot_chunk),
        k_slab,
        _interpret(backend),
        _dtype_name(out_dtype),
    )
    return fn(x2, w)


def _run_pallas_block(x2, w, *, out_dtype=None, backend=None, bm=128):
    from repro.kernels import vjp

    fn = vjp.block_matmul(
        vjp.pick_bm(x2.shape[0], bm), _interpret(backend),
        _dtype_name(out_dtype)
    )
    return fn(x2, w)


_JITTED: dict[str, Callable] = {}


def _jitted_ref(name: str) -> Callable:
    # jit once per oracle so registry-run calls (and the autotuner's
    # measurements) see compiled-dispatch cost, same as the pallas wrappers
    if not _JITTED:
        from repro.kernels import ref

        for n, fn in (("tiled", ref.sod_matmul_ref),
                      ("block", ref.block_matmul_ref),
                      ("dense", ref.dense_matmul_ref)):
            _JITTED[n] = jax.jit(fn, static_argnames=("out_dtype",))
    return _JITTED[name]


def _run_jnp_oracle(x2, w, *, out_dtype=None, backend=None):
    fn = _jitted_ref("tiled" if isinstance(w, TiledCSC) else "block")
    return fn(x2, w, out_dtype=out_dtype)


def _run_dense(x2, w, *, out_dtype=None, backend=None):
    return _jitted_ref("dense")(x2, w, out_dtype=out_dtype)


def _bm_axis(key: ProblemKey) -> tuple[int, ...]:
    opts = [128] + [b for b in (256, 64, 32, 16, 8) if b <= max(key.m, 8)]
    return tuple(dict.fromkeys(opts))  # keep order, drop dups


def _fused_space(key: ProblemKey) -> dict[str, tuple]:
    # k_slab: 0 = fully resident K-slab (the seed's hard-coded behaviour,
    # kept first = default); 1 = re-decompress per use (minimal VMEM).  A
    # resident slab larger than the VMEM budget is not offered at all.
    # The slab scratch is allocated in the *activation* dtype, which can be
    # wider than the packed weights — budget for f32 worst case.
    bk, bn = key.tile
    itemsize = max(jnp.dtype(key.dtype).itemsize, 4)
    slab_bytes = key.kt * bk * bn * itemsize
    k_slab = (0, 1) if slab_bytes <= VMEM_SLAB_BUDGET else (1,)
    chunks = tuple(c for c in (8, 4, 16) if c <= key.cap)
    return {
        "bm": _bm_axis(key),
        "slot_chunk": chunks or (1,),
        "k_slab": k_slab,
    }


def _block_space(key: ProblemKey) -> dict[str, tuple]:
    return {"bm": _bm_axis(key)}


def _fused_canonical(key: ProblemKey, params: dict, m: int) -> dict:
    from repro.kernels import vjp

    k_slab = params.get("k_slab", 0)
    if k_slab <= 0 or k_slab >= key.kt:
        k_slab = 0               # fully resident, however it was spelled
    return {
        "bm": vjp.pick_bm(m, params.get("bm", 128)),
        "slot_chunk": _sanitize_slot_chunk(key.cap,
                                           params.get("slot_chunk", 8)),
        "k_slab": k_slab,
    }


def _block_canonical(key: ProblemKey, params: dict, m: int) -> dict:
    from repro.kernels import vjp

    return {"bm": vjp.pick_bm(m, params.get("bm", 128))}


# The pallas impls list "cpu" too: they run there through the interpreter,
# which the autotuner's prior penalizes heavily — so a cold cache on CPU
# still dispatches to the jnp oracle, but *measurement* may promote the
# interpreted kernel where it genuinely wins (e.g. block-skip at high
# zero-tile fractions).
# mesh-legal via the repro.runtime.spmd shard_map wrappers ("data" =
# M-sharding / compressed FSDP gather, "model" = column/row tensor
# parallelism); dispatch outside the wrapper in a multi-device process still
# treats them as unpartitionable — see unwrapped_may_be_sharded().
register(KernelImpl(
    name="pallas_fused",
    formats=("tiled_csc",),
    backends=("tpu", "interpret", "cpu"),
    differentiable=True,   # custom VJP in kernels/vjp.py
    spmd_partitionable=True,
    priority=30,
    param_space=_fused_space,
    run=_run_pallas_fused,
    canonicalize=_fused_canonical,
    mesh_axes=("data", "model"),
))

register(KernelImpl(
    name="pallas_block",
    formats=("block_csr",),
    backends=("tpu", "interpret", "cpu"),
    differentiable=True,   # custom VJP in kernels/vjp.py
    spmd_partitionable=True,
    priority=30,
    param_space=_block_space,
    run=_run_pallas_block,
    canonicalize=_block_canonical,
    mesh_axes=("data", "model"),
))

register(KernelImpl(
    name="jnp_oracle",
    formats=("tiled_csc", "block_csr"),
    backends=("cpu", "gpu", "tpu"),
    differentiable=True,
    spmd_partitionable=True,
    priority=20,
    param_space=lambda key: {},
    run=_run_jnp_oracle,
))

register(KernelImpl(
    name="dense_ref",
    formats=("dense",),
    backends=BACKENDS,
    differentiable=True,
    spmd_partitionable=True,
    priority=10,
    param_space=lambda key: {},
    run=_run_dense,
))
