"""Public jit'd wrappers around the Sparse-on-Dense kernels.

These handle arbitrary leading batch dims and the dense bypass (paper
Fig. 2c): a plain dense array flows straight to ``jnp.dot`` with no
decompression, exactly as dense-format data bypasses the decompression unit
in the paper.  Implementation choice and tile parameters come from the
kernel registry (:mod:`repro.kernels.registry`) consulted with the
autotuner's persisted winners (:mod:`repro.kernels.autotune`):

* ``impl="auto"``   — registry dispatch: tuned entry if the tuning cache has
  one for this (format, shape, density, backend, mesh), else the cost-model-
  prior default.  On CPU this is the differentiable jnp oracle; on one TPU
  (or under ``backend="interpret"``) the fused Pallas kernel.
* ``impl="pallas"`` — force the Pallas kernel (interpret mode off-TPU).
* ``impl="jnp"``    — force the jnp scatter oracle.

When a jax mesh is active (``with mesh:`` around the jit'd model step) and
the operand is packed, dispatch routes through the SPMD execution layer
(:mod:`repro.runtime.spmd`): the chosen impl runs *inside* a ``shard_map``
whose per-device body is single-device code, which is what makes the Pallas
kernels legal in pjit-sharded steps (``pallas_call`` has no GSPMD
partitioning rule).  ``spmd=None`` opts a call site out (the SPMD layer's
own shard_map bodies do this); ``REPRO_SPMD=0`` disables the routing
process-wide.

Dispatch is pure Python over static shapes, so it is trace-safe; nothing is
ever measured inside ``jit`` (run :func:`repro.kernels.autotune.tune` or the
launch scripts' ``--autotune`` to populate the cache).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core.formats import BlockCSR, TiledCSC
from repro.kernels import registry
from repro.kernels.decompress import decompress_pallas

__all__ = ["sod_matmul", "decompress", "resolve"]

_FORCED = {
    "pallas": {"tiled_csc": "pallas_fused", "block_csr": "pallas_block"},
    "jnp": {"tiled_csc": "jnp_oracle", "block_csr": "jnp_oracle"},
}


def _as_2d(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def resolve(key: "registry.ProblemKey", impl: str,
            params: dict | None = None,
            bm: int | None = None,
            fallback_params: dict | None = None,
            ) -> tuple["registry.KernelImpl", dict]:
    """(impl, run_params) for a problem key — the one dispatch resolver.

    Shared by the local path below and the shard_map bodies in
    :mod:`repro.runtime.spmd`, so mesh dispatch sees exactly the same
    tuned-entry/prior/forcing semantics as single-device dispatch.

    ``params`` always overrides the tuned/default choice;
    ``fallback_params`` (a pack plan's dispatch hint) only seeds dispatch
    when no measured tuning-cache entry was found — a hint recorded at one
    M must never override a winner tuned at another.  Under forced impls
    the cache is never consulted, so the hint applies over the forced
    impl's defaults (``sod.apply`` only passes both together when the
    forcing came from the same plan entry as the hint; a caller-forced
    ``impl=`` suppresses the hint there).
    """
    fmt = key.fmt
    tuned = None
    if impl in _FORCED:
        chosen = registry.get_impl(_FORCED[impl][fmt])
        run_params = chosen.default_params(key)
        registry.note_dispatch(key, chosen, run_params, "forced")
    elif impl == "auto":
        from repro.kernels import autotune  # deferred: autotune imports registry

        tuned = autotune.lookup(key)
        chosen, run_params = registry.choose(key, tuned=tuned)
    else:
        raise ValueError(f"unknown impl {impl!r}; want auto | jnp | pallas")
    amend = False
    if fallback_params and tuned is None:
        run_params = dict(run_params)
        run_params.update(
            (k, v) for k, v in fallback_params.items()
            if k in chosen.param_space(key)
        )
        amend = True
    if params:
        run_params = dict(run_params)
        run_params.update(
            (k, v) for k, v in params.items()
            if k in chosen.param_space(key)
        )
    if bm is not None and "bm" in chosen.param_space(key):
        run_params = dict(run_params, bm=bm)
    if params or bm is not None or amend:
        registry.amend_last_dispatch(key, chosen, run_params)
    return chosen, run_params


def sod_matmul(
    x: jax.Array,
    w,
    *,
    impl: str = "auto",
    bm: int | None = None,
    interpret: bool | None = None,
    out_dtype=None,
    backend: str | None = None,
    params: dict | None = None,
    fallback_params: dict | None = None,
    spmd: object = "auto",
) -> jax.Array:
    """``x @ W`` where ``W`` is dense, :class:`TiledCSC` or :class:`BlockCSR`.

    ``x``: (..., K).  Returns (..., N) in ``out_dtype`` (default: x.dtype).
    ``params`` overrides individual tunables (e.g. ``{"bm": 64}``) on top of
    the tuned/default choice; ``backend`` overrides dispatch-backend
    detection (``cpu``/``tpu``/``interpret``).

    ``spmd``: ``"auto"`` (default) wraps the kernel in the SPMD layer's
    shard_map when a mesh is active; an explicit
    :class:`repro.runtime.spmd.SpmdPlan` forces a particular partitioning;
    ``None`` disables mesh routing for this call.
    """
    out_dtype = out_dtype or x.dtype
    if isinstance(w, jax.Array) or not isinstance(w, (TiledCSC, BlockCSR)):
        # dense bypass
        return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(out_dtype)

    k_logical, n_logical = w.shape
    if x.shape[-1] != k_logical:
        raise ValueError(f"x inner dim {x.shape[-1]} != W K {k_logical}")

    if backend is None:
        backend = registry.current_backend()
        if impl == "pallas" and backend not in ("tpu", "interpret"):
            backend = "interpret"
        if interpret:
            backend = "interpret"

    if spmd is not None and os.environ.get("REPRO_SPMD", "1") != "0":
        # deferred import: runtime layers over kernels, but the SPMD entry
        # point lives with the other runtime collectives
        from repro.runtime import spmd as spmd_mod

        plan = spmd if isinstance(spmd, spmd_mod.SpmdPlan) else None
        mesh = spmd_mod.active_mesh()
        if not spmd_mod.in_spmd_body():
            if plan is None and spmd == "auto" and mesh is not None:
                plan = spmd_mod.auto_plan(mesh, w)
            if plan is not None:
                return spmd_mod.sod_matmul_spmd(
                    x, w, mesh=mesh, plan=plan, impl=impl, bm=bm,
                    out_dtype=out_dtype, backend=backend, params=params,
                    fallback_params=fallback_params)

    x2, lead = _as_2d(x)
    key = registry.problem_key(w, m=x2.shape[0], backend=backend)
    chosen, run_params = resolve(key, impl, params=params, bm=bm,
                                 fallback_params=fallback_params)
    y = chosen.run(x2, w, out_dtype=out_dtype, backend=backend, **run_params)
    return y.reshape(*lead, n_logical)


def decompress(w, *, impl: str = "auto",
               interpret: bool | None = None) -> jax.Array:
    """Dense matrix from a packed operand (logical, un-padded shape).

    ``interpret=None`` compiles the Pallas kernel on a TPU backend and
    interprets it elsewhere."""
    if isinstance(w, TiledCSC) and impl in ("auto", "pallas"):
        dense = decompress_pallas(w, interpret=interpret)
        return dense[: w.shape[0], : w.shape[1]]
    if hasattr(w, "to_dense"):
        return w.to_dense()
    return w
