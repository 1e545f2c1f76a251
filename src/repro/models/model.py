"""Unified model API over the three assemblies + loss functions."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import cache as cache_mod
from repro.models import layers, transformer

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class LM:
    """Functional language model: init / apply / loss / prefill / decode."""

    cfg: ModelConfig

    # -- init ----------------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        if cfg.family == "hybrid":
            return transformer.init_hybrid(key, cfg)
        if cfg.family == "ssm":
            return transformer.init_xlstm_lm(key, cfg)
        if cfg.latent_attention:
            return transformer.init_latent(key, cfg)
        return transformer.init_transformer(key, cfg)

    # -- full-sequence forward ------------------------------------------------
    def apply(self, params: Params, batch: Params, want_cache: bool = False):
        cfg = self.cfg
        if cfg.family == "hybrid":
            return transformer.hybrid_forward(params, batch, cfg, want_cache)
        if cfg.family == "ssm":
            return transformer.xlstm_forward(params, batch, cfg, want_cache)
        if cfg.latent_attention:
            return transformer.latent_forward(params, batch, cfg, want_cache)
        return transformer.transformer_forward(params, batch, cfg, want_cache)

    def loss(self, params: Params, batch: Params):
        logits, aux, _ = self.apply(params, batch)
        targets = batch["targets"]
        mask = batch.get("loss_mask")
        if self.cfg.family == "vlm":
            # logits cover [patches + text]; loss only on the text suffix
            n_patch = logits.shape[1] - targets.shape[1]
            logits = logits[:, n_patch:]
        ce = layers.cross_entropy(logits, targets, mask)
        loss = ce + aux
        return loss, {"loss": loss, "ce": ce, "aux": jnp.asarray(aux)}

    # -- serving ---------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int) -> Params:
        cfg = self.cfg
        if cfg.family == "hybrid":
            return transformer.hybrid_init_cache(cfg, batch, max_len)
        if cfg.family == "ssm":
            return transformer.xlstm_init_cache(cfg, batch, max_len)
        if cfg.latent_attention:
            return transformer.latent_init_cache(cfg, batch, max_len)
        return transformer.transformer_init_cache(cfg, batch, max_len)

    def prefill(self, params: Params, batch: Params):
        """Returns (last-token logits, cache).  Attention families only; the
        recurrent families rebuild state by stepping (see serve driver)."""
        logits, _, cache = self.apply(params, batch, want_cache=True)
        return logits[:, -1], cache

    def decode_step(self, params: Params, cache: Params, tokens: jax.Array,
                    pos: jax.Array):
        """tokens: (B, 1) (or (B, 1, C) audio).  Returns (logits, cache).

        ``pos`` is a scalar (all rows at the same position) or a ``(B,)``
        vector (ragged batches — each row decodes at its own position)."""
        cfg = self.cfg
        if cfg.family == "hybrid":
            return transformer.hybrid_decode(params, cache, tokens, pos, cfg)
        if cfg.family == "ssm":
            return transformer.xlstm_decode(params, cache, tokens, pos, cfg)
        if cfg.latent_attention:
            return transformer.latent_decode(params, cache, tokens, pos, cfg)
        return transformer.transformer_decode(params, cache, tokens, pos, cfg)

    # -- cache geometry (serving engine / serve driver) ------------------------
    def cache_spec(self) -> Params:
        """Structure-matched tree of :class:`repro.models.cache.CacheAxes`
        describing every decode-cache leaf's batch/sequence axes."""
        cfg = self.cfg
        if cfg.family == "hybrid":
            return transformer.hybrid_cache_spec(cfg)
        if cfg.family == "ssm":
            return transformer.xlstm_cache_spec(cfg)
        if cfg.latent_attention:
            return transformer.latent_cache_spec(cfg)
        return transformer.transformer_cache_spec(cfg)

    def grow_cache(self, cache: Params, new_len: int) -> Params:
        """Explicit cache growth: zero-pad the *sequence* axes (and only
        those) out to ``new_len``.  Replaces the serve driver's old
        shape-matching heuristic, which mis-grew any leaf whose unrelated
        dim happened to equal the prompt length."""
        return cache_mod.grow_cache(cache, self.cache_spec(), new_len)

    # -- paged decode (continuous-batching engine) -----------------------------
    def init_paged_pool(self, n_pages: int, page_size: int) -> Params:
        if self.cfg.family in ("hybrid", "ssm"):
            raise ValueError(
                f"family {self.cfg.family!r} keeps O(1) recurrent state per "
                "slot; only attention-family KV caches are paged")
        if self.cfg.latent_attention:
            return transformer.latent_init_paged_pool(
                self.cfg, n_pages, page_size)
        return transformer.transformer_init_paged_pool(
            self.cfg, n_pages, page_size)

    def paged_decode_step(self, params: Params, pool: Params,
                          block_tables: jax.Array, tokens: jax.Array,
                          pos: jax.Array,
                          valid_len: jax.Array | None = None):
        """Ragged decode step over the paged KV pool: tokens (B, 1), pos
        (B,), block_tables (B, max_pages).  ``valid_len`` (optional, (B,))
        is a per-row write cutoff — rows at or beyond it redirect their KV
        write to the trash page.  Returns (logits, pool), and for latent
        attention's held-expert model the rows routed to a held expert,
        summed over experts and MoE layers, after those."""
        if self.cfg.family in ("hybrid", "ssm"):
            raise ValueError(
                f"family {self.cfg.family!r} has no paged decode path")
        if self.cfg.latent_attention:
            return transformer.latent_decode_paged(
                params, pool, block_tables, tokens, pos, self.cfg,
                valid_len=valid_len)
        return transformer.transformer_decode_paged(
            params, pool, block_tables, tokens, pos, self.cfg,
            valid_len=valid_len)

    def prefill_chunk(self, params: Params, pool: Params,
                      block_tables: jax.Array, tokens: jax.Array,
                      start: jax.Array, valid_len: jax.Array):
        """Chunked prefill against the paged pool: tokens (B, C) covering
        prompt positions [start, start+C), zero-padded past ``valid_len``.
        Returns (logits for all C positions, pool)."""
        if self.cfg.family in ("hybrid", "ssm"):
            raise ValueError(
                f"family {self.cfg.family!r} has no paged prefill path — "
                "recurrent prompts replay through the decode step")
        if self.cfg.latent_attention:
            raise ValueError("latent attention prefills a whole prompt at "
                             "admission; it has no chunked prefill path")
        return transformer.transformer_prefill_chunk(
            params, pool, block_tables, tokens, start, valid_len, self.cfg)

    def verify_chunk(self, params: Params, pool: Params,
                     block_tables: jax.Array, tokens: jax.Array,
                     start: jax.Array, valid_len: jax.Array):
        """Speculative-window verification: tokens (B, C) covering cache
        positions [start[b], start[b]+C) per row, writes clamped at
        valid_len[b].  Returns (logits for all C positions, pool); logits
        are bitwise what C sequential paged decode steps would produce."""
        if self.cfg.family in ("hybrid", "ssm"):
            raise ValueError(
                f"family {self.cfg.family!r} has no paged verify path — "
                "speculative decoding needs the paged-KV cache")
        if self.cfg.latent_attention:
            raise ValueError("latent attention has no speculative verify "
                             "path")
        return transformer.transformer_verify_chunk(
            params, pool, block_tables, tokens, start, valid_len, self.cfg)

    # -- info -------------------------------------------------------------------
    def param_count(self, params: Params | None = None) -> int:
        if params is None:
            return self.cfg.param_count()
        return sum(
            int(x.size) for x in jax.tree_util.tree_leaves(params)
            if hasattr(x, "size")
        )


def build_model(cfg: ModelConfig) -> LM:
    return LM(cfg)
