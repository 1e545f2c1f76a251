"""Continuous-batching inference engine over Sparse-on-Dense weights.

One :class:`Engine` owns a fixed number of *slots* (rows of the batched
decode step) and admits/evicts requests every step, so sequences of
different lengths join and leave the running batch continuously — the
regime where the paper's compressed weight storage pays off most, since
decode is weight-bytes-bound and every slot shares the one packed copy.

Two cache regimes, chosen by model family:

* **paged** (attention families): per-layer KV page pools
  (:func:`repro.models.transformer.transformer_init_paged_pool`) with a
  host-side refcounted free-list allocator
  (:class:`repro.serving.pool.PagePool`) and one block table per slot.
* **slot state** (hybrid / ssm): O(1) recurrent state lives in a
  max_slots-batched cache; admission replays the prompt through the
  batch-1 decode step (exactly the static serve path) and scatters the
  final state into the slot via the explicit cache-axes API
  (:func:`repro.models.cache.write_slot`).

Three scheduler upgrades (paged families, all off by default) keep the
batch busy under real load:

* **chunked prefill** (``prefill_chunk=C``): admission splits a prompt
  into fixed C-token chunks run one per engine step, interleaved with the
  running batch's decode steps — a long prompt no longer freezes decode.
  The admitted sequence holds a slot in the *prefilling* state (its
  block-table row is masked to the trash page for decode) until its final
  chunk delivers the first token.
* **preemption with page-level swapping** (``preemption=True``): on pool
  pressure the engine swaps the lowest-priority (youngest-arrival)
  decoding sequence's pages to host memory instead of blocking — the
  worst-case-reservation admission rule is replaced by a
  preemption-backed one (admit when the *prompt* pages fit; growth
  recovers pages by preempting).  Swapped sequences resume ahead of any
  pending newcomer once pages free up; the KV bytes round-trip exactly,
  so tokens are unchanged.
* **prefix sharing** (``prefix_sharing=True``, requires chunked prefill):
  a prefix trie over page-sized prompt token chunks
  (:class:`repro.serving.pool.PrefixTrie`) maps shared prefixes to
  refcounted pages — identical few-shot prefixes pack once, admission
  maps them straight into the block table and prefill skips their
  positions.  Writes into a shared page (a fully shared prompt recomputes
  its last token for logits) copy-on-write fork it first.

One decode upgrade rides the same machinery: **sparsity-tiered
speculative decoding** (``spec_k=k`` with ``draft_params`` — a second,
aggressively compressed pack of the *same* weights, typically from
:func:`repro.runtime.planner.build_draft_plan`).  Each step, every
decoding slot drafts k tokens ahead with the cheap tier (its KV lives in
a parallel page pool addressed by the same block tables), then one
batched verify pass scores the whole k+1-token window with the target
weights; the longest draft prefix matching the target's greedy tokens is
accepted plus one bonus target token, and pages allocated past the new
position roll back to the pool.  Emitted tokens are always the *target's*
argmax, so output is bit-identical to non-speculative greedy decoding —
the draft tier only changes how many positions each step commits.
``spec_k=0`` (the default) leaves every code path byte-identical to the
non-speculative engine.

Every feature composes with every other.  :meth:`Engine.step` is an
explicit phase pipeline — admission (resume swapped, admit what fits) →
prefill (fused at admission, or one chunk per prefilling slot) →
capacity (grow pages out to each slot's decode or draft-window span,
preempting under pressure) → draft window → verify/decode →
commit/rollback — where each phase is a method over the shared slot
state and the feature flags select phase *implementations* rather than
gating ``ValueError``\\s.  The composition rules the pipeline enforces:

* a slot mid-chunked-prefill takes no decode or draft steps — its
  per-row write cutoff (``valid_len``) is 0, so one batched step safely
  covers a mix of prefilling and decoding slots without host-side
  block-table copies;
* draft-pool pages share the target pool's page ids, so the preemption
  reservation rule covers them for free; on preemption a slot's
  speculative pages are *trimmed* (rolled back, never swapped) and its
  draft-pool KV is dropped — the resumed sequence re-drafts from
  scratch, which can only lower acceptance, never change a token;
* rollback (:meth:`_trim_spec_pages`) returns pages through the
  refcount-aware :meth:`repro.serving.pool.PagePool.trim`, so a
  rollback on a prefix-sharing sequence can never free a page the trie
  still maps.

One retention layer sits on top: the **persistent multi-tier prefix
cache** (``prefix_cache_budget`` / ``prefix_cache_dir``, requires prefix
sharing).  Completed prompts' trie-held pages stay alive past sequence
completion under an LRU byte budget (HBM tier); cold pages demote to
host memory through the same per-page gather path preemption uses, and
optionally spill to disk keyed by token-prefix hash so the cache
survives engine restarts.  Admission promotes lower-tier chunks back
into fresh pages (skipping their re-prefill entirely), counts
cache-retained-but-sole-referenced pages as reclaimable capacity, and
demotes them on demand under pool pressure — so retention can never
starve admission.  See :mod:`repro.serving.prefix_cache` and
``docs/caching.md``.  With the cache off, every code path is
byte-identical to the cache-less engine.

Greedy tokens are bit-identical to per-request static-batch serve
(:func:`static_generate`) under any schedule because every per-row
computation is batch-row-independent and padding/masked positions
contribute exact zeros; shared pages hold KV bytes identical to what the
sharer's own prefill would have written, and swapped pages are restored
byte-for-byte.  One documented exception: MoE capacity-factor routing is
batch-global, so under expert-capacity pressure an engine batch can drop
different tokens than a batch-1 run.

All jit-compiled shapes are fixed by (max_slots, pool size, block-table
width, prompt buckets / the chunk size), so steady-state serving never
recompiles; :meth:`Engine.warmup` pre-compiles everything for the queued
trace and is timed separately from steady-state throughput.
"""
from __future__ import annotations

import math
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.launch import steps as steps_mod
from repro.models import cache as cache_mod
from repro.models.model import LM
from repro.serving.pool import PagePool, PoolExhausted, PrefixTrie
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import Request, Scheduler, SeqPhase, SeqState

Params = dict[str, Any]


def bucket_len(plen: int, page_size: int, chunk: int | None = None) -> int:
    """Page-aligned prefill bucket for a prompt of ``plen`` tokens.

    Rounds up to the page size so prompt KV fills whole pages; prompts
    longer than the attention chunk additionally round to a multiple of
    the chunk (``chunked_attention`` requires divisibility there).
    """
    b = -(-plen // page_size) * page_size
    if chunk and b > chunk:
        lcm = math.lcm(page_size, chunk)
        b = -(-plen // lcm) * lcm
    return b


def _set_pages(a: jax.Array, page_ids, pages: jax.Array) -> jax.Array:
    """Write ``pages`` at ``page_ids`` of every layer of the stacked pool
    side ``a`` (G, P, n_pages, page, KV, hd).  ``pages`` holds each layer's
    pages in order, (G, P, n_ids, ...) or any shape that reshapes to
    (G·P·n_ids, page, KV, hd).  One scatter along the leading axis of the
    pool viewed as (G·P·n_pages, page, KV, hd) (a bitcast), so a donated
    pool is written in place."""
    g, p, n = a.shape[:3]
    ids = jnp.asarray(page_ids, jnp.int32).reshape(1, -1)
    rows = (jnp.arange(g * p, dtype=jnp.int32)[:, None] * n + ids).reshape(-1)
    flat = a.reshape((-1,) + a.shape[3:])
    flat = flat.at[rows].set(pages.reshape((-1,) + a.shape[3:]))
    return flat.reshape(a.shape)


def _pool_write_pages(pool: Params, cache: Params, page_ids):
    """Scatter a whole prefill's KV into pages ``page_ids`` of every
    layer's pool in one shot — page j of the bucketed prompt (positions
    [j·page, (j+1)·page)) lands in pool page ``page_ids[j]``.  One
    scatter per admission, in place: the engine donates the pool.  The
    cache is (G, P, 1, S, KV, hd), S = len(page_ids)·page, so its rows are
    each layer's pages in order."""
    return {k: _set_pages(a, page_ids, cache[k]) for k, a in pool.items()}


def _pool_copy_page(pool: Params, src, dst):
    """Copy-on-write fork: duplicate page ``src`` into ``dst`` across
    every layer's pool."""
    return {k: _set_pages(a, dst, a[:, :, src]) for k, a in pool.items()}


def _pool_gather_pages(pool: Params, page_ids):
    """Swap-out: pull pages ``page_ids`` (padded with the trash page to a
    fixed width, so one compile serves every page count) out of every
    layer's pool — (G, P, n_ids, page, KV, hd)."""
    return {"k": pool["k"][:, :, page_ids], "v": pool["v"][:, :, page_ids]}


def _pool_scatter_pages(pool: Params, kv: Params, page_ids):
    """Swap-in: write a gathered snapshot back at fresh page ids.  Padding
    entries target the trash page, which is garbage by design."""
    return {k: _set_pages(a, page_ids, kv[k]) for k, a in pool.items()}


def _pool_get_page(pool: Params, page_id):
    """Cache demotion: slice one page out of every layer's pool —
    (G, P, page, KV, hd) per side."""
    return {"k": pool["k"][:, :, page_id], "v": pool["v"][:, :, page_id]}


def _pool_set_page(pool: Params, kv: Params, page_id):
    """Cache promotion: write one host-restored page's KV back into a
    freshly allocated page of every layer's pool."""
    return {k: _set_pages(a, page_id, kv[k]) for k, a in pool.items()}


class Engine:
    """Continuous-batching engine: paged KV pool + request scheduler +
    ragged batched decode over one shared (optionally SoD-packed) model."""

    def __init__(self, model: LM, params: Params, *, max_slots: int = 4,
                 page_size: int = 16, max_len: int = 256,
                 n_pages: int | None = None, plan=None, mesh=None,
                 prefill_chunk: int | None = None, preemption: bool = False,
                 prefix_sharing: bool = False, spec_k: int = 0,
                 draft_params: Params | None = None, draft_plan=None,
                 prefix_cache_budget: int = 0,
                 prefix_cache_dir: str | None = None, tracer=None):
        cfg = model.cfg
        if cfg.family in ("vlm", "audio"):
            raise NotImplementedError(
                f"engine serves token-in/token-out families; {cfg.family!r} "
                "needs frontend plumbing (prefix embeds / codebook stacks)")
        self.model = model
        self.params = params
        self.plan = plan
        self.max_slots = int(max_slots)
        self.max_len = int(max_len)
        self.paged = cfg.family not in ("hybrid", "ssm")
        if not self.paged and (prefill_chunk or preemption or prefix_sharing
                               or prefix_cache_budget or prefix_cache_dir):
            raise ValueError(
                f"family {cfg.family!r} keeps O(1) recurrent state per slot; "
                "chunked prefill / preemption / prefix sharing / the prefix "
                "cache are paged-KV scheduler features")
        if prefix_sharing and not prefill_chunk:
            raise ValueError(
                "prefix sharing needs chunked prefill (prefill_chunk=...): "
                "admission skips shared positions, so prefill must be able "
                "to start mid-prompt")
        if (prefix_cache_budget or prefix_cache_dir) and not prefix_sharing:
            raise ValueError(
                "the prefix cache retains trie-held prompt pages: pass "
                "prefix_sharing=True (and prefill_chunk=...) to enable it")
        self.spec_k = int(spec_k or 0)
        if self.spec_k:
            if not self.paged:
                raise ValueError(
                    f"family {cfg.family!r} keeps O(1) recurrent state per "
                    "slot; speculative decoding verifies windows against "
                    "the paged KV cache")
            if draft_params is None:
                raise ValueError(
                    "spec_k > 0 needs draft_params — a second (aggressively "
                    "compressed) pack of the same weights, e.g. from "
                    "repro.runtime.planner.build_draft_plan")
        self.draft_params = draft_params
        self.draft_plan = draft_plan
        self.prefill_chunk = int(prefill_chunk) if prefill_chunk else None
        self.preemption = bool(preemption)
        self.prefix_sharing = bool(prefix_sharing)
        self.tracer = tracer if tracer is not None else obs.get_tracer()
        self.metrics = obs.Metrics()
        self.sched = Scheduler(max_slots, tracer=self.tracer)
        self._step_idx = 0
        self._submitted: list[Request] = []
        self._first_seen: dict[int, float] = {}
        self._finished: dict[int, SeqState] = {}
        self.preempt_log: list[int] = []      # rids in eviction order
        # the stats dict lives on the metrics registry's counter table —
        # a dict-compatible view, so every existing key and access stays
        # bit-identical while snapshots see the same numbers
        self.stats = self.metrics.stats_view()
        self.stats.update({
            "warmup_s": 0.0, "prefill_chunks": 0, "preemptions": 0,
            "swapped_out_pages": 0, "swapped_in_pages": 0, "cow_forks": 0,
            "shared_prompt_pages": 0, "prompt_pages_total": 0,
            "prompt_pages_fresh": 0, "spec_windows": 0,
            "draft_proposed": 0, "draft_accepted": 0,
            "spec_rollbacks": 0, "spec_rollback_pages": 0,
            "spec_window_preemptions": 0,
            "prefix_hits": 0, "prefix_misses": 0, "prefix_hbm_hits": 0,
            "prefix_host_hits": 0, "prefix_disk_hits": 0,
            "prefix_restored_pages": 0, "prefix_demotions_host": 0,
            "prefix_demotions_disk": 0, "reprefill_tokens_saved": 0,
            "prefix_bytes_hbm": 0, "prefix_bytes_host": 0,
            "prefix_bytes_disk": 0,
        })
        self._pos = np.zeros(self.max_slots, np.int32)
        self._tok = np.zeros((self.max_slots, 1), np.int32)
        self.prefix_cache: PrefixCache | None = None

        if self.paged:
            self.page_size = int(page_size)
            self._chunk = cfg.attn_chunk
            # speculative windows probe up to spec_k positions past a
            # sequence's own lifetime; widening the block tables keeps
            # those (trash-redirected) lookups in bounds so a clamped
            # gather can never alias a live page
            self.max_pages = -(-(self.max_len + self.spec_k)
                               // self.page_size)
            if n_pages is None:
                n_pages = 1 + self.max_slots * self.max_pages
            self.page_pool = PagePool(n_pages, self.page_size)
            self.trie = PrefixTrie(self.page_size) if prefix_sharing else None
            self.pool = model.init_paged_pool(n_pages, self.page_size)
            if prefix_cache_budget or prefix_cache_dir:
                k = self.pool["k"]
                page_nbytes = 2 * (k.size // k.shape[2]) * k.dtype.itemsize
                self._page_get = jax.jit(_pool_get_page)
                self._page_set = jax.jit(_pool_set_page, donate_argnums=0)
                self.prefix_cache = PrefixCache(
                    self.page_pool, page_nbytes,
                    budget_bytes=int(prefix_cache_budget or 0),
                    cache_dir=prefix_cache_dir,
                    gather=self._gather_page_host,
                    on_page_freed=self.trie.drop)
            self.block_tables = np.full(
                (self.max_slots, self.max_pages), PagePool.TRASH_PAGE,
                np.int32)
            # every program that writes the pool donates it (the caller
            # rebinds the result), so the pool is updated in place
            self._decode = jax.jit(
                steps_mod.make_paged_decode_step(model, mesh=mesh, plan=plan),
                donate_argnums=1)
            self._prefill = jax.jit(
                steps_mod.make_prefill_full(model, mesh=mesh, plan=plan))
            self._page_write = jax.jit(_pool_write_pages, donate_argnums=0)
            self._copy_page = jax.jit(_pool_copy_page, donate_argnums=0)
            self._gather_pages = jax.jit(_pool_gather_pages)
            self._scatter_pages = jax.jit(_pool_scatter_pages,
                                          donate_argnums=0)
            if self.prefill_chunk:
                self._chunk_prefill = jax.jit(
                    steps_mod.make_chunked_prefill_step(model, mesh=mesh,
                                                        plan=plan),
                    donate_argnums=1)
            if self.spec_k:
                # the draft tier's KV lives in a parallel page pool
                # addressed by the same block tables / page ids
                self.draft_pool = model.init_paged_pool(n_pages,
                                                        self.page_size)
                self._draft_decode = jax.jit(
                    steps_mod.make_paged_decode_step(model, mesh=mesh,
                                                     plan=draft_plan),
                    donate_argnums=1)
                self._draft_prefill = jax.jit(
                    steps_mod.make_prefill_full(model, mesh=mesh,
                                                plan=draft_plan))
                self._verify = jax.jit(
                    steps_mod.make_verify_step(model, mesh=mesh, plan=plan),
                    donate_argnums=1)
                if self.prefill_chunk:
                    self._draft_chunk_prefill = jax.jit(
                        steps_mod.make_chunked_prefill_step(
                            model, mesh=mesh, plan=draft_plan),
                        donate_argnums=1)
        else:
            self.cache = model.init_cache(self.max_slots, self.max_len)
            spec = model.cache_spec()
            self._decode = jax.jit(
                steps_mod.make_decode_step(model, mesh=mesh, plan=plan))
            self._write_slot = jax.jit(
                lambda c, sub, slot: cache_mod.write_slot(c, sub, spec, slot))

    # -- admission ------------------------------------------------------------
    def _bucket(self, plen: int) -> int:
        return bucket_len(plen, self.page_size, self._chunk)

    def submit(self, req: Request) -> None:
        """Queue a request, validating it can ever fit this engine
        (prompt + generation budget within ``max_len`` and the page
        pool); admission happens later, when a slot and pages free up."""
        plen = len(req.tokens)
        end = plen + req.max_new - 1          # last cache position + 1
        if self.paged:
            if self.prefill_chunk and self._chunk and plen > self._chunk:
                raise ValueError(
                    f"request {req.rid}: prompt of {plen} tokens exceeds "
                    f"attn_chunk={self._chunk}; chunked prefill's "
                    "single-block attention is only bit-identical to the "
                    "fused reference for prompts within one attention "
                    "chunk")
            need = end if self.prefill_chunk else max(self._bucket(plen), end)
            pages = self.page_pool.pages_for(need)
            if need > self.max_len or pages > self.page_pool.n_pages - 1:
                raise ValueError(
                    f"request {req.rid}: needs {need} positions / {pages} "
                    f"pages; engine max_len={self.max_len}, pool="
                    f"{self.page_pool.n_pages}")
        elif end > self.max_len:
            raise ValueError(
                f"request {req.rid}: needs {end} positions; engine "
                f"max_len={self.max_len}")
        self._submitted.append(req)
        self.sched.submit(req)

    @staticmethod
    def _seq_end(seq: SeqState) -> int:
        """Last cache position the sequence will ever write, + 1.  Holds
        for prefilling and decoding states alike (for a decoding sequence
        it equals ``pos + remaining``)."""
        return len(seq.req.tokens) + seq.req.max_new - 1

    def _lifetime_pages(self, req: Request) -> int:
        """Worst-case pages the request will ever hold: its prefill
        bucket (or bare prompt, chunked) plus decode growth out to its
        last write position."""
        plen = len(req.tokens)
        end = plen + req.max_new - 1
        need = end if self.prefill_chunk else max(self._bucket(plen), end)
        return self.page_pool.pages_for(need)

    def _reserved_pages(self) -> int:
        """Pages the *running* sequences may still claim via growth.
        Without preemption, admission holds these back so mid-decode
        growth can never find the pool empty."""
        r = self._pending_forks()
        for seq in self.sched.active.values():
            r += max(0, self.page_pool.pages_for(self._seq_end(seq))
                     - len(seq.pages))
        return r

    def _pending_forks(self) -> int:
        """Copy-on-write forks admitted-but-not-yet-taken: a prefilling
        sequence whose next write lands in a page it still shares will
        claim one fresh page at its next tick."""
        n = 0
        for seq in self.sched.active.values():
            if seq.is_prefilling and seq.pages:
                j = seq.prefilled // self.page_size
                if (j < len(seq.pages)
                        and self.page_pool.ref_count(seq.pages[j]) > 1):
                    n += 1
        return n

    def _share_plan(self, req: Request,
                    ) -> tuple[list[int], list[str], int, int]:
        """Prefix-trie + cache lookup for a prompt: (shared page ids,
        lower-tier restore keys, prefill start position, fresh pages
        needed now).  The trie walk finds HBM-resident prefix pages; with
        a prefix cache, the walk continues through the host/disk tiers —
        each further page-aligned chunk whose token-prefix hash is cached
        gets promoted at admission instead of prefilled (its page still
        counts as *fresh* for allocation).  A fully shared page-aligned
        prompt still recomputes its last token (the engine needs its
        logits); when that last page is trie-shared the write
        copy-on-write-forks it — budget one extra page — while a
        restored last page is private, so the recompute writes in place
        (byte-identical by determinism of the prefill math)."""
        plen = len(req.tokens)
        shared = self.trie.match(req.tokens) if self.trie is not None else []
        restore: list[str] = []
        if self.prefix_cache is not None:
            ps = self.page_size
            j = len(shared)
            while (j + 1) * ps <= plen:
                key = PrefixCache.key(req.tokens[:(j + 1) * ps])
                if self.prefix_cache.peek(key) is None:
                    break
                restore.append(key)
                j += 1
        start = (len(shared) + len(restore)) * self.page_size
        fresh = self.page_pool.pages_for(plen) - len(shared)
        if start >= plen:                 # fully covered, aligned prompt
            start = plen - 1
            if not restore:
                fresh += 1                # COW fork of the last page
        return shared, restore, start, fresh

    def _can_admit(self, req: Request,
                   share: tuple[list[int], list[str], int, int] | None = None,
                   ) -> bool:
        plen = len(req.tokens)
        end = plen + req.max_new - 1
        if self.prefill_chunk:
            share = share if share is not None else self._share_plan(req)
            fresh = share[3]
            growth = (self.page_pool.pages_for(end)
                      - self.page_pool.pages_for(plen))
        else:
            fresh = self.page_pool.pages_for(self._bucket(plen))
            growth = self._lifetime_pages(req) - fresh
        if self.preemption:
            # preemption-backed rule: admit when the prompt fits NOW
            # (counting forks already-admitted prefills will still take);
            # decode growth later recovers pages by evicting the youngest
            return fresh + self._pending_forks() <= self._headroom()
        # reservation rule: the pool must also cover this request's own
        # growth (incl. any COW fork) and every running sequence's
        # worst-case growth
        budget = self._headroom() - self._reserved_pages()
        return fresh + growth <= budget

    # -- cache-aware allocation -----------------------------------------------
    def _headroom(self) -> int:
        """Pages allocatable right now plus cache-retained pages whose
        only holder is the cache — those demote on demand, so admission
        treats them as reclaimable capacity."""
        free = self.page_pool.free_count
        if self.prefix_cache is not None:
            free += self.prefix_cache.reclaimable()
        return free

    def _provide(self, n: int) -> bool:
        """Make ``n`` pages allocatable without preempting anyone, by
        demoting reclaimable cache entries LRU-first.  Returns whether
        :meth:`PagePool.alloc` of ``n`` would now succeed."""
        if self.page_pool.can_alloc(n):
            return True
        if self.prefix_cache is not None:
            self.prefix_cache.reclaim(n - self.page_pool.free_count)
        return self.page_pool.can_alloc(n)

    def _alloc_pages(self, n: int) -> list[int]:
        """Allocate ``n`` pages, demoting cache entries under pressure."""
        if n and not self.page_pool.can_alloc(n):
            self._provide(n)
        return self.page_pool.alloc(n)

    def _admit_paged(self, req: Request) -> list[tuple[int, int]]:
        plen = len(req.tokens)
        bucket = self._bucket(plen)
        with self._phase("prefill"):
            padded = np.zeros(bucket, np.int32)
            padded[:plen] = req.tokens
            logits, cache = self._prefill(
                self.params, {"tokens": jnp.asarray(padded)[None]})
        with self._phase("prefill.wait"):
            first = int(jnp.argmax(logits[0, plen - 1]))
        with self._phase("page_write"):
            n = self.page_pool.pages_for(bucket)
            pages = self.page_pool.alloc(n)
            self.pool = self._page_write(
                self.pool, cache, jnp.asarray(np.asarray(pages, np.int32)))
        if self.spec_k:
            # the draft tier needs its own prompt KV: same pages, its own
            # pool, its own (cheaper) weights.  Draft logits are unused —
            # the first token must be the target's.
            with self._phase("draft.prefill"):
                _, dcache = self._draft_prefill(
                    self.draft_params, {"tokens": jnp.asarray(padded)[None]})
                self.draft_pool = self._page_write(
                    self.draft_pool, dcache,
                    jnp.asarray(np.asarray(pages, np.int32)))
        seq = self.sched.place(req, pos=plen, first_token=first, pages=pages,
                               ready_wall=self._first_seen[req.rid])
        self.block_tables[seq.slot, :] = PagePool.TRASH_PAGE
        self.block_tables[seq.slot, :len(pages)] = pages
        self.stats["prompt_pages_total"] += n
        self.stats["prompt_pages_fresh"] += n
        return self._post_admit(seq)

    def _gather_page_host(self, page: int) -> dict:
        """Snapshot one page's KV bytes to host numpy arrays — the cache's
        demotion path (same per-page movement preemption's swap uses)."""
        snap = self._page_get(self.pool, jnp.asarray(page, jnp.int32))
        with self._phase("demote.wait"):
            return jax.device_get(snap)

    def _restore_prefix(self, keys: list[str]) -> list[int]:
        """Promote cached chunks back into HBM: allocate one fresh page
        per key (demoting colder cache entries under pressure) and
        scatter the host/disk bytes in.  Stops at the first miss or at a
        snapshot whose shape/dtype doesn't match this engine's pool (a
        cache dir written by a different model config) — the remaining
        chunks just prefill normally."""
        k = self.pool["k"]
        expect = k.shape[:2] + k.shape[3:]
        pages: list[int] = []
        for key in keys:
            got = self.prefix_cache.fetch(key)
            if got is None:
                break
            kv, tier = got
            if (kv["k"].shape != expect or kv["v"].shape != expect
                    or str(kv["k"].dtype) != str(k.dtype)
                    or str(kv["v"].dtype) != str(k.dtype)):
                break
            (pg,) = self._alloc_pages(1)
            self.pool = self._page_set(
                self.pool,
                {"k": jnp.asarray(kv["k"]), "v": jnp.asarray(kv["v"])},
                jnp.asarray(pg, jnp.int32))
            self.stats["prefix_host_hits" if tier == "host"
                       else "prefix_disk_hits"] += 1
            self.stats["prefix_restored_pages"] += 1
            pages.append(pg)
        return pages

    def _admit_chunked(self, req: Request,
                       share: tuple[list[int], list[str], int, int]
                       | None = None) -> list[tuple[int, int]]:
        """Admit into the prefilling state: map shared prefix pages,
        promote any lower-tier cached chunks, allocate the rest, and let
        :meth:`_prefill_tick` advance one chunk per step.  No tokens are
        emitted until the final chunk.  Restored pages carry complete KV,
        so they register in the trie immediately and never count as
        *fresh prompt pages* — the second epoch of a repeated prompt
        prefills zero fresh pages."""
        plen = len(req.tokens)
        shared, restore, start, _ = share if share is not None else \
            self._share_plan(req)
        total = self.page_pool.pages_for(plen)
        if shared:
            # retain before any cache reclaim can run: a shared page now
            # has a sequence reference, so demotions can't free it
            self.page_pool.retain(shared)
        hbm_hits = 0
        if self.prefix_cache is not None:
            hbm_hits = sum(1 for p in shared if self.prefix_cache.held(p))
            # leaf-first LRU touch keeps parents younger than children
            for p in reversed(shared):
                self.prefix_cache.touch(p)
        restored = (self._restore_prefix(restore)
                    if self.prefix_cache is not None and restore else [])
        # recompute coverage from what actually promoted (a corrupt disk
        # file truncates the restore chain)
        start = (len(shared) + len(restored)) * self.page_size
        if start >= plen:
            start = plen - 1
        fresh = self._alloc_pages(total - len(shared) - len(restored))
        pages = list(shared) + restored + fresh
        seq = self.sched.place(req, pos=plen, pages=pages,
                               ready_wall=self._first_seen[req.rid],
                               prefilled=start)
        self.block_tables[seq.slot, :] = PagePool.TRASH_PAGE
        self.block_tables[seq.slot, :len(pages)] = pages
        if restored:
            # restored chunks are fully prefilled: share them immediately
            self.trie.register(req.tokens, pages,
                               len(shared) + len(restored))
        self.stats["shared_prompt_pages"] += len(shared)
        self.stats["prompt_pages_total"] += total
        self.stats["prompt_pages_fresh"] += total - len(shared) - len(restored)
        if self.prefix_cache is not None:
            seq.cached_prompt_pages = hbm_hits + len(restored)
            if seq.cached_prompt_pages:
                self.stats["prefix_hits"] += 1
                self.stats["prefix_hbm_hits"] += hbm_hits
                self.stats["reprefill_tokens_saved"] += (
                    self.page_size * seq.cached_prompt_pages)
            else:
                self.stats["prefix_misses"] += 1
        return []

    def _admit_state(self, req: Request) -> list[tuple[int, int]]:
        prompt = jnp.asarray(req.tokens, jnp.int32)[None]
        with self._phase("prefill"):
            sub = self.model.init_cache(1, self.max_len)
            nxt = None
            for t in range(prompt.shape[1]):
                nxt, _, sub = self._decode(
                    self.params, sub, prompt[:, t:t + 1],
                    jnp.asarray(t, jnp.int32))
        with self._phase("prefill.wait"):
            first = int(np.asarray(nxt).reshape(-1)[0])
        seq = self.sched.place(req, pos=prompt.shape[1], first_token=first,
                               pages=[],
                               ready_wall=self._first_seen[req.rid])
        self.cache = self._write_slot(self.cache, sub,
                                      jnp.asarray(seq.slot))
        return self._post_admit(seq)

    def _post_admit(self, seq: SeqState) -> list[tuple[int, int]]:
        seq.first_token_wall = time.perf_counter()
        self._pos[seq.slot] = seq.pos
        self._tok[seq.slot, 0] = seq.generated[-1]
        events = [(seq.req.rid, seq.generated[-1])]
        if seq.remaining == 0:               # max_new == 1: done at prefill
            self._complete(seq.slot)
        return events

    def _complete(self, slot: int) -> None:
        seq = self.sched.release(slot)
        seq.done_wall = time.perf_counter()
        self.metrics.observe("queue_wait_s",
                             seq.admitted_wall - seq.ready_wall)
        self.metrics.observe("ttft_s", seq.first_token_wall - seq.ready_wall)
        self.metrics.observe("tpot_s",
                             (seq.done_wall - seq.first_token_wall)
                             / max(len(seq.generated) - 1, 1))
        if self.paged:
            if self.prefix_cache is not None:
                self._cache_hold(seq)
            freed = self.page_pool.free(seq.pages)
            if self.trie is not None:
                for p in freed:
                    self.trie.drop(p)
            self.block_tables[slot, :] = PagePool.TRASH_PAGE
        self._pos[slot] = 0
        self._tok[slot, 0] = 0
        self._finished[seq.req.rid] = seq

    def _cache_hold(self, seq: SeqState) -> None:
        """Retain the completed sequence's trie-resident prompt chain in
        the cache, so the pages outlive the sequence.  Holds run
        leaf-first so every parent ends more recently used than its
        children — LRU demotions then peel chains leaf-first and can
        never orphan a still-held subtree.  The chain is the *canonical*
        trie pages (another sequence's copy may have won registration),
        keyed by the full token prefix through each chunk."""
        tokens = seq.req.tokens
        matched = self.trie.match(tokens)
        ps = self.page_size
        for j in range(len(matched) - 1, -1, -1):
            self.prefix_cache.hold(
                PrefixCache.key(tokens[:(j + 1) * ps]), matched[j])

    # -- chunked prefill ------------------------------------------------------
    def _try_capacity(self, n: int) -> bool:
        """Try to make ``n`` pages allocatable, preempting youngest-first
        when allowed.  Returns False when every victim is exhausted (a
        victim holding only shared pages frees nothing) — the caller
        decides whether that means waiting or an invariant violation.
        Without preemption this raises: the reservation-based admission
        rule is supposed to make pressure here impossible.  Cache-retained
        pages are demoted first — they are capacity, not residents."""
        while not self._provide(n):
            if not self.preemption:
                raise PoolExhausted(
                    "invariant violation: admission reserved too few pages "
                    "(decode growth or copy-on-write fork)")
            victim = self.sched.preemption_victim()
            if victim is None:
                return False
            self._preempt(victim)
        return True

    def _ensure_exclusive(self, seq: SeqState, lo: int, hi: int) -> bool:
        """Copy-on-write: before writing cache positions [lo, hi), fork
        any page in that range the sequence shares with another.  Returns
        False when a needed fork cannot get a page even after preemption
        — the caller should wait a step, not die."""
        for j in range(lo // self.page_size,
                       (hi - 1) // self.page_size + 1):
            pid = seq.pages[j]
            if self.page_pool.ref_count(pid) > 1:
                if not self._try_capacity(1):
                    return False
                if self.page_pool.ref_count(pid) == 1:
                    # making room preempted the only other sharer — the
                    # page is private now, write in place
                    continue
                new = self.page_pool.fork(pid)
                self.pool = self._copy_page(
                    self.pool, jnp.asarray(pid, jnp.int32),
                    jnp.asarray(new, jnp.int32))
                if self.spec_k:
                    # the draft tier addresses the same page ids: its copy
                    # of the shared prompt KV must follow the fork
                    self.draft_pool = self._copy_page(
                        self.draft_pool, jnp.asarray(pid, jnp.int32),
                        jnp.asarray(new, jnp.int32))
                seq.pages[j] = new
                self.block_tables[seq.slot, j] = new
                self.stats["cow_forks"] += 1
        return True

    def _prefill_tick(self, seq: SeqState) -> list[tuple[int, int]]:
        """Advance one C-token chunk of a prefilling sequence; the final
        chunk (zero-padded past the prompt) yields the first token.  With
        a draft tier, the same chunk also prefills the draft pool (same
        pages, draft weights) so later draft windows see real prompt KV;
        draft logits are unused — the first token must be the target's."""
        c = self.prefill_chunk
        req = seq.req
        plen = len(req.tokens)
        start = seq.prefilled
        end = min(start + c, plen)
        if not self._ensure_exclusive(seq, start, end):
            return []                  # no page for the fork yet: wait
        chunk = np.zeros(c, np.int32)
        chunk[:end - start] = req.tokens[start:end]
        bt_row = jnp.asarray(self.block_tables[seq.slot][None])
        chunk_j = jnp.asarray(chunk)[None]
        start_j = jnp.asarray(start, jnp.int32)
        plen_j = jnp.asarray(plen, jnp.int32)
        logits, self.pool = self._chunk_prefill(
            self.params, self.pool, bt_row, chunk_j, start_j, plen_j)
        if self.spec_k:
            _, self.draft_pool = self._draft_chunk_prefill(
                self.draft_params, self.draft_pool, bt_row, chunk_j,
                start_j, plen_j)
        seq.prefilled = end
        self.stats["prefill_chunks"] += 1
        if self.trie is not None:
            self.trie.register(req.tokens, seq.pages,
                               end // self.page_size)
        if end < plen:
            return []
        with self._phase("prefill.wait"):
            first = int(jnp.argmax(logits[0, plen - 1 - start]))
        seq.generated.append(first)
        seq.pos = plen
        self.sched.set_phase(seq, SeqPhase.DECODING)
        return self._post_admit(seq)

    # -- preemption / swapping ------------------------------------------------
    def _padded_ids(self, pages: list[int]) -> jax.Array:
        ids = np.full(self.max_pages, PagePool.TRASH_PAGE, np.int32)
        ids[:len(pages)] = pages
        return jnp.asarray(ids)

    def _preempt(self, seq: SeqState) -> None:
        """Swap the sequence's pages to host memory and free them; the
        scheduler queues it for resume ahead of pending newcomers.

        Speculative pages — anything grown past the committed prefix for
        an in-flight draft window — are *trimmed* first, never swapped:
        their KV is uncommitted by definition, so the resumed sequence
        just re-drafts.  The draft pool's KV for the swapped pages is
        dropped with them (only the target pool round-trips to host);
        after resume the draft tier re-builds its KV as decode proceeds,
        which can lower acceptance for that sequence but never changes a
        token — emissions are always the target's argmax.
        """
        if self.spec_k and seq.phase is SeqPhase.DECODING:
            keep = self.page_pool.pages_for(seq.pos)
            if len(seq.pages) > keep:
                # a spec window was in flight for this slot: roll its
                # uncommitted pages back before the swap snapshot
                freed = self.page_pool.trim(seq.pages[keep:])
                if self.trie is not None:
                    for p in freed:
                        self.trie.drop(p)
                del seq.pages[keep:]
                self.block_tables[seq.slot, keep:] = PagePool.TRASH_PAGE
                self.stats["spec_window_preemptions"] += 1
                self.stats["spec_rollback_pages"] += len(freed)
        n = len(seq.pages)
        snap = self._gather_pages(self.pool, self._padded_ids(seq.pages))
        with self._phase("swap_out.wait"):
            host = jax.device_get(snap)
        seq.host_kv = (host, n)
        freed = self.page_pool.swap_out(seq.pages)
        if self.trie is not None:
            for p in freed:
                self.trie.drop(p)
        slot = seq.slot
        seq.pages = []
        self.block_tables[slot, :] = PagePool.TRASH_PAGE
        self._pos[slot] = 0
        self._tok[slot, 0] = 0
        self.sched.preempt(slot)
        self.preempt_log.append(seq.req.rid)
        self.stats["preemptions"] += 1
        # count pages that actually left the device — shared prefix pages
        # another sequence still references stay resident
        self.stats["swapped_out_pages"] += len(freed)

    def _swap_in(self, seq: SeqState) -> None:
        """Restore a preempted sequence: fresh pages, exact KV bytes."""
        host, n = seq.host_kv
        pages = self.page_pool.swap_in(n)
        self.pool = self._scatter_pages(
            self.pool, jax.tree_util.tree_map(jnp.asarray, host),
            self._padded_ids(pages))
        seq.host_kv = None
        seq.pages = pages
        self.sched.place_swapped(seq)
        self.block_tables[seq.slot, :] = PagePool.TRASH_PAGE
        self.block_tables[seq.slot, :n] = pages
        self._pos[seq.slot] = seq.pos
        self._tok[seq.slot, 0] = seq.generated[-1]
        self.stats["swapped_in_pages"] += n

    def _phase_capacity(self) -> None:
        """Capacity phase: grow every decoding sequence's pages out to
        the span the coming step will write — position ``pos`` for plain
        decode, ``[pos, min(pos + spec_k, seq_end - 1)]`` for a draft
        window (positions past ``seq_end`` redirect to the trash page, so
        the worst-case-reservation rule ``pages_for(seq_end)`` still
        bounds growth).  Under pressure, preemption evicts the youngest
        decoding sequence (possibly the needy one itself — re-checked per
        slot) instead of dying mid-decode; a preempted victim's own
        speculative pages are trimmed by :meth:`_preempt`, not swapped."""
        for slot in sorted(self.sched.active):
            seq = self.sched.active.get(slot)
            if seq is None or seq.phase is not SeqPhase.DECODING:
                continue
            need = self.page_pool.pages_for(
                min(seq.pos + self.spec_k + 1, self._seq_end(seq)))
            if need <= len(seq.pages):
                # in-place write: must be exclusive — only *complete*
                # prompt pages are ever shared, and decode writes land
                # strictly past them (the fully-shared boundary page is
                # forked during the recompute prefill tick)
                assert self.page_pool.ref_count(
                    seq.pages[seq.pos // self.page_size]) == 1, (
                    "decode write into shared page "
                    f"{seq.pages[seq.pos // self.page_size]}")
                continue
            ok = self._try_capacity(need - len(seq.pages))
            if self.sched.active.get(slot) is not seq:
                continue                     # the hunt preempted seq itself
            if not ok:
                raise PoolExhausted(
                    "pool exhausted with no preemptible sequence — "
                    "the pool cannot hold even one request")
            while len(seq.pages) < need:
                (pg,) = self.page_pool.alloc(1)
                seq.pages.append(pg)
                self.block_tables[slot, len(seq.pages) - 1] = pg

    # -- speculative decoding -------------------------------------------------
    def _trim_spec_pages(self, seq: SeqState) -> None:
        """Roll back pages allocated for rejected window positions: keep
        only what covers the committed prefix ``[0, pos)`` (never below
        the prompt bucket — ``pos > plen`` always) and return the rest to
        the pool via the refcount-aware :meth:`~repro.serving.pool.
        PagePool.trim`, so a sharer's rollback can never free a page the
        trie still maps (only pages whose last reference dropped leave
        the trie).  Stale KV beyond ``pos`` needs no scrubbing: the next
        window re-writes each position before any row can attend to it."""
        keep = self.page_pool.pages_for(seq.pos)
        if len(seq.pages) > keep:
            freed = self.page_pool.trim(seq.pages[keep:])
            if self.trie is not None:
                for p in freed:
                    self.trie.drop(p)
            del seq.pages[keep:]
            self.block_tables[seq.slot, keep:] = PagePool.TRASH_PAGE
            self.stats["spec_rollbacks"] += 1
            self.stats["spec_rollback_pages"] += len(freed)

    def _valid_lens(self) -> np.ndarray:
        """Per-slot write cutoffs for batched decode/draft/verify steps:
        a decoding slot may write up to its ``seq_end``; prefilling and
        idle slots get 0 (every write redirects to the trash page), which
        is what lets one batched step span a partially-prefilled batch
        without host-side block-table masking."""
        valid = np.zeros(self.max_slots, np.int32)
        for slot, seq in self.sched.active.items():
            if seq.phase is SeqPhase.DECODING:
                valid[slot] = self._seq_end(seq)
        return valid

    def _spec_window(self, decoding: dict[int, SeqState],
                     ) -> list[tuple[int, int]]:
        """One propose/verify/accept window for every decoding slot.

        The draft tier runs ``spec_k`` batched decode steps ahead (its KV
        goes to the parallel draft pool), then one batched verify pass
        scores the whole window ``[committed token, d_1, ..., d_k]`` with
        the target weights.  Per slot, the longest draft prefix matching
        the target's greedy tokens is accepted plus one bonus target
        token — every emission is the *target's* argmax, so the output
        equals sequential greedy decode token-for-token; rejected
        positions' pages roll back via :meth:`_trim_spec_pages`.  Slots
        mid-chunked-prefill ride along with write cutoff 0: their rows
        write to the trash page and their outputs are discarded, so a
        window can run while another slot's prompt is still streaming in.
        """
        k = self.spec_k
        btj = jnp.asarray(self.block_tables)
        valid = jnp.asarray(self._valid_lens())
        d_tok = self._tok.copy()
        d_pos = self._pos.copy()
        drafts = np.zeros((self.max_slots, k), np.int32)
        # k + 1 steps: step j < k proposes d_{j+1}; the extra step only
        # backfills draft KV for position pos + k, which full acceptance
        # commits without another draft read of it this window — skipping
        # it leaves stale pad KV behind the next window's proposals
        with self._phase("draft"):
            for j in range(k + 1):
                nxt, _, self.draft_pool = self._draft_decode(
                    self.draft_params, self.draft_pool, btj,
                    jnp.asarray(d_tok), jnp.asarray(d_pos), valid)
                if j == k:
                    break
                with self._phase("draft.wait"):
                    col = np.asarray(nxt).reshape(self.max_slots, -1)[:, 0]
                drafts[:, j] = col
                d_tok[:, 0] = col
                d_pos += 1

        with self._phase("verify"):
            v_tok = np.zeros((self.max_slots, k + 1), np.int32)
            v_tok[:, 0] = self._tok[:, 0]
            v_tok[:, 1:] = drafts
            nxt, _, self.pool = self._verify(
                self.params, self.pool, btj, jnp.asarray(v_tok),
                jnp.asarray(self._pos), valid)
        with self._phase("verify.wait"):
            target = np.asarray(nxt).reshape(self.max_slots, k + 1)
        with self._phase("commit"):
            return self._spec_commit(decoding, drafts, target)

    def _spec_commit(self, decoding: dict[int, SeqState], drafts: np.ndarray,
                     target: np.ndarray) -> list[tuple[int, int]]:
        """Accept each slot's longest matching draft prefix plus one bonus
        target token, and roll back the pages of rejected positions."""
        k = self.spec_k
        events: list[tuple[int, int]] = []
        for slot, seq in list(decoding.items()):
            m = 0
            while m < k and drafts[slot, m] == target[slot, m]:
                m += 1
            e = min(m + 1, seq.remaining)
            emitted = [int(target[slot, i]) for i in range(e)]
            seq.generated.extend(emitted)
            seq.pos += e
            seq.spec_proposed += k
            seq.spec_accepted += min(m, e)
            self.stats["spec_windows"] += 1
            self.stats["draft_proposed"] += k
            self.stats["draft_accepted"] += min(m, e)
            self._pos[slot] = seq.pos
            self._tok[slot, 0] = emitted[-1]
            events += [(seq.req.rid, t) for t in emitted]
            if seq.remaining == 0:
                self._complete(slot)
            else:
                self._trim_spec_pages(seq)
        return events

    # -- stepping: the per-step phase pipeline --------------------------------
    def _phase(self, name: str):
        """Context manager around one phase of the current step: a tracer
        span ``engine.step.<name>`` (a profiler annotation at least) and
        the phase's seconds in the step log, ``self.metrics.steps``."""
        return self.metrics.steps.phase(
            self.tracer.span(f"engine.step.{name}", track="engine"), name)

    def _phase_admission(self, now: int,
                         rec: obs.StepRecord) -> list[tuple[int, int]]:
        """Admission phase: resume swapped sequences first (they were
        admitted before anyone still pending), then admit queue heads
        while a slot and pages are free.  Fused-prefill admission emits
        the first token immediately; chunked admission places the slot in
        the prefilling phase for :meth:`_phase_prefill` to advance.
        Counts the admitted and the still-waiting admissible requests
        into the step's record ``rec``."""
        now_wall = time.perf_counter()
        # latency clock starts when a request becomes admissible, not when
        # it reaches the queue head — queue wait is part of tail latency
        ready = 0
        for r in self.sched.pending:
            if r.arrival > now:
                break                        # pending is arrival-sorted
            self._first_seen.setdefault(r.rid, now_wall)
            ready += 1
        events: list[tuple[int, int]] = []
        admitted = 0
        if self.paged:
            # swapped sequences were admitted first: resume before anyone
            while self.sched.swapped and self.sched.has_free_slot():
                seq = self.sched.peek_swapped()
                if not self._provide(seq.host_kv[1]):
                    break
                self._swap_in(seq)
        while self.sched.has_free_slot():
            if self.paged and self.sched.swapped:
                break                        # no admission past a swapped seq
            req = self.sched.peek_ready(now)
            if req is None:
                break
            if self.paged:
                # one trie walk per admission attempt, shared between the
                # capacity check and the admission itself
                share = (self._share_plan(req) if self.prefill_chunk
                         else None)
                if not self._can_admit(req, share):
                    break
                if self.prefill_chunk:
                    events += self._admit_chunked(req, share)
                else:
                    events += self._admit_paged(req)
            else:
                events += self._admit_state(req)
            admitted += 1
        rec.admitted = admitted
        rec.queue_ready = ready - admitted
        return events

    def _phase_prefill(self) -> list[tuple[int, int]]:
        """Prefill phase: advance one chunk for every prefilling slot.
        A slot stays excluded from decode and draft windows (write cutoff
        0) until its final chunk delivers the first token."""
        events: list[tuple[int, int]] = []
        for seq in list(self.sched.active.values()):
            if seq.phase is SeqPhase.PREFILLING:
                events += self._prefill_tick(seq)
        return events

    def _phase_decode(self, decoding: dict[int, SeqState],
                      ) -> list[tuple[int, int]]:
        """Verify/decode + commit phase, non-speculative: one ragged
        batched decode step; every decoding slot commits one token.
        Prefilling/idle rows ride along with write cutoff 0 (paged) or an
        untouched slot cache (recurrent)."""
        events: list[tuple[int, int]] = []
        with self._phase("decode.prepare"):
            tok = jnp.asarray(self._tok)
            pos = jnp.asarray(self._pos)
            if self.paged:
                tables = jnp.asarray(self.block_tables)
                valid = jnp.asarray(self._valid_lens())
        with self._phase("decode.dispatch"):
            if self.paged:
                nxt, _, self.pool = self._decode(
                    self.params, self.pool, tables, tok, pos, valid)
            else:
                nxt, _, self.cache = self._decode(
                    self.params, self.cache, tok, pos)
        with self._phase("decode.wait"):
            nxt = np.asarray(nxt)
        with self._phase("commit"):
            nxt = nxt.reshape(self.max_slots, -1)[:, 0]
            for slot, seq in list(decoding.items()):
                t = int(nxt[slot])
                seq.generated.append(t)
                seq.pos += 1
                self._pos[slot] = seq.pos
                self._tok[slot, 0] = t
                events.append((seq.req.rid, t))
                if seq.remaining == 0:
                    self._complete(slot)
        return events

    def step(self) -> list[tuple[int, int]]:
        """Advance virtual time one step through the phase pipeline:
        admission (resume + admit) → prefill (chunk ticks) → capacity
        (page growth, preempting under pressure) → draft window →
        verify/decode → commit/rollback.  Feature flags select phase
        implementations — every combination of chunked prefill,
        preemption, prefix sharing, and speculative decoding runs through
        this one pipeline.  Returns (rid, token) emissions.

        The step is a span ``engine.step`` and each phase a nested span
        ``engine.step.<phase>`` (see :meth:`_phase`); the step's record in
        ``self.metrics.steps`` gets each phase's seconds and what the step
        admitted, decoded and completed.  The ``*.wait`` phases wrap the
        step's existing host-device syncs and add none."""
        now = self._step_idx
        finished = len(self._finished)
        with self.tracer.span("engine.step", track="engine", step=now), \
                self.metrics.steps.step(now) as rec:
            with self._phase("admission"):
                events = self._phase_admission(now, rec)
            if self.paged:
                if self.prefill_chunk:
                    with self._phase("prefill"):
                        events += self._phase_prefill()
                with self._phase("capacity"):
                    self._phase_capacity()
            decoding = {slot: seq for slot, seq in self.sched.active.items()
                        if seq.phase is SeqPhase.DECODING}
            if decoding:
                rec.decode_rows = len(decoding)
                if self.spec_k:
                    events += self._spec_window(decoding)
                else:
                    events += self._phase_decode(decoding)
            if self.paged:
                with self._phase("pool_sample"):
                    self._sample_pool()
                rec.free_pages = self.page_pool.free_count
            rec.completed = len(self._finished) - finished
        self._step_idx += 1
        return events

    def _sample_pool(self) -> None:
        """Record page-pool occupancy (free/live/swapped) as gauges and,
        when tracing, one sample on the ``pool`` counter track."""
        occ = self.page_pool.occupancy()
        swapped = sum(s.host_kv[1] for s in self.sched.swapped
                      if s.host_kv is not None)
        self.metrics.gauge("pool_free_pages", occ["free"])
        self.metrics.gauge("pool_live_pages", occ["live"])
        self.metrics.gauge("pool_swapped_pages", swapped)
        if self.prefix_cache is not None:
            self.metrics.gauge("pool_cached_pages",
                               len(self.prefix_cache.held_pages))
            self._sync_cache_stats()
        if self.tracer.enabled:
            self.tracer.counter(
                "pool_pages", {"free": occ["free"], "live": occ["live"],
                               "swapped": swapped}, track="pool")

    def _sync_cache_stats(self) -> None:
        """Mirror the cache's tier accounting into the stats dict (the
        per-tier byte counters land in ``BENCH_serving.json``)."""
        c = self.prefix_cache
        tiers = c.bytes_by_tier()
        self.stats["prefix_bytes_hbm"] = tiers["hbm"]
        self.stats["prefix_bytes_host"] = tiers["host"]
        self.stats["prefix_bytes_disk"] = tiers["disk"]
        self.stats["prefix_demotions_host"] = c.demotions_host
        self.stats["prefix_demotions_disk"] = c.demotions_disk

    def flush_prefix_cache(self) -> None:
        """Demote every HBM-resident cache entry — the drain path.  On an
        idle engine this returns the pool to fully-free and empties the
        trie; host/disk copies persist, so identical prompts submitted
        later (or to a fresh engine sharing the cache dir) still promote
        instead of re-prefilling."""
        if self.prefix_cache is not None:
            self.prefix_cache.flush()
            self._sync_cache_stats()

    # -- warmup / run ---------------------------------------------------------
    def warmup(self) -> float:
        """Pre-compile the union of jitted shapes the composed feature
        set can reach on the queued trace — prefill buckets or chunk
        shapes (target and draft tiers alike), the write-cutoff-gated
        batched decode, COW page copies, swap gathers/scatters, and
        draft/verify windows — so steady-state throughput excludes
        compile time.  Results are discarded and every pool write lands in
        the trash page, so no engine state changes; the pool-writing
        programs donate the pool, so each call's returned pool is rebound."""
        with self.tracer.span("warmup", track="engine"):
            return self._warmup_impl()

    def _warmup_impl(self) -> float:
        t0 = time.perf_counter()
        if self.paged:
            if self.prefill_chunk:
                trash_row = jnp.full((1, self.max_pages),
                                     PagePool.TRASH_PAGE, jnp.int32)
                logits, self.pool = self._chunk_prefill(
                    self.params, self.pool, trash_row,
                    jnp.zeros((1, self.prefill_chunk), jnp.int32),
                    jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))
                jax.block_until_ready(logits)
            else:
                buckets = sorted({self._bucket(len(r.tokens))
                                  for r in self.sched.pending})
                for b in buckets:
                    logits, cache = self._prefill(
                        self.params,
                        {"tokens": jnp.zeros((1, b), jnp.int32)})
                    trash = np.full(b // self.page_size,
                                    PagePool.TRASH_PAGE, np.int32)
                    self.pool = self._page_write(
                        self.pool, cache, jnp.asarray(trash))
                    jax.block_until_ready(logits)
            if self.prefix_sharing:
                self.pool = self._copy_page(
                    self.pool, jnp.asarray(0, jnp.int32),
                    jnp.asarray(0, jnp.int32))
            if self.prefix_cache is not None:
                zero = jnp.asarray(PagePool.TRASH_PAGE, jnp.int32)
                snap = self._page_get(self.pool, zero)
                jax.block_until_ready(snap["k"])
                self.pool = self._page_set(self.pool, snap, zero)
            if self.preemption:
                ids = jnp.zeros(self.max_pages, jnp.int32)
                snap = self._gather_pages(self.pool, ids)
                jax.block_until_ready(snap["k"])
                self.pool = self._scatter_pages(self.pool, snap, ids)
            out = self._decode(
                self.params, self.pool, jnp.asarray(self.block_tables),
                jnp.asarray(self._tok), jnp.asarray(self._pos),
                jnp.zeros(self.max_slots, jnp.int32))
            self.pool = out[2]
            jax.block_until_ready(out[0])
            if self.spec_k:
                if self.prefill_chunk:
                    # draft prompt KV streams in per chunk — same chunk
                    # shape as the target tier, draft weights
                    trash_row = jnp.full((1, self.max_pages),
                                         PagePool.TRASH_PAGE, jnp.int32)
                    dlogits, self.draft_pool = self._draft_chunk_prefill(
                        self.draft_params, self.draft_pool, trash_row,
                        jnp.zeros((1, self.prefill_chunk), jnp.int32),
                        jnp.asarray(0, jnp.int32),
                        jnp.asarray(0, jnp.int32))
                    jax.block_until_ready(dlogits)
                else:
                    for b in sorted({self._bucket(len(r.tokens))
                                     for r in self.sched.pending}):
                        _, dcache = self._draft_prefill(
                            self.draft_params,
                            {"tokens": jnp.zeros((1, b), jnp.int32)})
                        trash = np.full(b // self.page_size,
                                        PagePool.TRASH_PAGE, np.int32)
                        self.draft_pool = self._page_write(
                            self.draft_pool, dcache, jnp.asarray(trash))
                out = self._draft_decode(
                    self.draft_params, self.draft_pool,
                    jnp.asarray(self.block_tables), jnp.asarray(self._tok),
                    jnp.asarray(self._pos),
                    jnp.zeros(self.max_slots, jnp.int32))
                self.draft_pool = out[2]
                jax.block_until_ready(out[0])
                out = self._verify(
                    self.params, self.pool, jnp.asarray(self.block_tables),
                    jnp.zeros((self.max_slots, self.spec_k + 1), jnp.int32),
                    jnp.asarray(self._pos),
                    jnp.zeros(self.max_slots, jnp.int32))
                self.pool = out[2]
                jax.block_until_ready(out[0])
            jax.block_until_ready(
                (self.pool, self.draft_pool) if self.spec_k else self.pool)
        else:
            sub = self.model.init_cache(1, self.max_len)
            out = self._decode(self.params, sub,
                               jnp.zeros((1, 1), jnp.int32),
                               jnp.asarray(0, jnp.int32))
            jax.block_until_ready(out[0])
            jax.block_until_ready(jax.tree_util.tree_leaves(
                self._write_slot(self.cache, sub, jnp.asarray(0)))[0])
            out = self._decode(self.params, self.cache,
                               jnp.asarray(self._tok),
                               jnp.asarray(self._pos))
            jax.block_until_ready(out[0])
        dt = time.perf_counter() - t0
        self.stats["warmup_s"] += dt
        return dt

    def run(self, requests: list[Request] | None = None, *,
            warmup: bool = True, max_steps: int | None = None) -> dict:
        """Drive the engine until every submitted request completes.

        Returns ``{"tokens": {rid: [...]}, "stats": {...}}`` with
        compile/warmup time reported separately from steady-state
        throughput (tokens/sec over the post-warmup serving loop).
        """
        for r in requests or []:
            self.submit(r)
        if warmup:
            self.warmup()
        if max_steps is None:
            max_steps = (max((r.arrival for r in self._submitted), default=0)
                         + sum(r.max_new for r in self._submitted)
                         + self.max_slots + 16)
            if self.paged and self.prefill_chunk:
                max_steps += sum(
                    -(-len(r.tokens) // self.prefill_chunk) + 1
                    for r in self._submitted)
            if self.paged and self.preemption:
                max_steps *= 2               # slack for swap cycles
        t0 = time.perf_counter()
        n_tok = 0
        start = self._step_idx
        while not self.sched.done:
            if self._step_idx - start > max_steps:
                raise RuntimeError(
                    f"engine stalled: {len(self.sched.pending)} pending / "
                    f"{len(self.sched.active)} active / "
                    f"{len(self.sched.swapped)} swapped after "
                    f"{max_steps} steps")
            n_tok += len(self.step())
        if self.paged and self.prefix_cache is not None:
            # final completions' demotions happen inside the last step;
            # re-sync so the returned stats carry the end-state tiers
            self._sync_cache_stats()
        steady_s = time.perf_counter() - t0
        fin = list(self._finished.values())
        lat = sorted(s.done_wall - s.ready_wall for s in fin)
        queue = [s.admitted_wall - s.ready_wall for s in fin]
        ttft = [s.first_token_wall - s.ready_wall for s in fin]
        tpot = [(s.done_wall - s.first_token_wall)
                / max(len(s.generated) - 1, 1) for s in fin]

        def _pct(vals: list[float], q: float) -> float:
            return round(float(np.percentile(vals, q)), 6) if vals else 0.0

        self.stats.update({
            "steps": self._step_idx - start,
            "completed": len(self._finished),
            "generated_tokens": n_tok,
            "tokens_per_step": round(
                n_tok / max(self._step_idx - start, 1), 4),
            "acceptance_rate": round(
                self.stats["draft_accepted"]
                / max(self.stats["draft_proposed"], 1), 4),
            "steady_s": round(steady_s, 4),
            "steady_tok_per_s": round(n_tok / max(steady_s, 1e-9), 2),
            "p50_latency_s": round(float(np.percentile(lat, 50)), 4)
            if lat else 0.0,
            "p99_latency_s": round(float(np.percentile(lat, 99)), 4)
            if lat else 0.0,
            "queue_wait_p50_s": _pct(queue, 50),
            "queue_wait_p99_s": _pct(queue, 99),
            "ttft_p50_s": _pct(ttft, 50),
            "ttft_p99_s": _pct(ttft, 99),
            "tpot_p50_s": _pct(tpot, 50),
            "tpot_p99_s": _pct(tpot, 99),
        })
        return {"tokens": {rid: list(s.generated)
                           for rid, s in sorted(self._finished.items())},
                "stats": dict(self.stats)}


# ---------------------------------------------------------------------------
# static-batch reference
# ---------------------------------------------------------------------------
# jit caches key on function identity, so building fresh closures per
# request would recompile identical shapes every call (the reference runs
# once per request per bench variant).  Keyed by object ids, which is safe
# here because the cached closures keep model/plan alive — their ids can't
# be recycled while an entry exists.
_STATIC_FNS: dict[tuple[int, int], tuple] = {}


def _static_fns(model: LM, plan):
    key = (id(model), id(plan))
    if key not in _STATIC_FNS:
        _STATIC_FNS[key] = (
            jax.jit(steps_mod.make_decode_step(model, plan=plan)),
            jax.jit(steps_mod.make_prefill_step(model, plan=plan)),
        )
    return _STATIC_FNS[key]


def static_generate(model: LM, params: Params, req: Request,
                    max_len: int | None = None, plan=None) -> list[int]:
    """Per-request static-batch greedy generation — the reference the
    engine must match token-for-token.  Mirrors the classic serve path:
    fused prefill for attention families, prompt replay through the
    batch-1 decode step for recurrent families."""
    cfg = model.cfg
    prompt = jnp.asarray(req.tokens, jnp.int32)[None]
    plen = prompt.shape[1]
    if max_len is None:
        max_len = plen + req.max_new
    decode, prefill = _static_fns(model, plan)
    if cfg.family in ("hybrid", "ssm"):
        cache = model.init_cache(1, max_len)
        nxt = None
        for t in range(plen):
            nxt, _, cache = decode(params, cache, prompt[:, t:t + 1],
                                   jnp.asarray(t, jnp.int32))
        first = int(np.asarray(nxt).reshape(-1)[0])
    else:
        nxt, cache = prefill(params, {"tokens": prompt})
        cache = model.grow_cache(cache, max_len)
        first = int(np.asarray(nxt).reshape(-1)[0])
    out = [first]
    tok = jnp.full((1, 1), first, jnp.int32)
    for t in range(req.max_new - 1):
        nxt, _, cache = decode(params, cache, tok,
                               jnp.asarray(plen + t, jnp.int32))
        out.append(int(np.asarray(nxt).reshape(-1)[0]))
        tok = jnp.asarray(nxt, jnp.int32).reshape(1, 1)
    return out
