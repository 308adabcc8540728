"""Paged-KV-cache decoding (block tables + continuous batching).

Reference role: the reference's block cache serving stack —
``incubate.nn.functional.block_multihead_attention``
(/root/reference/python/paddle/incubate/nn/functional/
block_multihead_attention.py) and the fleet serving loops above it.

Why paged beats the dense cache (models/decode.py) for serving:

* The dense cache allocates ``[L, B, S_max, nkv, d]`` — every row pays
  the batch-wide maximum.  The POOL allocates pages of ``page`` tokens
  and a row owns ``ceil(len/page)`` of them: HBM scales with the sum of
  ACTUAL lengths (continuous batching's whole point).
* Decode attention reads only a row's own pages (block-table indexed
  DMA in ops/pallas/paged_attention.py), so the cache-traffic-bound
  batch-32 regime (PERF.md) pays for real context, not for S_max.
* Rows advance INDEPENDENTLY: per-row positions/lengths, so requests
  of different ages batch together — the dense ``make_generate`` locks
  the whole batch to one position.

Host side, :class:`PagedKVCache` is a free-list page allocator (the
role vLLM's block manager plays); device side, one jitted step embeds
the batch's next tokens, RoPEs at per-row positions, appends K/V into
pages, and runs the paged-attention kernel per layer.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..profiler.utils import RecordEvent
from ..testing import faults
from .llama_pretrain import (LlamaPretrainConfig, _block_post_attn, _mm,
                             _rms_norm)

__all__ = ["PagedKVCache", "make_paged_decode_step",
           "make_paged_decode_step_async",
           "make_paged_decode_step_multi", "make_mixed_step",
           "generate_paged", "generate_auto"]


class PagedKVCache:
    """Free-list page allocator + device page pools for all layers.

    Pools: ``[L, num_pages, nkv, page, d]`` (page layout matches the
    reference's ``[max_block_num, kv_num_head, block_size, head_dim]``).
    Page 0 is reserved as the junk page unused table slots point at —
    the kernel skips them, but their ids must stay DMA-valid.

    With ``mesh`` (an mp>1 device mesh) the pools are SHARDED on the
    kv-head axis — each model-parallel rank stores only its own heads'
    pages, so a model wider than one chip serves with per-chip cache
    HBM of nkv/mp heads (the fleet-executor dist-model serving case,
    reference: fluid/distributed/fleet_executor/dist_model.h:57).

    With ``host_pages`` > 0 a HOST-RAM page tier (kv_offload.py)
    backs the pool: preempted rows swap out instead of releasing
    (``swap_out_row`` / ``swap_in_row`` — resume restores pages with
    zero prefill tokens) and evicted cached-prefix pages demote to
    host and promote back on lookup, so prefix-cache depth scales
    with host RAM rather than the decode pool.  The two compose: on a
    TP mesh the host tier stages PER SHARD (each rank's local-heads
    slice rides its own async D2H copy — see kv_offload.py), and the
    int8 scale planes shard with the heads, so offload / promote /
    demote and :meth:`audit` all work against the sharded pool.
    """

    def __init__(self, cfg: LlamaPretrainConfig, num_pages: int,
                 pages_max: int, batch: int, page: int = 64,
                 dtype=None, kv_quant: Optional[str] = None,
                 mesh=None, host_pages: int = 0):
        if kv_quant not in (None, "int8"):
            raise ValueError("kv_quant must be None or 'int8'")
        self.cfg = cfg
        self.page = page
        self.pages_max = pages_max
        self.num_pages = num_pages
        self.kv_quant = kv_quant
        self.mesh = mesh
        dt = dtype or cfg.dtype
        L = cfg.num_hidden_layers
        nkv, d = cfg.num_key_value_heads, cfg.head_dim
        pool_dt = jnp.int8 if kv_quant == "int8" else dt

        def _put(x, spec):
            if mesh is None or mesh.shape.get("mp", 1) == 1:
                return x
            from jax.sharding import NamedSharding, PartitionSpec
            return jax.device_put(
                x, NamedSharding(mesh, PartitionSpec(*spec)))

        if mesh is not None and nkv % mesh.shape.get("mp", 1) != 0:
            raise ValueError(
                f"kv heads {nkv} must divide over mp="
                f"{mesh.shape.get('mp', 1)}")
        self.kpool = _put(jnp.zeros((L, num_pages, nkv, page, d),
                                    pool_dt),
                          (None, None, "mp", None, None))
        self.vpool = _put(jnp.zeros((L, num_pages, nkv, page, d),
                                    pool_dt),
                          (None, None, "mp", None, None))
        if kv_quant == "int8":
            # per-(head, slot) f32 scales — halves cache HBM traffic in
            # the large-batch decode regime (PERF.md round-4 lever)
            self.kscale = _put(jnp.ones((L, num_pages, nkv, page),
                                        jnp.float32),
                               (None, None, "mp", None))
            self.vscale = _put(jnp.ones((L, num_pages, nkv, page),
                                        jnp.float32),
                               (None, None, "mp", None))
        else:
            self.kscale = self.vscale = None
        self._free = list(range(num_pages - 1, 0, -1))   # page 0 reserved
        self.tables = np.zeros((batch, pages_max), np.int32)
        self.lens = np.zeros((batch,), np.int32)
        self._owned = [[] for _ in range(batch)]
        # bumped on every host-side ``tables`` mutation so callers
        # keeping a device-resident copy (the dispatch-ahead serving
        # loop) re-upload only when the block tables actually changed
        self.tables_version = 0
        # PREFIX CACHING (vLLM-style, the sharing the reference's block
        # tables exist for): refcounted pages + an LRU index mapping a
        # full page's token-CHAIN key -> page id.  Only FULL pages are
        # ever shared, so shared pages are immutable — decode writes
        # land at lens >= the shared region, in private pages; no
        # copy-on-write needed.  The index holds one ref per cached
        # page; rows holding it add theirs.
        self.refs = np.zeros(num_pages, np.int64)
        from collections import OrderedDict
        self._prefix_index: "OrderedDict" = OrderedDict()
        # chain structure for LEAF-FIRST eviction: evicting a chain's
        # head would orphan its tail (lookups break at the missing
        # head while the tail pages stay pinned).  The structure spans
        # BOTH tiers (a key lives in exactly one of _prefix_index /
        # _host_prefix_index at a time): parent link + live-children
        # sets, from which HBM-leaf / union-leaf checks derive.
        self._prefix_parent: dict = {}
        self._prefix_children: dict = {}
        self.prefix_hits = 0              # pages reused via the index
        # -- HOST TIER (two-tier cache, kv_offload.py) ----------------
        # a host_pages>0 pool holds demoted prefix pages and swapped-
        # out preempted rows in host RAM: 10-100x the device pool for
        # the cost of a DMA instead of a re-prefill
        if host_pages:
            from .kv_offload import HostPagePool
            self.host = HostPagePool(cfg, host_pages, page,
                                     self.kpool.dtype,
                                     kv_quant=kv_quant)
        else:
            self.host = None
        self._host_prefix_index: "OrderedDict" = OrderedDict()
        self._host_pinned: set = set()    # hids mid-promotion
        self._demote_pending: list = []   # (pid, hid) gathers to stage
        self._swapped: dict = {}          # handle -> swapped-row record
        self._next_swap = 0
        # live cross-cache exports (disaggregated prefill/decode KV
        # handoff): export id -> staging state; audit() accounts their
        # host pages until export_fetch/export_discard resolves them
        self._exports: dict = {}
        self._next_export = 0
        self.prefix_promotions = 0        # host->HBM page promotions
        self.swap_out_pages = 0
        self.swap_in_pages = 0
        self.swap_bytes = 0
        # device-dispatch seams, countable by tests: page-write
        # scatters (one per admission wave) and swap-in restores (one
        # per swap-in)
        self.scatter_dispatches = 0
        self.restore_dispatches = 0
        # observability hookup (an owning engine sets this to its
        # EngineMetrics; gauges over pool state are scrape-time
        # callbacks, so only the hit/miss counters touch hot paths)
        self.metrics = None

    @property
    def page_bytes(self) -> int:
        """Bytes one page costs across all layers, K + V (+ the int8
        scale planes) — the unit of the swap cost model."""
        per = (self.cfg.num_hidden_layers
               * self.cfg.num_key_value_heads * self.page
               * self.cfg.head_dim)
        b = 2 * per * self.kpool.dtype.itemsize
        if self.kv_quant == "int8":
            b += 2 * (per // self.cfg.head_dim) * 4
        return b

    def free_pages(self) -> int:
        return len(self._free)

    def available_pages(self) -> int:
        """Free pages PLUS evictable cached-prefix pages (refs==1 —
        held only by the index).  Admission gates must budget against
        this, not :meth:`free_pages`: registered prompt pages leave
        the free list permanently, and gating on the raw free list
        livelocks once the index absorbs enough of the pool."""
        evictable = sum(1 for pid in self._prefix_index.values()
                        if self.refs[pid] == 1)
        return len(self._free) + evictable

    # -- prefix caching ---------------------------------------------------
    @staticmethod
    def _chain_keys(ctx: np.ndarray, page: int):
        """Chain key per FULL page: key_i covers tokens [0, (i+1)*page)
        — position-sensitive by construction (each key hashes the whole
        prefix, not just its own page)."""
        import hashlib
        keys = []
        h = hashlib.sha1()
        for i in range(len(ctx) // page):
            h.update(np.ascontiguousarray(
                ctx[i * page:(i + 1) * page]).tobytes())
            keys.append(h.digest())
        return keys

    def _link_chain(self, key, parent) -> None:
        """(Re-)link ``key`` into the two-tier chain structure.
        Idempotent — called on every index insertion (register,
        host-refresh, promotion) because a fully-evicted parent that
        was later re-registered starts with an empty children set and
        must re-learn surviving children, or leaf-first eviction
        would take it from under them."""
        self._prefix_parent[key] = parent
        self._prefix_children.setdefault(key, set())
        if parent is not None:
            self._prefix_children.setdefault(parent, set()).add(key)

    def _drop_chain_entry(self, key) -> None:
        """Remove ``key`` from the (two-tier) chain structure — the key
        no longer exists in either index."""
        parent = self._prefix_parent.pop(key, None)
        if parent is not None and parent in self._prefix_children:
            self._prefix_children[parent].discard(key)
        self._prefix_children.pop(key, None)

    def _host_free(self, hid: int) -> None:
        """Free a host page, dropping any still-deferred demotion
        gather targeting it (the content is being discarded — letting
        the stale gather land later would clobber the slot's next
        tenant)."""
        if self._demote_pending:
            self._demote_pending = [
                (p, h) for p, h in self._demote_pending if h != hid]
        self.host.free(hid)

    def _host_evict_one(self) -> bool:
        """Free the oldest union-leaf host-tier prefix page (hids
        pinned mid-promotion are skipped).  Leaf-first for the same
        reason as the device tier: chains must stay lookup-able."""
        for key in list(self._host_prefix_index):
            hid = self._host_prefix_index[key]
            if hid in self._host_pinned:
                continue
            if self._prefix_children.get(key):
                continue                      # has live children
            del self._host_prefix_index[key]
            self._drop_chain_entry(key)
            self._host_free(hid)
            return True
        return False

    def _host_alloc(self) -> int:
        """Pop a host page, evicting host-tier cached prefixes
        (oldest leaf first) when the host free list is dry."""
        while not self.host._free:
            if not self._host_evict_one():
                break
        return self.host.alloc()

    def host_available(self) -> int:
        """Host pages obtainable right now: free + evictable cached
        host-tier prefix pages (iterated leaf-first eviction can drain
        every unpinned entry)."""
        if self.host is None:
            return 0
        if faults.active("host_pool_full"):
            # injected exhaustion: the cost model and swap-out
            # preconditions read zero capacity and degrade to
            # recompute-style preemption (testing/faults.py)
            return 0
        return (self.host.free_pages()
                + len(self._host_prefix_index)
                - len(self._host_pinned))

    def _evict_one_prefix(self) -> bool:
        """Take the oldest LEAF cached-prefix page held only by the
        index out of HBM — DEMOTED to the host tier when one is
        attached (a later lookup promotes it back: the prefix cache's
        effective capacity is host RAM), freed outright otherwise.
        Leaf-first keeps chains lookup-able: a head eviction would
        orphan every dependent tail entry.  "Leaf" here means no child
        resident in HBM — children already demoted to the host tier
        don't pin their parent on-device."""
        for key in list(self._prefix_index):
            pid = self._prefix_index[key]
            if self.refs[pid] != 1:
                continue
            if any(c in self._prefix_index
                   for c in self._prefix_children.get(key, ())):
                continue
            del self._prefix_index[key]
            demoted = False
            if self.host is not None and self.host_available() > 0:
                hid = self._host_alloc()
                # DEFERRED gather: demotions triggered by one
                # allocator call coalesce into a single batched
                # dispatch (_flush_demotions) instead of one per page
                self._demote_pending.append((pid, hid))
                self._host_prefix_index[key] = hid
                demoted = True                # chain entry survives
            else:
                self._drop_chain_entry(key)
            self.refs[pid] = 0
            self._free.append(pid)
            # traffic is counted at flush time (_flush_demotions): a
            # deferred demotion dropped before its gather runs (host
            # eviction of the just-demoted entry) never moved bytes
            return True
        return False

    def _count_swap(self, n: int, out: bool) -> None:
        """Single site for swap-traffic bookkeeping (plain counters +
        registry instruments stay in lockstep)."""
        nbytes = n * self.page_bytes
        if out:
            self.swap_out_pages += n
        else:
            self.swap_in_pages += n
        self.swap_bytes += nbytes
        if self.metrics is not None:
            (self.metrics.swap_out_pages if out
             else self.metrics.swap_in_pages).inc(n)
            self.metrics.swap_bytes.inc(nbytes)

    def _flush_demotions(self) -> None:
        """Stage every demotion deferred by ``_evict_one_prefix`` as
        ONE batched gather.  Must run before any pool WRITE dispatch
        (a demoted page may already be reallocated — a write landing
        first would corrupt the host copy), so the write seams call
        this too; allocator entry points flush on exit."""
        if not self._demote_pending:
            return
        pending, self._demote_pending = self._demote_pending, []
        self._stage_swap_out([p for p, _ in pending],
                             [h for _, h in pending])
        self._count_swap(len(pending), out=True)

    def _stage_swap_out(self, pids, hids) -> None:
        """ONE batched device gather of ``pids`` staged as an async
        copy into host pages ``hids`` — the device→HBM→host leg of a
        swap, overlappable with in-flight decode steps (the engine
        flushes at its scheduler-mutation points)."""
        ids = jnp.asarray(np.asarray(pids, np.int32))
        kg = self.kpool[:, ids]
        vg = self.vpool[:, ids]
        if self.kv_quant == "int8":
            self.host.stage(hids, kg, vg, self.kscale[:, ids],
                            self.vscale[:, ids])
        else:
            self.host.stage(hids, kg, vg)

    def _restore_pages(self, pids, k, v, ks, vs) -> None:
        """ONE batched ``.at[ids].set`` restore dispatch (per pool
        tensor) writing host page blocks back into device pages
        ``pids`` — the host→device leg of a swap-in / promotion."""
        self._flush_demotions()       # gathers must precede pool writes
        ids = jnp.asarray(np.asarray(pids, np.int32))
        self.kpool = self.kpool.at[:, ids].set(
            jnp.asarray(k).astype(self.kpool.dtype))
        self.vpool = self.vpool.at[:, ids].set(
            jnp.asarray(v).astype(self.vpool.dtype))
        if self.kv_quant == "int8":
            self.kscale = self.kscale.at[:, ids].set(jnp.asarray(ks))
            self.vscale = self.vscale.at[:, ids].set(jnp.asarray(vs))
        self.restore_dispatches += 1

    def _page_alloc(self) -> int:
        """Pop a free page, evicting cached prefixes (oldest leaf
        first) when the free list is dry."""
        if not self._free:
            self._evict_one_prefix()
        if not self._free:
            raise RuntimeError("KV page pool exhausted")
        return self._free.pop()

    def alloc_row_prefix(self, b: int, ctx: np.ndarray) -> int:
        """Like :meth:`alloc_row` but REUSES cached prefix pages: the
        longest chain-key run found in the index is shared (increfed),
        only the remainder gets fresh pages.  A key that misses in HBM
        but hits the HOST TIER is PROMOTED: a fresh device page is
        claimed, its content restored from host RAM (one batched
        restore dispatch for the whole row), and the key moves back
        into the HBM index — a cache depth of host-RAM pages at the
        cost of a DMA.  Returns the number of reused TOKENS (a page
        multiple) — the caller prefills from there.

        Hit/miss stats are recorded only after the WHOLE claim commits
        — a pool-exhaustion rollback must not leave hits counted for
        pages the row never kept."""
        page = self.page
        L = len(ctx)
        need = (L + page - 1) // page
        if need > self.pages_max:
            raise ValueError(f"length {L} exceeds pages_max")
        self.release_row(b)
        keys = self._chain_keys(ctx, page)
        plan = []                  # chain-ordered ("share"|"promote")
        for key in keys:
            pid = self._prefix_index.get(key)
            if pid is not None:
                self._prefix_index.move_to_end(key)  # LRU touch
                plan.append(("share", key, pid))
                continue
            hid = self._host_prefix_index.get(key)
            if hid is not None:
                self._host_prefix_index.move_to_end(key)
                plan.append(("promote", key, hid))
                continue
            break
        # a fully-cached page-aligned context would leave nothing to
        # prefill — the engine needs the LAST page's K/V computed to
        # produce next-token logits anyway, so keep >=1 page private
        if L % page == 0 and len(plan) == len(keys) and plan:
            plan.pop()
        promos = [(j, key, hid) for j, (kind, key, hid)
                  in enumerate(plan) if kind == "promote"]
        # pin promo source pages: allocs below may demote other pages
        # to the host tier, and host-side eviction must not take the
        # very pages we are about to read
        self._host_pinned.update(h for _, _, h in promos)
        row = [None] * need        # final page id per table position
        try:
            # 1. claim the HBM hits FIRST — an incref lifts them above
            #    the demotion threshold before any alloc below runs
            for j, (kind, key, val) in enumerate(plan):
                if kind == "share":
                    self.refs[val] += 1
                    row[j] = val
            # 2. promotions: claim device pages, then ONE batched
            #    restore, then move the index entries host -> HBM
            promo_pids = []
            try:
                for _ in promos:
                    promo_pids.append(self._page_alloc())
            except RuntimeError:
                self._free.extend(promo_pids)
                for j, (kind, key, val) in enumerate(plan):
                    if kind == "share":
                        self.refs[val] -= 1   # index ref remains >= 1
                raise
            if promos:
                hids = [h for _, _, h in promos]
                k, v, ks, vs = self.host.gather(hids)
                self._restore_pages(promo_pids, k, v, ks, vs)
                for (j, key, hid), pid in zip(promos, promo_pids):
                    del self._host_prefix_index[key]
                    self._host_free(hid)
                    self._prefix_index[key] = pid
                    self._link_chain(key, keys[j - 1] if j else None)
                    self.refs[pid] = 2        # index ref + row ref
                    row[j] = pid
                self.prefix_promotions += len(promos)
                self._count_swap(len(promos), out=False)
            # 3. fresh pages for the remainder
            try:
                for j in range(len(plan), need):
                    pid = self._page_alloc()
                    self.refs[pid] += 1
                    row[j] = pid
            except RuntimeError:
                # roll back the row's claim; promoted pages keep their
                # index ref — they are valid cached pages either way
                for pid in row:
                    if pid is None:
                        continue
                    self.refs[pid] -= 1
                    if self.refs[pid] == 0:
                        self._free.append(pid)
                raise
        finally:
            self._host_pinned.difference_update(
                h for _, _, h in promos)
            self._flush_demotions()
        self._owned[b] = row
        for j, pid in enumerate(row):
            self.tables[b, j] = pid
        self.tables_version += 1
        self.lens[b] = L
        # stats AFTER the claim committed (satellite fix: a rollback
        # used to leave hits counted for pages the row never kept)
        self.prefix_hits += len(plan)
        if self.metrics is not None:
            self.metrics.prefix_hit_pages.inc(len(plan))
            self.metrics.prefix_miss_pages.inc(need - len(plan))
        return len(plan) * page

    def register_prefix(self, b: int, ctx: np.ndarray) -> None:
        """Insert row ``b``'s FULL pages into the prefix index (one
        index ref each) so later admissions sharing the prefix reuse
        them.  A key already demoted to the host tier is REFRESHED:
        the host copy is dropped in favour of the identical,
        freshly-written device page (a key lives in exactly one
        tier)."""
        page = self.page
        keys = self._chain_keys(ctx, page)
        for j, key in enumerate(keys):
            if key in self._prefix_index:
                continue
            pid = int(self.tables[b, j])
            hid = self._host_prefix_index.pop(key, None)
            if hid is not None:
                self._host_free(hid)      # same content by key
            self._prefix_index[key] = pid
            self._link_chain(key, keys[j - 1] if j else None)
            self.refs[pid] += 1

    def alloc_row(self, b: int, length: int) -> None:
        """Claim pages for ``length`` tokens on row ``b`` (prefill)."""
        need = (length + self.page - 1) // self.page
        if need > self.pages_max:
            raise ValueError(f"length {length} exceeds pages_max")
        # uniform failure contract (shared with alloc_row_prefix): on
        # pool exhaustion the partial claim rolls back and the row is
        # left EMPTY
        self.release_row(b)
        try:
            for j in range(need):
                pid = self._page_alloc()
                self.refs[pid] += 1
                self._owned[b].append(pid)
                self.tables[b, j] = pid
        except RuntimeError:
            self.release_row(b)     # roll back the partial claim
            raise
        finally:
            self._flush_demotions()
        self.tables_version += 1
        self.lens[b] = length

    def ensure_capacity(self, b: int, new_tokens: int = 1) -> None:
        """Grow row ``b`` so the next ``new_tokens`` writes (slots
        ``lens[b] .. lens[b]+new_tokens-1``) have pages."""
        self.ensure_capacity_batch([(b, new_tokens)])

    def ensure_capacity_batch(self, needs) -> None:
        """Grow EVERY ``(row, new_tokens)`` in ``needs`` as one
        coalesced claim: however many rows grow (and whatever the
        per-row horizon pre-claim depth), ``tables_version`` bumps at
        most ONCE — each bump invalidates the overlap loop's
        device-resident tables copy and forces a re-upload, so the
        old per-slot ``ensure_capacity`` loop paid one re-upload per
        growing row per tick.  On pool exhaustion mid-claim the rows
        already grown keep their pages (they are owned and accounted;
        the caller's preemption fallback reclaims space and retries)
        and ``RuntimeError`` propagates; the version still bumps so a
        device-resident tables copy can never miss the partial
        growth."""
        grew = False
        try:
            for b, new_tokens in needs:
                need = (int(self.lens[b]) + new_tokens - 1) \
                    // self.page + 1
                if need > self.pages_max:
                    raise ValueError(
                        f"row {b}: {int(self.lens[b])} + {new_tokens} "
                        f"tokens needs {need} pages > pages_max "
                        f"{self.pages_max}")
                while len(self._owned[b]) < need:
                    pid = self._page_alloc()
                    self.refs[pid] += 1
                    self.tables[b, len(self._owned[b])] = pid
                    self._owned[b].append(pid)
                    grew = True
        finally:
            self._flush_demotions()
            if grew:
                self.tables_version += 1

    def write_row_pages(self, slot: int, ks, vs, L: int,
                        first_page: int = 0) -> None:
        """Write one row's prefill K/V (``[Lyr, S>=L, nkv, d]``, layer-
        major) into its allocated pages, quantising when the cache is
        int8.  ``first_page`` offsets into the row's table (chunked
        prefill appends chunk c at page c*chunk/page).  One entry of
        :meth:`write_pages_batch` — multi-row admission waves use the
        batch form directly so the whole wave is ONE scatter
        dispatch."""
        self.write_pages_batch([(slot, ks, vs, L, first_page)])

    @RecordEvent("admit.write_pages")
    def write_pages_batch(self, entries) -> None:
        """Coalesced page write for a whole admission wave: every
        entry's ``(slot, ks, vs, L, first_page)`` K/V lands through
        ONE batched ``.at[ids].set`` scatter per pool tensor (the
        packed lane used to pay one device dispatch per segment).
        Single source of the page-layout transpose — generate_paged's
        batched multi-row write mirrors it for local
        (donation-managed) pool variables."""
        page = self.page
        ids_all, kss, vss = [], [], []
        for slot, ks, vs, L, first_page in entries:
            npg = (L + page - 1) // page
            Wp = npg * page
            if ks.shape[1] < Wp:
                raise ValueError(
                    f"prefill output covers {ks.shape[1]} slots but "
                    f"the row needs {Wp} (pad the prefill to a page "
                    f"multiple)")
            kss.append(ks[:, :Wp])
            vss.append(vs[:, :Wp])
            ids_all.append(
                self.tables[slot, first_page:first_page + npg].copy())
        ks = kss[0] if len(kss) == 1 else jnp.concatenate(kss, axis=1)
        vs = vss[0] if len(vss) == 1 else jnp.concatenate(vss, axis=1)
        ids = np.concatenate(ids_all)
        npg = ids.shape[0]
        ks_s = vs_s = None
        if self.kv_quant == "int8":
            from ..ops.pallas.paged_attention import quantize_kv_token
            ks, ks_s = quantize_kv_token(ks)
            vs, vs_s = quantize_kv_token(vs)
        Lyr, nkv, d = ks.shape[0], ks.shape[2], ks.shape[3]
        kb = ks.reshape(Lyr, npg, page, nkv, d).transpose(0, 1, 3, 2, 4)
        vb = vs.reshape(Lyr, npg, page, nkv, d).transpose(0, 1, 3, 2, 4)
        if self.kv_quant == "int8":
            ks_s = ks_s.reshape(Lyr, npg, page, nkv).transpose(0, 1, 3, 2)
            vs_s = vs_s.reshape(Lyr, npg, page, nkv).transpose(0, 1, 3, 2)
        self._scatter_pages(ids, kb, vb, ks_s, vs_s)

    def _scatter_pages(self, ids, kb, vb, ks_s=None, vs_s=None) -> None:
        """The page-write device-dispatch seam (tests count calls
        through it: one per admission wave)."""
        self._flush_demotions()       # gathers must precede pool writes
        self.kpool = self.kpool.at[:, ids].set(kb.astype(self.kpool.dtype))
        self.vpool = self.vpool.at[:, ids].set(vb.astype(self.vpool.dtype))
        if self.kv_quant == "int8":
            self.kscale = self.kscale.at[:, ids].set(ks_s)
            self.vscale = self.vscale.at[:, ids].set(vs_s)
        self.scatter_dispatches += 1

    def release_row(self, b: int) -> None:
        for pid in self._owned[b]:
            self.refs[pid] -= 1
            if self.refs[pid] == 0:     # cached/shared pages stay put
                self._free.append(pid)
        self._owned[b] = []
        self.tables[b] = 0
        self.lens[b] = 0
        self.tables_version += 1

    # -- host-tier row swap (recompute-free preemption) -------------------
    def private_pages(self, b: int) -> int:
        """Pages of row ``b``'s written context held ONLY by the row
        (refs==1) — exactly what a :meth:`swap_out_row` must move to
        the host tier.  The engine's preemption cost model and the
        swap precondition both read this so they can never diverge."""
        L = int(self.lens[b])
        npg = (L + self.page - 1) // self.page
        return sum(1 for pid in self._owned[b][:npg]
                   if self.refs[pid] == 1)

    def swap_out_row(self, b: int) -> int:
        """Park row ``b``'s cached context in the host tier instead of
        destroying it: PRIVATE pages (refs==1) ride one batched device
        gather + async host copy, SHARED pages (prefix-cache pages,
        refs>1) stay on-device with the row's ref carried by the swap
        record (the held ref keeps them from being demoted under us).
        The row itself is released.  Returns a handle for
        :meth:`swap_in_row`.

        Raises ``RuntimeError`` (before mutating anything) when the
        host tier cannot hold the private pages — the caller's cost
        model should have checked :meth:`host_available` and fallen
        back to recompute-style preemption."""
        if self.host is None:
            raise RuntimeError("no host page tier attached")
        faults.fire("swap_out")       # injected: raises before mutation
        page = self.page
        L = int(self.lens[b])
        npg = (L + page - 1) // page
        data = self._owned[b][:npg]
        private = self.private_pages(b)
        if self.host_available() < private:
            raise RuntimeError(
                f"host tier full: {private} pages to swap, "
                f"{self.host_available()} available")
        entries = []
        dev_ids, host_ids = [], []
        for pid in data:
            if self.refs[pid] > 1:
                entries.append(("dev", pid))      # carry the row's ref
            else:
                hid = self._host_alloc()
                entries.append(("host", hid))
                dev_ids.append(pid)
                host_ids.append(hid)
        if dev_ids:
            self._stage_swap_out(dev_ids, host_ids)
            for pid in dev_ids:
                self.refs[pid] = 0
                self._free.append(pid)
            self._count_swap(len(dev_ids), out=True)
        for pid in self._owned[b][npg:]:          # unwritten growth
            self.refs[pid] -= 1
            if self.refs[pid] == 0:
                self._free.append(pid)
        self._owned[b] = []
        self.tables[b] = 0
        self.lens[b] = 0
        self.tables_version += 1
        handle = self._next_swap
        self._next_swap += 1
        self._swapped[handle] = {"entries": entries, "lens": L}
        return handle

    def swap_pages_needed(self, handle: int) -> int:
        """Device pages a :meth:`swap_in_row` of this record must
        claim (its "dev" entries already hold theirs)."""
        return sum(1 for kind, _ in self._swapped[handle]["entries"]
                   if kind == "host")

    def swap_ctx_len(self, handle: int) -> int:
        return int(self._swapped[handle]["lens"])

    def swap_in_row(self, b: int, handle: int) -> int:
        """Rebuild row ``b`` from a swap record: fresh device pages
        for the host-tier entries, restored with ONE batched
        ``.at[ids].set`` dispatch; on-device ("dev") entries slot
        their held pages straight back into the table.  ZERO prefill
        tokens.  Returns the restored context length.  On device-pool
        exhaustion the record is left intact and ``RuntimeError``
        propagates (the caller falls back to recompute)."""
        faults.fire("swap_in")        # injected: raises before mutation
        rec = self._swapped[handle]
        entries = rec["entries"]
        self.release_row(b)
        fresh = []
        try:
            for _ in range(sum(1 for kind, _ in entries
                               if kind == "host")):
                fresh.append(self._page_alloc())
        except RuntimeError:
            self._free.extend(fresh)
            raise
        finally:
            self._flush_demotions()
        del self._swapped[handle]
        it = iter(fresh)
        restore_ids, hids = [], []
        for j, (kind, val) in enumerate(entries):
            if kind == "host":
                pid = next(it)
                self.refs[pid] += 1
                restore_ids.append(pid)
                hids.append(val)
            else:
                pid = val                 # the record's ref becomes
                #                           the row's ref
            self.tables[b, j] = pid
            self._owned[b].append(pid)
        if restore_ids:
            k, v, ks, vs = self.host.gather(hids)
            self._restore_pages(restore_ids, k, v, ks, vs)
            for hid in hids:
                self._host_free(hid)
            self._count_swap(len(restore_ids), out=False)
        self.lens[b] = rec["lens"]
        self.tables_version += 1
        return int(rec["lens"])

    def discard_swap(self, handle: int) -> None:
        """Drop a swap record without restoring it (the owning request
        falls back to recompute): host pages free, held device refs
        release."""
        rec = self._swapped.pop(handle)
        for kind, val in rec["entries"]:
            if kind == "dev":
                self.refs[val] -= 1
                if self.refs[val] == 0:
                    self._free.append(val)
            else:
                self._host_free(val)

    # -- cross-cache KV handoff (disaggregated prefill/decode) ------------
    def export_row(self, b: int) -> dict:
        """Stage row ``b``'s WHOLE written context (shared prefix pages
        included — a foreign cache holds none of our pages) for a
        CROSS-CACHE handoff and release the row.  Unlike
        :meth:`swap_out_row`, the result is portable: pages destined
        for another engine's pool, not a parked record in this one.

        The gather stages through the host tier's async D2H path when
        capacity allows (the copy then rides under neighbouring
        dispatches — the same T3 discipline swap-out uses; the
        disaggregation coordinator materialises one tick later,
        after the next prefill wave has been dispatched over it) and
        falls back to a synchronous fetch otherwise.  Returns an
        opaque export state for :meth:`export_fetch` /
        :meth:`export_discard`; live exports are tracked so
        :meth:`audit` accounts their host pages."""
        page = self.page
        L = int(self.lens[b])
        npg = (L + page - 1) // page
        pids = self._owned[b][:npg]
        state = {"id": self._next_export, "lens": L, "pages": npg}
        self._next_export += 1
        if npg and self.host is not None \
                and self.host_available() >= npg:
            hids = [self._host_alloc() for _ in range(npg)]
            self._stage_swap_out(pids, hids)
            state["hids"] = hids
        elif npg:
            ids = jnp.asarray(np.asarray(pids, np.int32))
            state["k"] = np.asarray(self.kpool[:, ids])
            state["v"] = np.asarray(self.vpool[:, ids])
            if self.kv_quant == "int8":
                state["ks"] = np.asarray(self.kscale[:, ids])
                state["vs"] = np.asarray(self.vscale[:, ids])
        self.release_row(b)
        self._exports[state["id"]] = state
        return state

    def export_fetch(self, state: dict):
        """Materialise an export into portable numpy blocks
        ``(k, v, kscale, vscale, ctx_len)`` (scales ``None`` for
        non-int8 pools) and free the staging host pages.  This is the
        handoff's one blocking point — the host-pool flush commits
        copies that have been riding under dispatches since
        :meth:`export_row`."""
        self._exports.pop(state["id"], None)
        if "hids" in state:
            k, v, ks, vs = self.host.gather(state["hids"])
            for hid in state["hids"]:
                self._host_free(hid)
            return k, v, ks, vs, state["lens"]
        return (state.get("k"), state.get("v"), state.get("ks"),
                state.get("vs"), state["lens"])

    def export_discard(self, state: dict) -> None:
        """Drop an un-shipped export (its request degraded to a
        colocated re-prefill, or its prefill engine died): staging
        host pages free, nothing leaks (audit-verified)."""
        if self._exports.pop(state["id"], None) is None:
            return                     # already fetched or discarded
        for hid in state.get("hids", ()):
            self._host_free(hid)

    def adopt_swap(self, k, v, kscale, vscale, length: int) -> int:
        """Import a shipped context into THIS cache's host tier as a
        swap record (all-``host`` entries) — the receiving half of a
        KV handoff.  The owning engine maps the returned handle to its
        request and re-admits through the ordinary ``_admit_swapped``
        path: ONE batched restore scatter, zero prefill tokens, the
        exact machinery preemption resume already trusts.  Raises
        ``RuntimeError`` (before mutating) when there is no host tier
        or it cannot hold the pages — the caller degrades the request
        to a colocated re-prefill."""
        if self.host is None:
            raise RuntimeError(
                "adopt_swap needs a host page tier on the receiving "
                "cache (PagedKVCache(host_pages=N)) — handoff records "
                "park there until their batched restore")
        npg = (int(length) + self.page - 1) // self.page
        if self.host_available() < npg:
            raise RuntimeError(
                f"host tier full: {npg} pages to adopt, "
                f"{self.host_available()} available")
        if k.dtype != self.host.kbuf.dtype:
            raise ValueError(
                f"handoff dtype {k.dtype} != pool dtype "
                f"{self.host.kbuf.dtype} (source and destination "
                f"caches must share dtype/kv_quant for a bitwise "
                f"restore)")
        if (kscale is None) == (self.kv_quant == "int8"):
            raise ValueError(
                "handoff kv_quant mismatch: int8 records need their "
                "scale planes and fp records must not carry them")
        hids = [self._host_alloc() for _ in range(npg)]
        self.host.kbuf[:, hids] = k
        self.host.vbuf[:, hids] = v
        if self.kv_quant == "int8":
            self.host.kscale[:, hids] = kscale
            self.host.vscale[:, hids] = vscale
        handle = self._next_swap
        self._next_swap += 1
        self._swapped[handle] = {
            "entries": [("host", h) for h in hids],
            "lens": int(length)}
        return handle

    # -- page-accounting audit --------------------------------------------
    def audit(self) -> dict:
        """Check every page-accounting invariant and return pool
        stats; raises ``AssertionError`` on the first violation.  Used
        by the fuzz test and handy when debugging allocator state:

        * ``refs[pid] == #rows owning + #index entries + #swap-record
          "dev" holds`` for every page;
        * the free list is duplicate-free, never contains page 0, and
          intersects neither owned nor index nor swap-held pages;
        * a page owned by two rows must be a prefix-index page (the
          immutability contract sharing relies on);
        * ``tables[b]`` mirrors ``_owned[b]`` positionally;
        * host tier: free list + (host index ∪ swap-record "host"
          pages) partition the pool exactly.
        """
        from collections import Counter
        free = self._free
        assert len(set(free)) == len(free), "free list has duplicates"
        assert 0 not in set(free), "reserved page 0 on the free list"
        owned_cnt: Counter = Counter()
        for b, row in enumerate(self._owned):
            assert len(set(row)) == len(row), \
                f"row {b} owns a page twice"
            for j, pid in enumerate(row):
                assert int(self.tables[b, j]) == pid, \
                    f"tables[{b},{j}]={self.tables[b, j]} != owned {pid}"
            owned_cnt.update(row)
        index_cnt = Counter(self._prefix_index.values())
        swap_cnt = Counter(pid for rec in self._swapped.values()
                           for kind, pid in rec["entries"]
                           if kind == "dev")
        free_set = set(free)
        for pid in range(self.num_pages):
            want = owned_cnt[pid] + index_cnt[pid] + swap_cnt[pid]
            assert int(self.refs[pid]) == want, \
                (f"page {pid}: refs {int(self.refs[pid])} != owned "
                 f"{owned_cnt[pid]} + index {index_cnt[pid]} + "
                 f"swapped {swap_cnt[pid]}")
            if pid in free_set:
                assert want == 0, f"page {pid} free while referenced"
        for pid, c in owned_cnt.items():
            if c > 1:
                assert index_cnt[pid] > 0, \
                    (f"page {pid} owned by {c} rows but not a prefix-"
                     f"index page (sharing is index-mediated only)")
        # chain structure: a live key whose parent is also live must
        # sit in the parent's children set, or leaf-first eviction
        # could take the parent from under it
        live = set(self._prefix_index) | set(self._host_prefix_index)
        for key in live:
            parent = self._prefix_parent.get(key)
            if parent is not None and parent in live:
                assert key in self._prefix_children.get(parent, ()), \
                    "prefix chain edge missing (parent unaware of " \
                    "live child)"
        stats = {"free": len(free), "owned": sum(owned_cnt.values()),
                 "indexed": len(self._prefix_index),
                 "swap_records": len(self._swapped)}
        if self.host is not None:
            hfree = self.host._free
            assert len(set(hfree)) == len(hfree), \
                "host free list has duplicates"
            used = list(self._host_prefix_index.values()) + [
                hid for rec in self._swapped.values()
                for kind, hid in rec["entries"] if kind == "host"] + [
                hid for st in self._exports.values()
                for hid in st.get("hids", ())]
            assert len(set(used)) == len(used), \
                "host page held twice"
            assert not (set(hfree) & set(used)), \
                "host page free while in use"
            assert len(hfree) + len(used) == self.host.num_pages, \
                "host pages leaked"
            stats["host_free"] = len(hfree)
            stats["host_indexed"] = len(self._host_prefix_index)
        return stats


def _rope_rows(x, theta, pos):
    """RoPE for one token per row at per-row positions ``pos [B]``;
    x [B, 1, n, d]."""
    d = x.shape[-1]
    with jax.named_scope("rope"):
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        freqs = pos.astype(jnp.float32)[:, None] * inv[None]     # [B, d/2]
        cos = jnp.cos(freqs)[:, None, None, :]
        sin = jnp.sin(freqs)[:, None, None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
        return jnp.concatenate([x1f * cos - x2f * sin,
                                x2f * cos + x1f * sin],
                               -1).astype(x.dtype)


def _embed_rows(embed, toks, dt):
    """Embedding rows of ``toks`` in the compute dtype."""
    with jax.named_scope("embed"):
        return jnp.take(embed, toks, axis=0).astype(dt)


def _decode_layer(cfg, bp, kp, vp, xc, tables, lens, page_ids, slots,
                  ks=None, vs=None):
    """One transformer layer of a paged decode step: append this
    token's K/V into the layer's pool pages, then paged attention +
    block FFN.  Shared by the per-token serving step and the fused
    generation scan (single source of the decode math).  With
    ``ks``/``vs`` (scale pools) the pages are int8 and the append
    quantises per (row, head)."""
    from ..ops.pallas.paged_attention import (
        paged_decode_attention, paged_decode_attention_q8,
        quantize_kv_token)

    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    dt = cfg.dtype
    B = xc.shape[0]
    with jax.named_scope("block"):
        with jax.named_scope("attn_qkv"):
            y = _rms_norm(xc, bp["ln1"], cfg.rms_norm_eps)
            q = _mm(y, bp["wq"], dt).reshape(B, 1, n, d)
            k = _mm(y, bp["wk"], dt).reshape(B, 1, nkv, d)
            v = _mm(y, bp["wv"], dt).reshape(B, 1, nkv, d)
        q = _rope_rows(q, cfg.rope_theta, lens)
        k = _rope_rows(k, cfg.rope_theta, lens)
        if ks is not None:
            with jax.named_scope("kv_write"):
                kq, kss = quantize_kv_token(k[:, 0])
                vq, vss = quantize_kv_token(v[:, 0])
                kp = kp.at[page_ids, :, slots, :].set(kq)
                vp = vp.at[page_ids, :, slots, :].set(vq)
                ks = ks.at[page_ids, :, slots].set(kss)
                vs = vs.at[page_ids, :, slots].set(vss)
            with jax.named_scope("paged_attn"):
                attn = paged_decode_attention_q8(
                    q[:, 0], kp, vp, ks, vs, tables, lens + 1)
        else:
            with jax.named_scope("kv_write"):
                kp = kp.at[page_ids, :, slots, :].set(
                    k[:, 0].astype(kp.dtype))
                vp = vp.at[page_ids, :, slots, :].set(
                    v[:, 0].astype(vp.dtype))
            with jax.named_scope("paged_attn"):
                attn = paged_decode_attention(q[:, 0], kp, vp, tables,
                                              lens + 1)
        out = _block_post_attn(bp, xc, attn[:, None], cfg)
    return out, kp, vp, ks, vs


def _pick_token(logits, temperature, key, top_k: int = 0,
                top_p: float = 1.0):
    """Greedy / temperature / top-k / nucleus sampling, all as static
    lax ops (the sampler compiles into the decode step — reference:
    the sampling ops the generation ops feed,
    incubate top_p_sampling).  ``top_k=0`` disables k-filtering;
    ``top_p=1.0`` disables nucleus filtering; both compose."""
    with jax.named_scope("sample"):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        logits = logits / temperature
        if top_k and top_k > 0:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p < 1.0:
            sorted_l = jnp.sort(logits, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(sorted_l, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep the smallest prefix with cumulative mass >= top_p (the
            # first token is always kept: cum shifted right by one)
            keep = jnp.concatenate(
                [jnp.zeros_like(cum[..., :1]), cum[..., :-1]], -1) < top_p
            cutoff = jnp.min(jnp.where(keep, sorted_l, jnp.inf), axis=-1,
                             keepdims=True)
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return jax.random.categorical(key, logits, -1)


def _cfg_key(cfg) -> str:
    import dataclasses
    return repr(sorted(dataclasses.asdict(cfg).items(), key=repr))


_step_cache: dict = {}
_gen_cache: dict = {}


def _build_step_fns(cfg: LlamaPretrainConfig, temperature: float,
                    with_logits: bool, top_k: int, top_p: float):
    """Raw (unjitted) per-token step bodies ``(step, step_q8)`` —
    shared by the synchronous factory below and the dispatch-ahead
    :func:`make_paged_decode_step_async` wrapper (single source of the
    decode-step math)."""
    dt = cfg.dtype

    def tail(x, params):
        with jax.named_scope("logits"):
            h = _rms_norm(x[:, 0], params["final_norm"],
                          cfg.rms_norm_eps)
            return _mm(h, params["lm_head"], dt).astype(jnp.float32)

    # pools ride the scan xs->ys (per-layer slices update in place
    # under donation — a carry formulation was measured to copy the
    # full pool per layer, 10x slower); the append is one batched
    # scatter
    def step(params, kpool, vpool, tables, lens, tok, key):
        B = tok.shape[0]
        page = kpool.shape[3]
        x = _embed_rows(params["embed"], tok[:, None], dt)
        page_ids = tables[jnp.arange(B), lens // page]       # [B]
        slots = lens % page                                  # [B]

        def layer(carry, inp):
            bp, kp, vp = inp
            out, kp, vp, _, _ = _decode_layer(
                cfg, bp, kp, vp, carry, tables, lens, page_ids, slots)
            return out, (kp, vp)

        with jax.named_scope("pool_carry"):
            x, (kpool, vpool) = jax.lax.scan(
                layer, x, (params["blocks"], kpool, vpool))
        logits = tail(x, params)
        nxt = _pick_token(logits, temperature, key, top_k, top_p)
        if with_logits:
            return kpool, vpool, nxt, logits
        return kpool, vpool, nxt

    def step_q8(params, kpool, vpool, kscale, vscale, tables, lens,
                tok, key):
        B = tok.shape[0]
        page = kpool.shape[3]
        x = _embed_rows(params["embed"], tok[:, None], dt)
        page_ids = tables[jnp.arange(B), lens // page]
        slots = lens % page

        def layer(carry, inp):
            bp, kp, vp, ks, vs = inp
            out, kp, vp, ks, vs = _decode_layer(
                cfg, bp, kp, vp, carry, tables, lens, page_ids, slots,
                ks, vs)
            return out, (kp, vp, ks, vs)

        with jax.named_scope("pool_carry"):
            x, (kpool, vpool, kscale, vscale) = jax.lax.scan(
                layer, x,
                (params["blocks"], kpool, vpool, kscale, vscale))
        logits = tail(x, params)
        nxt = _pick_token(logits, temperature, key, top_k, top_p)
        if with_logits:
            return kpool, vpool, kscale, vscale, nxt, logits
        return kpool, vpool, kscale, vscale, nxt

    return step, step_q8


def make_paged_decode_step(cfg: LlamaPretrainConfig,
                           temperature: float = 0.0,
                           kv_quant: Optional[str] = None,
                           with_logits: bool = False,
                           top_k: int = 0, top_p: float = 1.0):
    """Jitted ``step(params, kpool, vpool, tables, lens, tok, key)
    -> (kpool, vpool, next_tok)`` — or, with ``kv_quant="int8"``,
    ``step(params, kpool, vpool, kscale, vscale, tables, lens, tok,
    key) -> (kpool, vpool, kscale, vscale, next_tok)``.

    ``lens [B]`` = cached context per row BEFORE this token (per-row —
    continuous batching).  ``tok [B]`` = this step's input token.  The
    new K/V land at per-row slot ``lens[b]``; callers bump ``lens`` and
    the page tables on the host (PagedKVCache).

    ``with_logits=True`` appends the f32 ``[B, V]`` logits to the
    return tuple — the cache-quantisation acceptance harness bounds
    int8-vs-fp LOGIT error directly instead of counting greedy token
    agreement (round-4 verdict item 9).
    """
    hit = _step_cache.get((_cfg_key(cfg), temperature, kv_quant,
                           with_logits, top_k, top_p))
    if hit is not None:
        return hit

    step, step_q8 = _build_step_fns(cfg, temperature, with_logits,
                                    top_k, top_p)
    # memoised per (cfg, temperature, quant): jax.jit caches by function
    # identity, so returning a fresh closure every call would recompile
    # every generate
    if kv_quant == "int8":
        fn = jax.jit(step_q8, donate_argnums=(1, 2, 3, 4))
    else:
        fn = jax.jit(step, donate_argnums=(1, 2))
    _step_cache[(_cfg_key(cfg), temperature, kv_quant, with_logits,
                 top_k, top_p)] = fn
    return fn


_step_async_cache: dict = {}


def _advance_loop_state(nxt, tok, lens, active, remaining, eos):
    """The ON-DEVICE serving-loop state advance (traced into the
    async and mixed step programs — ONE definition, or the two
    lanes' done/eos semantics could silently fork): inactive rows
    keep their token, lens/remaining move only under ``active``, and
    ``done`` marks rows that just hit eos or exhausted their
    budget."""
    nxt = jnp.where(active, nxt, tok)
    lens2 = lens + active.astype(lens.dtype)
    rem2 = remaining - active.astype(remaining.dtype)
    done = active & ((nxt == eos) | (rem2 <= 0))
    return nxt, lens2, rem2, active & ~done, done


def make_paged_decode_step_async(cfg: LlamaPretrainConfig,
                                 temperature: float = 0.0,
                                 kv_quant: Optional[str] = None,
                                 top_k: int = 0, top_p: float = 1.0,
                                 mesh=None,
                                 tp_allreduce: str = "fp32"):
    """Jitted DISPATCH-AHEAD decode step: the per-token program plus a
    functional advance of the whole serving-loop state, so the engine
    can chain step k's on-device outputs straight into step k+1's
    dispatch with zero host round-trips.

    ``step(params, kpool, vpool, [kscale, vscale,] tables, lens, tok,
    active, remaining, eos, key) -> (kpool, vpool, [kscale, vscale,]
    nxt, lens', remaining', active', done)``

    * rows advance only under ``active`` (bool [B]): ``lens``/
      ``remaining`` update on-device, an inactive row keeps its token
      (its pool write lands on a dead position — same as the
      synchronous engine's idle rows);
    * ``done`` [B] bool marks active rows that just hit ``eos`` (pass
      -1 for "no eos") or exhausted their remaining-token budget — the
      stop decision the host used to make after a blocking
      ``np.asarray``;
    * ``active' = active & ~done`` feeds the next dispatch, so a
      finished row stops advancing one step later WITHOUT the host
      ever having looked.

    With ``mesh`` (mp>1) the inner per-token program is the TP
    shard_map step; the state advance runs outside the shard_map on
    replicated [B] vectors.  Multi-token stop SEQUENCES stay host-side
    (the engine flushes its pipeline when one fires).
    """
    q8 = kv_quant == "int8"
    mesh_key = mesh if (mesh is not None
                        and mesh.shape.get("mp", 1) > 1) else None
    ckey = (_cfg_key(cfg), temperature, kv_quant, top_k, top_p,
            mesh_key, tp_allreduce if mesh_key is not None else "fp32")
    hit = _step_async_cache.get(ckey)
    if hit is not None:
        return hit

    if mesh_key is not None:
        base = _build_tp_inner(cfg, mesh, temperature, kv_quant,
                               top_k, top_p,
                               tp_allreduce=tp_allreduce)
    else:
        step, step_q8 = _build_step_fns(cfg, temperature, False,
                                        top_k, top_p)
        base = step_q8 if q8 else step

    advance = _advance_loop_state

    if q8:
        def fn(params, kpool, vpool, kscale, vscale, tables, lens,
               tok, active, remaining, eos, key):
            kpool, vpool, kscale, vscale, nxt = base(
                params, kpool, vpool, kscale, vscale, tables, lens,
                tok, key)
            nxt, lens2, rem2, act2, done = advance(
                nxt, tok, lens, active, remaining, eos)
            return (kpool, vpool, kscale, vscale, nxt, lens2, rem2,
                    act2, done)

        jitted = jax.jit(fn, donate_argnums=(1, 2, 3, 4))
    else:
        def fn(params, kpool, vpool, tables, lens, tok, active,
               remaining, eos, key):
            kpool, vpool, nxt = base(params, kpool, vpool, tables,
                                     lens, tok, key)
            nxt, lens2, rem2, act2, done = advance(
                nxt, tok, lens, active, remaining, eos)
            return kpool, vpool, nxt, lens2, rem2, act2, done

        jitted = jax.jit(fn, donate_argnums=(1, 2))
    _step_async_cache[ckey] = jitted
    return jitted


_step_tp_cache: dict = {}
_tp_inner_cache: dict = {}


# -- quantized + overlapped TP collectives (EQuARX / T3) ------------------
_Q8_SCALE_BYTES = 4                    # f32 per-block scales on the wire


def _q8_ring_plan(H: int, mp: int):
    """How ``tp_allreduce="int8"`` splits one ``[B, H]`` output
    reduction: ``nchunks`` column chunks of the producing matmul (each
    chunk runs its own ring, so chunk c's ppermute hops carry no data
    dependency on chunk c+1's matmul — the T3/FLUX latency-hiding
    arrangement) and the per-block scale granularity of the int8
    wire.  Wire bytes per fp32 byte = (1 + 4/block) / 4."""
    if H % mp:
        raise ValueError(f"hidden {H} must divide over mp={mp} for "
                         "tp_allreduce='int8'")
    C = H // mp
    # chunking needs the per-rank width to split evenly too (an odd C
    # would otherwise fail only at trace time, inside a reshape)
    nchunks = 2 if (C >= 64 and C % 2 == 0) else 1
    Cc = C // nchunks
    block = 32
    while block > 1 and Cc % block:
        block //= 2
    return nchunks, block


def tp_collective_bytes_per_step(cfg, mp: int, mode: str = "fp32",
                                 batch: int = 1) -> int:
    """Analytic bytes ONE device sends per decode step in the
    per-layer OUTPUT reductions (attention ``wo`` + FFN ``w_down`` —
    the collectives ``tp_allreduce`` controls; the vocab-parallel
    embed psum and the final logits all-gather are mode-independent
    and excluded).  fp32 lane: ring all-reduce of ``[B, H]`` in the
    compute dtype, ``2*(mp-1)/mp*B*H*itemsize`` per reduction.  int8
    lane: ring reduce-scatter + all-gather whose hops carry int8
    payloads + f32 per-block scales.  Feeds the
    ``paddle_tpu_engine_tp_allreduce_bytes_total`` counter and the
    bench A/B — and the ≤~30%-of-fp32 acceptance pin.  NOTE the
    baseline dtype: the pin is against a 4-BYTE fp32 wire; a bf16
    compute dtype halves the default lane's bytes, so the same int8
    lane reads ~0.53-0.56 of a bf16 baseline (bench reports both
    ratios)."""
    if mp <= 1:
        return 0
    H, L = cfg.hidden_size, cfg.num_hidden_layers
    if mode == "fp32":
        per = (2.0 * (mp - 1) / mp * batch * H
               * np.dtype(cfg.dtype).itemsize)
    else:
        nch, block = _q8_ring_plan(H, mp)
        C = H // (mp * nch)
        per = (nch * 2.0 * (mp - 1) * batch
               * (C + (C // block) * _Q8_SCALE_BYTES))
    return int(round(2 * L * per))


def _embed_vocab_parallel(embed_l, tok, ax: str, dt):
    """Vocab-parallel embedding lookup inside shard_map (Megatron
    VocabParallelEmbedding): mask the out-of-shard ids, take locally,
    psum across the mp axis.  ``tok`` may be any shape; shared by the
    TP decode step and both TP prefill programs so their embedding
    numerics can never fork."""
    with jax.named_scope("embed"):
        V_l = embed_l.shape[0]
        start = jax.lax.axis_index(ax) * V_l
        local = tok - start
        ok = (local >= 0) & (local < V_l)
        x = jnp.take(embed_l, jnp.clip(local, 0, V_l - 1), axis=0)
        return jax.lax.psum(jnp.where(ok[..., None], x, 0).astype(dt), ax)


def _make_q8_allreduce(ax: str, mp: int, Hc: int, block: int):
    """Quantized ring all-reduce closure for one ``[B, Hc]`` chunk
    inside shard_map (EQuARX, arxiv 2506.17615): a ring
    reduce-scatter followed by a ring all-gather via ``lax.ppermute``,
    every wire hop carrying int8 payloads + f32 per-block scales
    (~(1+4/block)/4 of the fp32 bytes).  Hops are Python-unrolled so
    each ppermute is an independent graph node XLA's latency-hiding
    scheduler can run under the neighbouring matmuls."""
    C = Hc // mp
    perm = [(d, (d + 1) % mp) for d in range(mp)]

    def wire(x):                      # [B, C] f32 -> int8 + scales
        xb = x.reshape(x.shape[0], C // block, block)
        s = jnp.max(jnp.abs(xb), -1, keepdims=True) / 127.0
        s = jnp.maximum(s, 1e-30)
        q = jnp.clip(jnp.round(xb / s), -127, 127).astype(jnp.int8)
        return q, s

    def unwire(q, s):
        return (q.astype(jnp.float32) * s).reshape(q.shape[0], C)

    def allreduce(x):                 # [B, Hc] partial sums -> reduced
        B = x.shape[0]
        i = jax.lax.axis_index(ax)
        xc = x.astype(jnp.float32).reshape(B, mp, C)
        # ring REDUCE-SCATTER: after mp-1 hops rank i holds the full
        # cross-rank sum of chunk i
        acc = jnp.take(xc, (i - 1) % mp, axis=1)
        for s in range(mp - 1):
            q, sc = wire(acc)
            q = jax.lax.ppermute(q, ax, perm)
            sc = jax.lax.ppermute(sc, ax, perm)
            acc = unwire(q, sc) + jnp.take(xc, (i - s - 2) % mp,
                                           axis=1)
        # ring ALL-GATHER of the reduced shards: each chunk is wired
        # ONCE and the (q, scale) payload forwards UNCHANGED hop to
        # hop — every rank dequantizes the SAME payload, so the
        # "replicated" output is bit-identical across ranks (a rank
        # keeping its own exact acc, or re-quantizing per hop, would
        # leave the mp copies divergent and the chained decode loop
        # would fork per-shard token histories).  Arrival r holds
        # chunk (i - r) mod mp, so the reversed stack rolled by i+1
        # reads in chunk order 0..mp-1.
        q, sc = wire(acc)
        rows = [unwire(q, sc)]
        for _ in range(mp - 1):
            q = jax.lax.ppermute(q, ax, perm)
            sc = jax.lax.ppermute(sc, ax, perm)
            rows.append(unwire(q, sc))
        stacked = jnp.stack(rows[::-1], axis=0)        # [mp, B, C]
        full = jnp.roll(stacked, i + 1, axis=0)
        return full.transpose(1, 0, 2).reshape(B, Hc)

    return allreduce


def _build_tp_inner(cfg: LlamaPretrainConfig, mesh,
                    temperature: float, kv_quant: Optional[str],
                    top_k: int, top_p: float,
                    tp_allreduce: str = "fp32"):
    """Memoised UNJITTED shard_map per-token TP step — the sync
    factory jits it directly; :func:`make_paged_decode_step_async`
    composes the loop-state advance around it inside one outer jit.
    Signature matches the single-device raw step (q8 variant inserts
    the scale pools after ``vpool``).

    ``tp_allreduce="int8"`` swaps each layer's two output all-reduces
    (attention ``wo``, FFN ``w_down``) for the quantized ring
    reduce-scatter/all-gather pair (:func:`_make_q8_allreduce`), with
    the producing matmul column-chunked so chunk c's collective hops
    overlap chunk c+1's matmul in the schedule.  Opt-in: greedy
    outputs then carry quantization noise and are held to a
    statistical bar, not token-exactness (tests/test_serving_tp.py).
    """
    if tp_allreduce not in ("fp32", "int8"):
        raise ValueError("tp_allreduce must be 'fp32' or 'int8', got "
                         f"{tp_allreduce!r}")
    mp = mesh.shape["mp"]
    ckey = (_cfg_key(cfg), temperature, kv_quant, mesh, top_k, top_p,
            tp_allreduce)
    hit = _tp_inner_cache.get(ckey)
    if hit is not None:
        return hit

    from jax.sharding import PartitionSpec as P
    from .llama_pretrain import param_specs
    from ..ops.pallas.paged_attention import (
        paged_decode_attention, paged_decode_attention_q8,
        quantize_kv_token)
    q8 = kv_quant == "int8"
    q8_ar = tp_allreduce == "int8"

    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    if n % mp or nkv % mp:
        raise ValueError(f"heads {n}/{nkv} must divide over mp={mp}")
    n_l, nkv_l = n // mp, nkv // mp
    dt = cfg.dtype
    ax = "mp"

    if q8_ar:
        ar_nchunks, ar_block = _q8_ring_plan(cfg.hidden_size, mp)
        ar_fn = _make_q8_allreduce(
            ax, mp, cfg.hidden_size // ar_nchunks, ar_block)

        def reduce_out(y, w):
            # T3/FLUX arrangement: column-chunk the row-parallel
            # matmul; chunk c's ring hops are graph-independent of
            # chunk c+1's matmul, so the collective hides under the
            # neighbouring compute instead of serialising after it
            Hc = w.shape[1] // ar_nchunks
            outs = [ar_fn(_mm(y, w[:, c * Hc:(c + 1) * Hc], dt))
                    for c in range(ar_nchunks)]
            out = outs[0] if len(outs) == 1 \
                else jnp.concatenate(outs, -1)
            return out.astype(dt)
    else:
        def reduce_out(y, w):
            return jax.lax.psum(_mm(y, w, dt), ax)

    def step_local(params, kpool, vpool, kscale, vscale, tables, lens,
                   tok, key):
        B = tok.shape[0]
        page = kpool.shape[3]
        x = _embed_vocab_parallel(params["embed"], tok, ax,
                                  dt)                 # [B, H] replicated
        page_ids = tables[jnp.arange(B), lens // page]
        slots = lens % page

        def layer(carry, inp):
            if q8:
                bp, kp, vp, ks, vs = inp
            else:
                bp, kp, vp = inp
                ks = vs = None
            xc = carry
            with jax.named_scope("block"):
                out = block(bp, kp, vp, ks, vs, xc)
            return out[0], (out[1:] if q8 else out[1:3])

        def block(bp, kp, vp, ks, vs, xc):
            with jax.named_scope("attn_qkv"):
                y = _rms_norm(xc, bp["ln1"], cfg.rms_norm_eps)
                q = _mm(y, bp["wq"], dt).reshape(B, n_l, d)
                k = _mm(y, bp["wk"], dt).reshape(B, 1, nkv_l, d)
                v = _mm(y, bp["wv"], dt).reshape(B, nkv_l, d)
            q = _rope_rows(q[:, None], cfg.rope_theta, lens)[:, 0]
            k = _rope_rows(k, cfg.rope_theta, lens)[:, 0]
            if q8:
                # per LOCAL head quantisation — scales shard with the
                # heads, nothing crosses the mp axis
                with jax.named_scope("kv_write"):
                    kq, kss = quantize_kv_token(k)
                    vq, vss = quantize_kv_token(v)
                    kp = kp.at[page_ids, :, slots, :].set(kq)
                    vp = vp.at[page_ids, :, slots, :].set(vq)
                    ks = ks.at[page_ids, :, slots].set(kss)
                    vs = vs.at[page_ids, :, slots].set(vss)
                with jax.named_scope("paged_attn"):
                    attn = paged_decode_attention_q8(
                        q, kp, vp, ks, vs, tables, lens + 1)
            else:
                with jax.named_scope("kv_write"):
                    kp = kp.at[page_ids, :, slots, :].set(
                        k.astype(kp.dtype))
                    vp = vp.at[page_ids, :, slots, :].set(
                        v.astype(vp.dtype))
                with jax.named_scope("paged_attn"):
                    attn = paged_decode_attention(q, kp, vp, tables,
                                                  lens + 1)
            with jax.named_scope("attn_out"):
                xc = xc + reduce_out(attn.reshape(B, n_l * d),
                                     bp["wo"])        # row-parallel
            with jax.named_scope("mlp"):
                y2 = _rms_norm(xc, bp["ln2"], cfg.rms_norm_eps)
                act = (jax.nn.silu(_mm(y2, bp["w_gate"], dt))
                       * _mm(y2, bp["w_up"], dt))
                return (xc + reduce_out(act, bp["w_down"]), kp, vp,
                        ks, vs)

        xs = (params["blocks"], kpool, vpool)
        if q8:
            xs = xs + (kscale, vscale)
        with jax.named_scope("pool_carry"):
            x, pools = jax.lax.scan(layer, x, xs)
        with jax.named_scope("logits"):
            h = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
            logits_l = _mm(h, params["lm_head"],
                           dt).astype(jnp.float32)
            logits = jax.lax.all_gather(logits_l, ax, axis=1,
                                        tiled=True)   # [B, V]
        nxt = _pick_token(logits, temperature, key, top_k, top_p)
        if q8:
            kpool, vpool, kscale, vscale = pools
            return kpool, vpool, kscale, vscale, nxt
        kpool, vpool = pools
        return kpool, vpool, nxt

    pool_spec = P(None, None, "mp", None, None)
    scale_spec = P(None, None, "mp", None)
    if q8:
        inner = jax.shard_map(
            step_local, mesh=mesh,
            in_specs=(param_specs(cfg, pp=1), pool_spec, pool_spec,
                      scale_spec, scale_spec, P(), P(), P(), P()),
            out_specs=(pool_spec, pool_spec, scale_spec, scale_spec,
                       P()),
            check_vma=False)
    else:
        def without_scales(params, kpool, vpool, tables, lens, tok,
                           key):
            return step_local(params, kpool, vpool, None, None,
                              tables, lens, tok, key)
        inner = jax.shard_map(
            without_scales, mesh=mesh,
            in_specs=(param_specs(cfg, pp=1), pool_spec, pool_spec,
                      P(), P(), P(), P()),
            out_specs=(pool_spec, pool_spec, P()),
            check_vma=False)
    _tp_inner_cache[ckey] = inner
    return inner


def make_paged_decode_step_tp(cfg: LlamaPretrainConfig, mesh,
                              temperature: float = 0.0,
                              kv_quant: Optional[str] = None,
                              top_k: int = 0, top_p: float = 1.0,
                              tp_allreduce: str = "fp32"):
    """TENSOR-PARALLEL paged decode step: the whole per-token program is
    ONE jitted shard_map over the mesh's ``mp`` axis — Megatron-sharded
    weights (column q/k/v + gate/up, row wo/w_down with psum),
    kv-head-sharded page pools, vocab-parallel embed/unembed with an
    all-gather only on the final [B, V/mp] logits.  This is how a model
    wider than one chip serves over the paged cache — the TPU-native
    answer to the reference's fleet-executor DistModel::Run
    (fluid/distributed/fleet_executor/dist_model.h:61).

    The Pallas paged-attention kernel runs PER SHARD on local heads
    (heads are embarrassingly parallel in attention), which is why this
    is shard_map and not GSPMD auto-partitioning — XLA cannot split a
    pallas_call.  Same signature/caller contract as
    :func:`make_paged_decode_step`.

    ``tp_allreduce="int8"`` (opt-in) quantizes the per-layer output
    all-reduces into ring reduce-scatter/all-gather pairs whose hops
    carry int8 + per-block scales, chunk-interleaved with the
    producing matmuls — see :func:`_build_tp_inner`.
    """
    hit = _step_tp_cache.get((_cfg_key(cfg), temperature, kv_quant,
                              mesh, top_k, top_p, tp_allreduce))
    if hit is not None:
        return hit

    inner = _build_tp_inner(cfg, mesh, temperature, kv_quant, top_k,
                            top_p, tp_allreduce=tp_allreduce)
    if kv_quant == "int8":
        fn = jax.jit(inner, donate_argnums=(1, 2, 3, 4))
    else:
        fn = jax.jit(inner, donate_argnums=(1, 2))
    _step_tp_cache[(_cfg_key(cfg), temperature, kv_quant, mesh,
                    top_k, top_p, tp_allreduce)] = fn
    return fn


_step_multi_cache: dict = {}


def make_paged_decode_step_multi(cfg: LlamaPretrainConfig,
                                 horizon: int,
                                 temperature: float = 0.0,
                                 kv_quant: Optional[str] = None,
                                 top_k: int = 0, top_p: float = 1.0,
                                 mesh=None,
                                 tp_allreduce: str = "fp32"):
    """MULTI-TOKEN DECODE HORIZON: one jitted program advancing every
    active row by up to ``horizon`` tokens — an H-iteration
    ``lax.scan`` of the async decode body, so the serving engine pays
    ONE dispatch (and, downstream, one blocking fetch and one pass of
    host bookkeeping) per H tokens instead of per token.  This is the
    serving-loop form of :func:`make_paged_generate_fused`'s
    fuse-the-loop move: the block tables stay CONSTANT across the
    horizon (the engine pre-claims H tokens of pages per slot before
    dispatching), and the per-slot done mask folds on-device each
    micro-step so a row that hits ``eos`` or exhausts its budget
    mid-horizon stops advancing — its remaining micro-steps write
    junk at a dead position exactly like the async step's inactive
    rows.

    ``fn(params, kpool, vpool, [kscale, vscale,] tables, lens, tok,
    active, remaining, eos, key) -> (kpool, vpool, [kscale, vscale,]
    toks [H, B], dones [H, B], tok', lens', remaining', active')``

    * ``toks[h]`` is micro-step h's next-token vector, ``dones[h]``
      the rows that just hit eos/budget at micro-step h (each row
      fires at most once; after it the row is inactive and its
      ``toks[h']`` entries repeat its last token);
    * the trailing ``tok'/lens'/remaining'/active'`` are the CHAINED
      loop state after the whole horizon — the overlap pipeline feeds
      them straight into the next block's dispatch with zero host
      round-trips (``tok'`` equals ``toks[-1]`` but returns from
      inside the jit so chaining costs no extra slice dispatch);
    * multi-token stop SEQUENCES stay host knowledge: the engine
      detects them at the drain and TRIMS the row's at-most-H-1
      over-generated trailing tokens before emission (the
      chained-dispatch extra-token discipline, generalized).

    With ``mesh`` (mp>1) each micro-step is the TP shard_map step
    through the :func:`_build_tp_inner` seam (``tp_allreduce="int8"``
    included) and the state advance rides replicated — one dispatch
    per horizon on the mesh.  ``kv_quant="int8"`` threads the scale
    pools through the scan carry.
    """
    H = int(horizon)
    if H < 1:
        raise ValueError(f"horizon must be >= 1, got {horizon}")
    q8 = kv_quant == "int8"
    mesh_key = mesh if (mesh is not None
                        and mesh.shape.get("mp", 1) > 1) else None
    ckey = (_cfg_key(cfg), H, temperature, kv_quant, top_k, top_p,
            mesh_key, tp_allreduce if mesh_key is not None else "fp32")
    hit = _step_multi_cache.get(ckey)
    if hit is not None:
        return hit

    if mesh_key is not None:
        base = _build_tp_inner(cfg, mesh, temperature, kv_quant,
                               top_k, top_p,
                               tp_allreduce=tp_allreduce)
    else:
        step, step_q8 = _build_step_fns(cfg, temperature, False,
                                        top_k, top_p)
        base = step_q8 if q8 else step

    advance = _advance_loop_state   # the async lane's exact advance

    if q8:
        def fn(params, kpool, vpool, kscale, vscale, tables, lens,
               tok, active, remaining, eos, key):
            def micro(carry, sub):
                (kp, vp, ks, vs, tok, lens, active, remaining) = carry
                kp, vp, ks, vs, nxt = base(
                    params, kp, vp, ks, vs, tables, lens, tok, sub)
                nxt, lens2, rem2, act2, done = advance(
                    nxt, tok, lens, active, remaining, eos)
                return ((kp, vp, ks, vs, nxt, lens2, act2, rem2),
                        (nxt, done))

            subs = jax.random.split(key, H)
            carry0 = (kpool, vpool, kscale, vscale, tok, lens,
                      active, remaining)
            (kpool, vpool, kscale, vscale, tok_f, lens_f, act_f,
             rem_f), (toks, dones) = jax.lax.scan(micro, carry0, subs)
            return (kpool, vpool, kscale, vscale, toks, dones, tok_f,
                    lens_f, rem_f, act_f)

        jitted = jax.jit(fn, donate_argnums=(1, 2, 3, 4))
    else:
        def fn(params, kpool, vpool, tables, lens, tok, active,
               remaining, eos, key):
            def micro(carry, sub):
                kp, vp, tok, lens, active, remaining = carry
                kp, vp, nxt = base(params, kp, vp, tables, lens, tok,
                                   sub)
                nxt, lens2, rem2, act2, done = advance(
                    nxt, tok, lens, active, remaining, eos)
                return (kp, vp, nxt, lens2, act2, rem2), (nxt, done)

            subs = jax.random.split(key, H)
            carry0 = (kpool, vpool, tok, lens, active, remaining)
            (kpool, vpool, tok_f, lens_f, act_f, rem_f), \
                (toks, dones) = jax.lax.scan(micro, carry0, subs)
            return (kpool, vpool, toks, dones, tok_f, lens_f, rem_f,
                    act_f)

        jitted = jax.jit(fn, donate_argnums=(1, 2))
    _step_multi_cache[ckey] = jitted
    return jitted


def make_paged_generate_fused(cfg: LlamaPretrainConfig,
                              max_new_tokens: int,
                              temperature: float = 0.0,
                              kv_quant: Optional[str] = None,
                              top_k: int = 0, top_p: float = 1.0):
    """ONE jitted program for the whole paged generation tail: pages
    for ``lens + max_new_tokens`` are pre-allocated so the block tables
    are CONSTANT across steps, and a ``lax.scan`` advances every row at
    its own position.  This is the shape-static TPU form of continuous
    batching — the per-token :func:`make_paged_decode_step` exists for
    serving loops that admit/evict requests between steps; this fused
    form is for generation (one dispatch instead of max_new)."""
    hit = _gen_cache.get((_cfg_key(cfg), max_new_tokens, temperature,
                          kv_quant, top_k, top_p))
    if hit is not None:
        return hit

    dt = cfg.dtype
    q8 = kv_quant == "int8"

    def generate(params, kpool, vpool, kscale, vscale, tables, lens0,
                 tok0, key):
        B = tok0.shape[0]
        page = kpool.shape[3]

        def dec_step(carry, _):
            kpool, vpool, kscale, vscale, tok, lens, key = carry
            x = _embed_rows(params["embed"], tok[:, None], dt)
            page_ids = tables[jnp.arange(B), lens // page]
            slots = lens % page

            if q8:
                def layer(carry2, inp):
                    bp, kp, vp, ks, vs = inp
                    out, kp, vp, ks, vs = _decode_layer(
                        cfg, bp, kp, vp, carry2, tables, lens,
                        page_ids, slots, ks, vs)
                    return out, (kp, vp, ks, vs)

                x2, (kpool, vpool, kscale, vscale) = jax.lax.scan(
                    layer, x,
                    (params["blocks"], kpool, vpool, kscale, vscale))
            else:
                def layer(carry2, inp):
                    bp, kp, vp = inp
                    out, kp, vp, _, _ = _decode_layer(
                        cfg, bp, kp, vp, carry2, tables, lens,
                        page_ids, slots)
                    return out, (kp, vp)

                x2, (kpool, vpool) = jax.lax.scan(
                    layer, x, (params["blocks"], kpool, vpool))
            with jax.named_scope("logits"):
                h = _rms_norm(x2[:, 0], params["final_norm"],
                              cfg.rms_norm_eps)
                logits = _mm(h, params["lm_head"],
                             dt).astype(jnp.float32)
            key, sub = jax.random.split(key)
            nxt = _pick_token(logits, temperature, sub, top_k, top_p)
            return (kpool, vpool, kscale, vscale, nxt, lens + 1,
                    key), nxt

        carry0 = (kpool, vpool, kscale, vscale, tok0,
                  jnp.asarray(lens0, jnp.int32), key)
        (kpool, vpool, kscale, vscale, _, _, _), toks = jax.lax.scan(
            dec_step, carry0, None, length=max_new_tokens - 1)
        return kpool, vpool, kscale, vscale, jnp.concatenate(
            [tok0[None], toks], axis=0)

    fn = jax.jit(generate, donate_argnums=(1, 2, 3, 4))
    _gen_cache[(_cfg_key(cfg), max_new_tokens, temperature,
                kv_quant, top_k, top_p)] = fn
    return fn


_prefill_cache: dict = {}


def _prefill(cfg: LlamaPretrainConfig):
    """Memoised jitted dense prefill: causal forward collecting per-
    layer K/V (shapes come from the traced prompt, so one cache entry
    per cfg serves every batch/length)."""
    hit = _prefill_cache.get(_cfg_key(cfg))
    if hit is not None:
        return hit
    from .llama_pretrain import _rope
    from .decode import _grouped_attn

    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    dt = cfg.dtype

    @jax.jit
    def prefill(params, prompt):
        B, S = prompt.shape
        x = _embed_rows(params["embed"], prompt, dt)
        causal = jnp.tril(jnp.ones((S, S), bool))

        def pre_layer(carry, bp):
            xc = carry
            y = _rms_norm(xc, bp["ln1"], cfg.rms_norm_eps)
            q = _mm(y, bp["wq"], dt).reshape(B, S, n, d)
            k = _mm(y, bp["wk"], dt).reshape(B, S, nkv, d)
            v = _mm(y, bp["wv"], dt).reshape(B, S, nkv, d)
            q, k = _rope(q, k, cfg.rope_theta)
            attn = _grouped_attn(q, k, v, causal[None, None, None])
            out = _block_post_attn(bp, xc, attn, cfg)
            return out, (k, v)

        x, (ks, vs) = jax.lax.scan(pre_layer, x, params["blocks"])
        return x, ks, vs

    _prefill_cache[_cfg_key(cfg)] = prefill
    return prefill


def _rope_at(x, theta, pos):
    """RoPE at explicit positions ``pos [S]`` or PER-ROW ``[B, S]``
    (chunked prefill: chunk tokens sit at ctx_len + arange(C));
    x [B, S, n, d].  Same split-half convention as
    llama_pretrain._rope (the cached pages were written by it)."""
    with jax.named_scope("rope"):
        d = x.shape[-1]
        inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        freqs = pos.astype(jnp.float32)[..., None] * inv   # [(B,) S, d/2]
        if freqs.ndim == 2:
            freqs = freqs[None]                            # [1, S, d/2]
        cos = jnp.cos(freqs)[:, :, None, :]
        sin = jnp.sin(freqs)[:, :, None, :]
        x1, x2 = jnp.split(x, 2, axis=-1)
        x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
        return jnp.concatenate([x1f * cos - x2f * sin,
                                x2f * cos + x1f * sin], -1).astype(x.dtype)


_chunk_prefill_cache: dict = {}


def _prefill_chunk(cfg: LlamaPretrainConfig, q8: bool):
    """Memoised jitted CHUNKED prefill-with-history: advance ONE row's
    prefill by a chunk of tokens, attending to the row's already-cached
    pages plus causally within the chunk.  The serving engine drives
    this for prompts longer than a prefill bucket — prefill cost stays
    bounded per dispatch instead of one giant O(S^2) program (the
    reference serves long prompts the same way via its block-cache op's
    encoder phase).

    ``run(params, toks [1, C], kpool, vpool, kscale, vscale,
    table [pages_max], ctx_len) -> (x [1, C, H], ks, vs [Lyr, C, nkv,
    d])`` — shapes are static per (C, pool, table) so one compile
    serves every chunk index; ``ctx_len`` is traced.  Chunk K/V are
    returned unquantised; the host write path quantises."""
    hit = _chunk_prefill_cache.get((_cfg_key(cfg), q8))
    if hit is not None:
        return hit
    from .llama_pretrain import _rope  # noqa: F401  (convention ref)
    from .decode import _grouped_attn

    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    dt = cfg.dtype

    @jax.jit
    def run(params, toks, kpool, vpool, kscale, vscale, table, ctx_len):
        B, C = toks.shape                      # B == 1
        P = table.shape[0]
        page = kpool.shape[3]
        S_ctx = P * page
        x = _embed_rows(params["embed"], toks, dt)
        pos = ctx_len + jnp.arange(C, dtype=jnp.int32)
        # visibility: cached slots < ctx_len, then causal within chunk
        ctx_vis = jnp.arange(S_ctx, dtype=jnp.int32) < ctx_len
        mask = jnp.concatenate(
            [jnp.broadcast_to(ctx_vis[None], (C, S_ctx)),
             jnp.tril(jnp.ones((C, C), bool))], axis=1)
        mask = mask[None, None, None]          # [1, 1, 1, C, S_ctx+C]

        def gather_ctx(pool, scale):
            # [P, nkv, page, d] pages -> [1, S_ctx, nkv, d] context
            pages = pool[table]
            if q8:
                pages = (pages.astype(jnp.float32) *
                         scale[table][..., None])
            return pages.transpose(0, 2, 1, 3).reshape(
                1, S_ctx, nkv, d).astype(dt)

        def layer(carry, inp):
            if q8:
                bp, kp_l, vp_l, ks_l, vs_l = inp
            else:
                bp, kp_l, vp_l = inp
                ks_l = vs_l = None
            xc = carry
            y = _rms_norm(xc, bp["ln1"], cfg.rms_norm_eps)
            q = _mm(y, bp["wq"], dt).reshape(B, C, n, d)
            k = _mm(y, bp["wk"], dt).reshape(B, C, nkv, d)
            v = _mm(y, bp["wv"], dt).reshape(B, C, nkv, d)
            q = _rope_at(q, cfg.rope_theta, pos)
            k = _rope_at(k, cfg.rope_theta, pos)
            ck = jnp.concatenate([gather_ctx(kp_l, ks_l), k], axis=1)
            cv = jnp.concatenate([gather_ctx(vp_l, vs_l), v], axis=1)
            attn = _grouped_attn(q, ck, cv, mask)
            out = _block_post_attn(bp, xc, attn, cfg)
            return out, (k[0], v[0])

        xs = (params["blocks"], kpool, vpool)
        if q8:
            xs = xs + (kscale, vscale)
        x, (ks, vs) = jax.lax.scan(layer, x, xs)
        return x, ks, vs

    _chunk_prefill_cache[(_cfg_key(cfg), q8)] = run
    return run


_packed_prefill_cache: dict = {}


def _prefill_packed(cfg: LlamaPretrainConfig, q8: bool,
                    with_hist: bool):
    """Memoised jitted PACKED VARLEN prefill: every waiting context —
    mixed lengths, prefix-cache suffixes, long prompts — packs into ONE
    ``[1, T]`` token stream with segment ids and prefills as a single
    program (the serving-admission form of the segmented flash kernel;
    FLUX-style dispatch fusion: K per-bucket dispatches become one).

    ``run(params, toks [1, T], seg [1, T], pos [1, T], kpool, vpool,
    kscale, vscale, hist_page [T], hist_slot [T], pool_hist [T],
    stream_src [T], stream_hist [T]) -> (x [1, T, H], ks, vs
    [Lyr, T, nkv, d])``

    * ``seg``: int32 contiguous runs, one id per request (bucket-tail
      padding rides a sentinel id and attends only itself);
    * ``pos``: within-segment RoPE positions (a prefix-cache suffix
      starts at its reused offset);
    * attention is segment-masked causal: the block-skipping Pallas
      kernel (ops/pallas/flash_varlen.py) on TPU when a block divides
      ``T``, an XLA segment-masked ``_grouped_attn`` otherwise
      (CPU/interpret fallback — same masked-softmax numerics as the
      dense ``_prefill``, so greedy outputs stay token-exact);
    * ``with_hist`` compiles the PREFIX-CACHE lane: ``pool_hist`` slots
      take their K/V from cached pool pages (``hist_page``/``hist_slot``
      — already RoPE'd at write time; int8 pools dequant via the
      gathered scales), ``stream_hist`` slots from the stream itself at
      ``stream_src`` (a page being written by an earlier segment of the
      SAME wave — its pool copy lands only after this program returns).
      History slots contribute K/V only; their q rows are dead weight
      the caller never reads.
    """
    hit = _packed_prefill_cache.get((_cfg_key(cfg), q8, with_hist))
    if hit is not None:
        return hit
    run = jax.jit(_packed_prefill_body(cfg, q8, with_hist))
    _packed_prefill_cache[(_cfg_key(cfg), q8, with_hist)] = run
    return run


_packed_body_cache: dict = {}


def _packed_prefill_body(cfg: LlamaPretrainConfig, q8: bool,
                         with_hist: bool):
    """Memoised UNJITTED packed-varlen prefill body — the stream math
    of :func:`_prefill_packed` (which jits it directly) factored out
    so :func:`make_mixed_step` can compose it with the decode-step
    body, the page scatter and the first-token tail inside ONE outer
    jit: a mixed prefill+decode tick stays a single dispatch."""
    hit = _packed_body_cache.get((_cfg_key(cfg), q8, with_hist))
    if hit is not None:
        return hit
    from .decode import _grouped_attn
    from ..ops.pallas import _common as _pallas_common
    from ..ops.pallas.flash_varlen import (_pick_seg_blocks,
                                           flash_attention_segmented)

    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    dt = cfg.dtype

    def run(params, toks, seg, pos, kpool, vpool, kscale, vscale,
            hist_page, hist_slot, pool_hist, stream_src, stream_hist):
        B, T = toks.shape                      # B == 1
        x = _embed_rows(params["embed"], toks, dt)
        # static routing (trace-time): the Pallas kernel's block
        # skipping needs a dividing block and a real TPU; otherwise the
        # XLA mask keeps bitwise parity with the dense prefill path
        use_kernel = (not _pallas_common.interpret()
                      and _pick_seg_blocks(T) is not None)
        if not use_kernel:
            idx = jnp.arange(T, dtype=jnp.int32)
            # segments are contiguous runs, so global causal ==
            # within-segment causal
            mask = ((seg[0][:, None] == seg[0][None, :])
                    & (idx[:, None] >= idx[None, :]))[None, None, None]

        def layer(carry, inp):
            if q8:
                bp, kp_l, vp_l, ks_l, vs_l = inp
            else:
                bp, kp_l, vp_l = inp
                ks_l = vs_l = None
            xc = carry
            with jax.named_scope("block"):
                with jax.named_scope("attn_qkv"):
                    y = _rms_norm(xc, bp["ln1"], cfg.rms_norm_eps)
                    q = _mm(y, bp["wq"], dt).reshape(B, T, n, d)
                    k = _mm(y, bp["wk"], dt).reshape(B, T, nkv, d)
                    v = _mm(y, bp["wv"], dt).reshape(B, T, nkv, d)
                q = _rope_at(q, cfg.rope_theta, pos)
                k = _rope_at(k, cfg.rope_theta, pos)
                if with_hist:
                    kh = kp_l[hist_page, :, hist_slot]     # [T, nkv, d]
                    vh = vp_l[hist_page, :, hist_slot]
                    if q8:
                        kh = (kh.astype(jnp.float32)
                              * ks_l[hist_page, :, hist_slot][..., None])
                        vh = (vh.astype(jnp.float32)
                              * vs_l[hist_page, :, hist_slot][..., None])
                    sel = pool_hist[None, :, None, None]
                    k = jnp.where(sel, kh.astype(dt)[None], k)
                    v = jnp.where(sel, vh.astype(dt)[None], v)
                    sel2 = stream_hist[None, :, None, None]
                    k = jnp.where(sel2, k[:, stream_src], k)
                    v = jnp.where(sel2, v[:, stream_src], v)
                with jax.named_scope("varlen_attn"):
                    if use_kernel:
                        attn = flash_attention_segmented(q, k, v, seg,
                                                         causal=True)
                    else:
                        attn = _grouped_attn(q, k, v, mask)
                out = _block_post_attn(bp, xc, attn, cfg)
            return out, (k[0], v[0])

        xs = (params["blocks"], kpool, vpool)
        if q8:
            xs = xs + (kscale, vscale)
        with jax.named_scope("layer_scan"):
            x, (ks, vs) = jax.lax.scan(layer, x, xs)
        return x, ks, vs

    _packed_body_cache[(_cfg_key(cfg), q8, with_hist)] = run
    return run


_packed_tp_cache: dict = {}


def _prefill_packed_tp(cfg: LlamaPretrainConfig, mesh, q8: bool,
                       with_hist: bool):
    """PACKED VARLEN prefill composed through the TP shard_map seam —
    same signature and stream layout as :func:`_prefill_packed`, so
    the engine's packed admission lane stays ONE dispatch per wave on
    a mesh.  Per shard: local-head q/k/v (Megatron column split),
    segment-masked attention over the LOCAL heads (the segmented
    Pallas kernel per shard on TPU — heads are embarrassingly
    parallel — XLA mask on CPU), history K/V gathered from the local
    pool shard (int8 dequant via the local scale planes: page ids are
    replicated, heads are sharded, so nothing crosses the mp axis),
    and row-parallel psums for wo / w_down (exact fp reductions —
    prefill keeps the token-exactness bar; ``tp_allreduce`` is a
    decode-lane knob).  Returns replicated ``x [1, T, H]`` and
    head-SHARDED ``ks``/``vs [Lyr, T, nkv, d]`` — per-segment page
    scatters then stay local to each shard."""
    ckey = (_cfg_key(cfg), mesh, q8, with_hist)
    hit = _packed_tp_cache.get(ckey)
    if hit is not None:
        return hit
    run = jax.jit(_packed_prefill_body_tp(cfg, mesh, q8, with_hist))
    _packed_tp_cache[ckey] = run
    return run


_packed_body_tp_cache: dict = {}


def _packed_prefill_body_tp(cfg: LlamaPretrainConfig, mesh, q8: bool,
                            with_hist: bool):
    """Memoised UNJITTED (but shard_map'd) TP packed-prefill body —
    :func:`_prefill_packed_tp` jits it directly; the TP form of
    :func:`make_mixed_step` composes it with the sharded decode step
    inside one outer jit so a mixed tick stays one dispatch on the
    mesh."""
    ckey = (_cfg_key(cfg), mesh, q8, with_hist)
    hit = _packed_body_tp_cache.get(ckey)
    if hit is not None:
        return hit
    from jax.sharding import PartitionSpec as P
    from .llama_pretrain import param_specs
    from .decode import _grouped_attn
    from ..ops.pallas import _common as _pallas_common
    from ..ops.pallas.flash_varlen import (_pick_seg_blocks,
                                           flash_attention_segmented)

    mp = mesh.shape["mp"]
    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    if n % mp or nkv % mp:
        raise ValueError(f"heads {n}/{nkv} must divide over mp={mp}")
    n_l, nkv_l = n // mp, nkv // mp
    dt = cfg.dtype
    ax = "mp"

    def run_local(params, toks, seg, pos, kpool, vpool, kscale,
                  vscale, hist_page, hist_slot, pool_hist, stream_src,
                  stream_hist):
        B, T = toks.shape                  # B == 1
        x = _embed_vocab_parallel(params["embed"], toks, ax, dt)
        use_kernel = (not _pallas_common.interpret()
                      and _pick_seg_blocks(T) is not None)
        if not use_kernel:
            idx = jnp.arange(T, dtype=jnp.int32)
            mask = ((seg[0][:, None] == seg[0][None, :])
                    & (idx[:, None] >= idx[None, :]))[None, None, None]

        def layer(carry, inp):
            if q8:
                bp, kp_l, vp_l, ks_l, vs_l = inp
            else:
                bp, kp_l, vp_l = inp
                ks_l = vs_l = None
            xc = carry
            with jax.named_scope("block"):
                with jax.named_scope("attn_qkv"):
                    y = _rms_norm(xc, bp["ln1"], cfg.rms_norm_eps)
                    q = _mm(y, bp["wq"], dt).reshape(B, T, n_l, d)
                    k = _mm(y, bp["wk"], dt).reshape(B, T, nkv_l, d)
                    v = _mm(y, bp["wv"], dt).reshape(B, T, nkv_l, d)
                q = _rope_at(q, cfg.rope_theta, pos)
                k = _rope_at(k, cfg.rope_theta, pos)
                if with_hist:
                    kh = kp_l[hist_page, :, hist_slot]   # [T, nkv_l, d]
                    vh = vp_l[hist_page, :, hist_slot]
                    if q8:
                        kh = (kh.astype(jnp.float32)
                              * ks_l[hist_page, :, hist_slot][..., None])
                        vh = (vh.astype(jnp.float32)
                              * vs_l[hist_page, :, hist_slot][..., None])
                    sel = pool_hist[None, :, None, None]
                    k = jnp.where(sel, kh.astype(dt)[None], k)
                    v = jnp.where(sel, vh.astype(dt)[None], v)
                    sel2 = stream_hist[None, :, None, None]
                    k = jnp.where(sel2, k[:, stream_src], k)
                    v = jnp.where(sel2, v[:, stream_src], v)
                with jax.named_scope("varlen_attn"):
                    if use_kernel:
                        attn = flash_attention_segmented(q, k, v, seg,
                                                         causal=True)
                    else:
                        attn = _grouped_attn(q, k, v, mask)
                with jax.named_scope("attn_out"):
                    o = _mm(attn.reshape(B, T, n_l * d), bp["wo"], dt)
                    xc = xc + jax.lax.psum(o, ax)             # row-parallel
                with jax.named_scope("mlp"):
                    res = xc
                    y2 = _rms_norm(xc, bp["ln2"], cfg.rms_norm_eps)
                    act = (jax.nn.silu(_mm(y2, bp["w_gate"], dt))
                           * _mm(y2, bp["w_up"], dt))
                    ffn = _mm(act, bp["w_down"], dt)
                    return res + jax.lax.psum(ffn, ax), (k[0], v[0])

        xs = (params["blocks"], kpool, vpool)
        if q8:
            xs = xs + (kscale, vscale)
        with jax.named_scope("layer_scan"):
            x, (ks, vs) = jax.lax.scan(layer, x, xs)
        return x, ks, vs

    pool_spec = P(None, None, "mp", None, None)
    scale_spec = P(None, None, "mp", None) if q8 else P()
    run = jax.shard_map(
        run_local, mesh=mesh,
        in_specs=(param_specs(cfg, pp=1), P(), P(), P(), pool_spec,
                  pool_spec, scale_spec, scale_spec, P(), P(), P(),
                  P(), P()),
        out_specs=(P(), P(None, None, "mp", None),
                   P(None, None, "mp", None)),
        check_vma=False)
    _packed_body_tp_cache[ckey] = run
    return run


_chunk_b_cache: dict = {}


def _prefill_chunk_batched(cfg: LlamaPretrainConfig):
    """BATCHED prefill-with-history: advance EVERY row's context by a
    chunk at its own offset — ``run(params, toks [B, C], kpool, vpool,
    tables [B, P], ctx_len [B]) -> (x [B, C, H], ks, vs
    [Lyr, B, C, nkv, d])``.  This is the batched speculative-decoding
    VERIFY program: one target forward scores all rows' candidate
    blocks over their cached pages (per-row tables, per-row positions,
    per-row visibility).  bf16/f32 pools only — the speculative engine
    path keeps quantisation out of the verify trunk."""
    hit = _chunk_b_cache.get(_cfg_key(cfg))
    if hit is not None:
        return hit
    from .decode import _grouped_attn

    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    dt = cfg.dtype

    @jax.jit
    def run(params, toks, kpool, vpool, tables, ctx_len):
        B, C = toks.shape
        P = tables.shape[1]
        page = kpool.shape[3]
        S_ctx = P * page
        x = _embed_rows(params["embed"], toks, dt)
        pos = ctx_len[:, None] + jnp.arange(C, dtype=jnp.int32)
        ctx_vis = (jnp.arange(S_ctx, dtype=jnp.int32)[None]
                   < ctx_len[:, None])                 # [B, S_ctx]
        mask = jnp.concatenate(
            [jnp.broadcast_to(ctx_vis[:, None], (B, C, S_ctx)),
             jnp.broadcast_to(jnp.tril(jnp.ones((C, C), bool))[None],
                              (B, C, C))], axis=2)
        mask = mask[:, None, None]        # [B, 1, 1, C, S_ctx + C]

        def gather_ctx(pool):
            # [num_pages, nkv, page, d] -> per-row pages [B, P, ...]
            pages = pool[tables]          # [B, P, nkv, page, d]
            return pages.transpose(0, 1, 3, 2, 4).reshape(
                B, S_ctx, nkv, d).astype(dt)

        def layer(carry, inp):
            bp, kp_l, vp_l = inp
            xc = carry
            y = _rms_norm(xc, bp["ln1"], cfg.rms_norm_eps)
            q = _mm(y, bp["wq"], dt).reshape(B, C, n, d)
            k = _mm(y, bp["wk"], dt).reshape(B, C, nkv, d)
            v = _mm(y, bp["wv"], dt).reshape(B, C, nkv, d)
            q = _rope_at(q, cfg.rope_theta, pos)
            k = _rope_at(k, cfg.rope_theta, pos)
            ck = jnp.concatenate([gather_ctx(kp_l), k], axis=1)
            cv = jnp.concatenate([gather_ctx(vp_l), v], axis=1)
            attn = _grouped_attn(q, ck, cv, mask)
            out = _block_post_attn(bp, xc, attn, cfg)
            return out, (k, v)

        x, (ks, vs) = jax.lax.scan(
            layer, x, (params["blocks"], kpool, vpool))
        return x, ks, vs

    _chunk_b_cache[_cfg_key(cfg)] = run
    return run


_chunk_b_tp_cache: dict = {}


def _prefill_chunk_batched_tp(cfg: LlamaPretrainConfig, mesh):
    """TENSOR-PARALLEL batched prefill-with-history — the speculative
    VERIFY program on a mesh, same signature as
    :func:`_prefill_chunk_batched`.  One shard_map forward scores
    every row's candidate block over the kv-head-SHARDED page pools:
    per-row tables/positions/visibility are replicated host state,
    the context gather and attention run on LOCAL heads, and wo /
    w_down reduce with exact fp psums (verification must stay exact —
    it is what makes speculative output provably the target model's
    greedy sequence).  Returns replicated ``x [B, C, H]`` and
    head-sharded ``ks``/``vs [Lyr, B, C, nkv, d]``."""
    ckey = (_cfg_key(cfg), mesh)
    hit = _chunk_b_tp_cache.get(ckey)
    if hit is not None:
        return hit
    from jax.sharding import PartitionSpec as P
    from .llama_pretrain import param_specs
    from .decode import _grouped_attn

    mp = mesh.shape["mp"]
    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    if n % mp or nkv % mp:
        raise ValueError(f"heads {n}/{nkv} must divide over mp={mp}")
    n_l, nkv_l = n // mp, nkv // mp
    dt = cfg.dtype
    ax = "mp"

    def run_local(params, toks, kpool, vpool, tables, ctx_len):
        B, C = toks.shape
        Pg = tables.shape[1]
        page = kpool.shape[3]
        S_ctx = Pg * page
        x = _embed_vocab_parallel(params["embed"], toks, ax, dt)
        pos = ctx_len[:, None] + jnp.arange(C, dtype=jnp.int32)
        ctx_vis = (jnp.arange(S_ctx, dtype=jnp.int32)[None]
                   < ctx_len[:, None])
        mask = jnp.concatenate(
            [jnp.broadcast_to(ctx_vis[:, None], (B, C, S_ctx)),
             jnp.broadcast_to(jnp.tril(jnp.ones((C, C), bool))[None],
                              (B, C, C))], axis=2)
        mask = mask[:, None, None]

        def gather_ctx(pool):
            pages = pool[tables]      # [B, P, nkv_l, page, d]
            return pages.transpose(0, 1, 3, 2, 4).reshape(
                B, S_ctx, nkv_l, d).astype(dt)

        def layer(carry, inp):
            bp, kp_l, vp_l = inp
            xc = carry
            y = _rms_norm(xc, bp["ln1"], cfg.rms_norm_eps)
            q = _mm(y, bp["wq"], dt).reshape(B, C, n_l, d)
            k = _mm(y, bp["wk"], dt).reshape(B, C, nkv_l, d)
            v = _mm(y, bp["wv"], dt).reshape(B, C, nkv_l, d)
            q = _rope_at(q, cfg.rope_theta, pos)
            k = _rope_at(k, cfg.rope_theta, pos)
            ck = jnp.concatenate([gather_ctx(kp_l), k], axis=1)
            cv = jnp.concatenate([gather_ctx(vp_l), v], axis=1)
            attn = _grouped_attn(q, ck, cv, mask)
            o = _mm(attn.reshape(B, C, n_l * d), bp["wo"], dt)
            xc = xc + jax.lax.psum(o, ax)
            res = xc
            y2 = _rms_norm(xc, bp["ln2"], cfg.rms_norm_eps)
            act = (jax.nn.silu(_mm(y2, bp["w_gate"], dt))
                   * _mm(y2, bp["w_up"], dt))
            ffn = _mm(act, bp["w_down"], dt)
            return res + jax.lax.psum(ffn, ax), (k, v)

        x, (ks, vs) = jax.lax.scan(
            layer, x, (params["blocks"], kpool, vpool))
        return x, ks, vs

    pool_spec = P(None, None, "mp", None, None)
    run = jax.jit(jax.shard_map(
        run_local, mesh=mesh,
        in_specs=(param_specs(cfg, pp=1), P(), pool_spec, pool_spec,
                  P(), P()),
        out_specs=(P(), P(None, None, None, "mp", None),
                   P(None, None, None, "mp", None)),
        check_vma=False))
    _chunk_b_tp_cache[ckey] = run
    return run


_mixed_step_cache: dict = {}


def make_mixed_step(cfg: LlamaPretrainConfig,
                    temperature: float = 0.0,
                    kv_quant: Optional[str] = None,
                    top_k: int = 0, top_p: float = 1.0,
                    mesh=None, tp_allreduce: str = "fp32",
                    with_hist: bool = True):
    """ONE jitted program per MIXED serving tick (Sarathi-style
    chunked-prefill piggybacking, the scheduler-level form of the
    T3/FLUX fuse-the-phases idea): advance every active decode row
    exactly like :func:`make_paged_decode_step_async` AND consume a
    budget of packed varlen prefill-stream tokens in the SAME
    dispatch — a colocated engine never stops decoding to admit.

    The dispatch packs decode rows as length-1 paged-attention
    segments alongside the prefill stream: the prefill half is the
    packed-varlen body (:func:`_packed_prefill_body` — segmented
    flash kernel on TPU, XLA segment mask on CPU, bitwise parity with
    the sequential packed lane) with prefix-history gathers for
    resumed chunks; its per-segment page scatters (int8
    quantize-on-write included) and the first-token sampling tail run
    INSIDE the program, so the host never syncs for admission.
    Completing segments ACTIVATE on-device: the returned loop state
    carries them into the next chained dispatch with no pipeline
    flush, and the host learns their sampled first token at the
    ordinary one-step-behind drain (``ftok``).

    ``fn(params, kpool, vpool, [kscale, vscale,] tables, lens, tok,
    active, remaining, eos, key,
    p_toks [1,T], p_seg [1,T], p_pos [1,T],
    hist_page [T], hist_slot [T], pool_hist [T],
    dest_page [T], dest_slot [T],
    sample_idx [B], activate [B], p_first [B], p_sample [B],
    p_len [B], p_rem [B])
    -> (kpool, vpool, [kscale, vscale,] nxt, lens', remaining',
    active', done, ftok)``

    * decode half: identical math/advance to the async step; inactive
      rows' junk writes are steered to reserved page 0 via a masked
      tables view, so mid-prefill rows' freshly-written pages can
      never be clobbered by an idle decode lane;
    * prefill half: ``dest_page``/``dest_slot`` route each fresh
      stream token's K/V into its row's pages (history + padding
      slots scatter to page 0); same-wave stream sharing is never
      needed — the scheduler registers prefix pages only after their
      chunk's dispatch, so sharers always gather from the pool one
      dispatch behind;
    * first tokens: ``sample_idx`` gathers each completing segment's
      last real hidden state through the shared logits tail;
      ``p_sample`` rows take the sampled token, resume rows take
      ``p_first`` (their saved next input).  ``activate`` rows enter
      the chained state with ``lens = p_len``, ``remaining = p_rem``.

    With ``mesh`` (mp>1) both halves compose through the existing
    shard_map seams (:func:`_build_tp_inner`,
    :func:`_packed_prefill_body_tp`) inside the same outer jit — one
    dispatch per tick on the mesh, scatters and history gathers stay
    shard-local on the kv-head axis.
    """
    q8 = kv_quant == "int8"
    mesh_key = mesh if (mesh is not None
                        and mesh.shape.get("mp", 1) > 1) else None
    ckey = (_cfg_key(cfg), temperature, kv_quant, top_k, top_p,
            mesh_key, tp_allreduce if mesh_key is not None else "fp32",
            with_hist)
    hit = _mixed_step_cache.get(ckey)
    if hit is not None:
        return hit

    from ..ops.pallas.paged_attention import quantize_kv_token
    dt = cfg.dtype
    if mesh_key is not None:
        dec_base = _build_tp_inner(cfg, mesh, temperature, kv_quant,
                                   top_k, top_p,
                                   tp_allreduce=tp_allreduce)
        pre_body = _packed_prefill_body_tp(cfg, mesh, q8, with_hist)
    else:
        step, step_q8 = _build_step_fns(cfg, temperature, False,
                                        top_k, top_p)
        dec_base = step_q8 if q8 else step
        pre_body = _packed_prefill_body(cfg, q8, with_hist)

    advance = _advance_loop_state   # the async lane's exact advance

    def scatter(kpool, vpool, kscale, vscale, ks, vs, dest_page,
                dest_slot):
        # per-token page scatter of the stream K/V (fresh chunk slots
        # land in their row's pages; history/padding slots land on
        # junk page 0 — DMA-valid, never read below lens)
        with jax.named_scope("kv_write"):
            if q8:
                ks, ksc = quantize_kv_token(ks)
                vs, vsc = quantize_kv_token(vs)
            kpool = kpool.at[:, dest_page, :, dest_slot, :].set(
                jnp.transpose(ks, (1, 0, 2, 3)).astype(kpool.dtype))
            vpool = vpool.at[:, dest_page, :, dest_slot, :].set(
                jnp.transpose(vs, (1, 0, 2, 3)).astype(vpool.dtype))
            if q8:
                kscale = kscale.at[:, dest_page, :, dest_slot].set(
                    jnp.transpose(ksc, (1, 0, 2)))
                vscale = vscale.at[:, dest_page, :, dest_slot].set(
                    jnp.transpose(vsc, (1, 0, 2)))
            return kpool, vpool, kscale, vscale

    def fn(params, kpool, vpool, kscale, vscale, tables, lens, tok,
           active, remaining, eos, key, p_toks, p_seg, p_pos,
           hist_page, hist_slot, pool_hist, dest_page, dest_slot,
           sample_idx, activate, p_first, p_sample, p_len, p_rem):
        T = p_toks.shape[1]
        k_dec, k_smp = jax.random.split(key)
        if q8:
            ks_in, vs_in = kscale, vscale
        else:
            ks_in = vs_in = jnp.zeros((1,), jnp.float32)
        x, ks, vs = pre_body(
            params, p_toks, p_seg, p_pos, kpool, vpool, ks_in, vs_in,
            hist_page, hist_slot, pool_hist,
            jnp.zeros((T,), jnp.int32), jnp.zeros((T,), bool))
        # first-token sampling: each completing segment's LAST real
        # position through the shared logits tail (the same eager
        # tail the sequential lanes use, so greedy outputs match)
        with jax.named_scope("logits"):
            h = _rms_norm(x[0, sample_idx], params["final_norm"],
                          cfg.rms_norm_eps)
            logits = _mm(h, params["lm_head"], dt).astype(jnp.float32)
        sampled = _pick_token(logits, temperature, k_smp, top_k,
                              top_p)
        kpool, vpool, kscale, vscale = scatter(
            kpool, vpool, kscale, vscale, ks, vs, dest_page,
            dest_slot)
        # decode half: inactive rows (mid-prefill rows included) see a
        # zeroed table row, so their dead writes land on page 0
        tables_d = jnp.where(active[:, None], tables, 0)
        if q8:
            kpool, vpool, kscale, vscale, nxt = dec_base(
                params, kpool, vpool, kscale, vscale, tables_d, lens,
                tok, k_dec)
        else:
            kpool, vpool, nxt = dec_base(params, kpool, vpool,
                                         tables_d, lens, tok, k_dec)
        nxt, lens2, rem2, act2, done = advance(nxt, tok, lens, active,
                                               remaining, eos)
        ftok = jnp.where(p_sample, sampled.astype(p_first.dtype),
                         p_first)
        nxt = jnp.where(activate, ftok.astype(nxt.dtype), nxt)
        lens2 = jnp.where(activate, p_len.astype(lens2.dtype), lens2)
        rem2 = jnp.where(activate, p_rem.astype(rem2.dtype), rem2)
        act2 = act2 | activate
        if q8:
            return (kpool, vpool, kscale, vscale, nxt, lens2, rem2,
                    act2, done, ftok)
        return kpool, vpool, nxt, lens2, rem2, act2, done, ftok

    if q8:
        jitted = jax.jit(fn, donate_argnums=(1, 2, 3, 4))
    else:
        def fn_fp(params, kpool, vpool, tables, lens, tok, active,
                  remaining, eos, key, *rest):
            return fn(params, kpool, vpool, None, None, tables, lens,
                      tok, active, remaining, eos, key, *rest)
        jitted = jax.jit(fn_fp, donate_argnums=(1, 2))
    _mixed_step_cache[ckey] = jitted
    return jitted


_spec_verify_cache: dict = {}


def _spec_verify_body(cfg: LlamaPretrainConfig, q8: bool):
    """Memoised UNJITTED batched verify-with-history body — the
    candidate-scoring math of :func:`make_spec_step` factored out so
    the fused draft+verify program can compose it with the draft scan,
    the page scatter and the accept fold inside ONE outer jit.

    ``run(params, toks [B, C], kpool, vpool, kscale, vscale,
    tables [B, P], ctx_len [B]) -> (x [B, C, H], ks, vs
    [Lyr, B, C, nkv, d])`` — per-row tables, per-row positions,
    per-row visibility, exactly :func:`_prefill_chunk_batched` PLUS
    the int8 dequant gather (the same scale-plane indexing the packed
    prefix-history lane uses), so speculative serving composes with
    quantised pools instead of rejecting them.  ``kscale``/``vscale``
    are ignored when ``q8`` is False (pass any placeholder)."""
    hit = _spec_verify_cache.get((_cfg_key(cfg), q8))
    if hit is not None:
        return hit
    from .decode import _grouped_attn
    from ..ops.pallas.paged_attention import quantize_kv_token

    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    dt = cfg.dtype

    def _qdq(t):
        # int8 parity: the per-token q8 decode step attends over its
        # OWN token's K/V read back quantized from the pool, so the
        # verify's within-block fresh K/V must round-trip through the
        # same quantizer or multi-token rounds drift off the oracle
        B, C = t.shape[0], t.shape[1]
        tq, sc = quantize_kv_token(t.reshape(B * C, *t.shape[2:]))
        return (tq.astype(jnp.float32) * sc[..., None]).reshape(
            t.shape).astype(dt)

    def run(params, toks, kpool, vpool, kscale, vscale, tables,
            ctx_len):
        B, C = toks.shape
        P = tables.shape[1]
        page = kpool.shape[3]
        S_ctx = P * page
        x = _embed_rows(params["embed"], toks, dt)
        pos = ctx_len[:, None] + jnp.arange(C, dtype=jnp.int32)
        ctx_vis = (jnp.arange(S_ctx, dtype=jnp.int32)[None]
                   < ctx_len[:, None])                 # [B, S_ctx]
        mask = jnp.concatenate(
            [jnp.broadcast_to(ctx_vis[:, None], (B, C, S_ctx)),
             jnp.broadcast_to(jnp.tril(jnp.ones((C, C), bool))[None],
                              (B, C, C))], axis=2)
        mask = mask[:, None, None]        # [B, 1, 1, C, S_ctx + C]

        def gather_ctx(pool, scale):
            # [num_pages, nkv, page, d] -> per-row pages [B, P, ...];
            # int8 pools dequant through the gathered scale planes
            pages = pool[tables]          # [B, P, nkv, page, d]
            out = pages.transpose(0, 1, 3, 2, 4).reshape(
                B, S_ctx, nkv, d)
            if q8:
                sc = scale[tables].transpose(0, 1, 3, 2).reshape(
                    B, S_ctx, nkv)
                out = out.astype(jnp.float32) * sc[..., None]
            return out.astype(dt)

        def layer(carry, inp):
            if q8:
                bp, kp_l, vp_l, ks_l, vs_l = inp
            else:
                bp, kp_l, vp_l = inp
                ks_l = vs_l = None
            xc = carry
            with jax.named_scope("block"):
                with jax.named_scope("attn_qkv"):
                    y = _rms_norm(xc, bp["ln1"], cfg.rms_norm_eps)
                    q = _mm(y, bp["wq"], dt).reshape(B, C, n, d)
                    k = _mm(y, bp["wk"], dt).reshape(B, C, nkv, d)
                    v = _mm(y, bp["wv"], dt).reshape(B, C, nkv, d)
                q = _rope_at(q, cfg.rope_theta, pos)
                k = _rope_at(k, cfg.rope_theta, pos)
                with jax.named_scope("attn"):
                    ku, vu = (_qdq(k), _qdq(v)) if q8 else (k, v)
                    ck = jnp.concatenate([gather_ctx(kp_l, ks_l), ku], axis=1)
                    cv = jnp.concatenate([gather_ctx(vp_l, vs_l), vu], axis=1)
                    attn = _grouped_attn(q, ck, cv, mask)
                out = _block_post_attn(bp, xc, attn, cfg)
            return out, (k, v)

        xs = (params["blocks"], kpool, vpool)
        if q8:
            xs = xs + (kscale, vscale)
        with jax.named_scope("layer_scan"):
            x, (ks, vs) = jax.lax.scan(layer, x, xs)
        return x, ks, vs

    _spec_verify_cache[(_cfg_key(cfg), q8)] = run
    return run


_spec_verify_tp_cache: dict = {}


def _spec_verify_body_tp(cfg: LlamaPretrainConfig, mesh, q8: bool):
    """Memoised UNJITTED (but shard_map'd) TP verify-with-history body
    — :func:`_spec_verify_body` on a mesh, same signature.  Per-row
    tables/positions/visibility are replicated host state, the context
    gather (int8 dequant via the LOCAL scale planes — page ids
    replicated, heads sharded, nothing crosses the mp axis) and
    attention run on local heads, and wo / w_down reduce with exact fp
    psums: verification must stay exact, it is what makes speculative
    output provably the target model's greedy sequence
    (``tp_allreduce='int8'`` is a DRAFT-lane knob).  Returns
    replicated ``x [B, C, H]`` and head-sharded ``ks``/``vs``."""
    ckey = (_cfg_key(cfg), mesh, q8)
    hit = _spec_verify_tp_cache.get(ckey)
    if hit is not None:
        return hit
    from jax.sharding import PartitionSpec as P
    from .llama_pretrain import param_specs
    from .decode import _grouped_attn

    mp = mesh.shape["mp"]
    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    if n % mp or nkv % mp:
        raise ValueError(f"heads {n}/{nkv} must divide over mp={mp}")
    n_l, nkv_l = n // mp, nkv // mp
    dt = cfg.dtype
    ax = "mp"
    from ..ops.pallas.paged_attention import quantize_kv_token

    def _qdq(t):
        # same int8 read-back parity as the single-device verify body
        B, C = t.shape[0], t.shape[1]
        tq, sc = quantize_kv_token(t.reshape(B * C, *t.shape[2:]))
        return (tq.astype(jnp.float32) * sc[..., None]).reshape(
            t.shape).astype(dt)

    def run_local(params, toks, kpool, vpool, kscale, vscale, tables,
                  ctx_len):
        B, C = toks.shape
        Pg = tables.shape[1]
        page = kpool.shape[3]
        S_ctx = Pg * page
        x = _embed_vocab_parallel(params["embed"], toks, ax, dt)
        pos = ctx_len[:, None] + jnp.arange(C, dtype=jnp.int32)
        ctx_vis = (jnp.arange(S_ctx, dtype=jnp.int32)[None]
                   < ctx_len[:, None])
        mask = jnp.concatenate(
            [jnp.broadcast_to(ctx_vis[:, None], (B, C, S_ctx)),
             jnp.broadcast_to(jnp.tril(jnp.ones((C, C), bool))[None],
                              (B, C, C))], axis=2)
        mask = mask[:, None, None]

        def gather_ctx(pool, scale):
            pages = pool[tables]      # [B, P, nkv_l, page, d]
            out = pages.transpose(0, 1, 3, 2, 4).reshape(
                B, S_ctx, nkv_l, d)
            if q8:
                sc = scale[tables].transpose(0, 1, 3, 2).reshape(
                    B, S_ctx, nkv_l)
                out = out.astype(jnp.float32) * sc[..., None]
            return out.astype(dt)

        def layer(carry, inp):
            if q8:
                bp, kp_l, vp_l, ks_l, vs_l = inp
            else:
                bp, kp_l, vp_l = inp
                ks_l = vs_l = None
            xc = carry
            with jax.named_scope("block"):
                with jax.named_scope("attn_qkv"):
                    y = _rms_norm(xc, bp["ln1"], cfg.rms_norm_eps)
                    q = _mm(y, bp["wq"], dt).reshape(B, C, n_l, d)
                    k = _mm(y, bp["wk"], dt).reshape(B, C, nkv_l, d)
                    v = _mm(y, bp["wv"], dt).reshape(B, C, nkv_l, d)
                q = _rope_at(q, cfg.rope_theta, pos)
                k = _rope_at(k, cfg.rope_theta, pos)
                with jax.named_scope("attn"):
                    ku, vu = (_qdq(k), _qdq(v)) if q8 else (k, v)
                    ck = jnp.concatenate([gather_ctx(kp_l, ks_l), ku], axis=1)
                    cv = jnp.concatenate([gather_ctx(vp_l, vs_l), vu], axis=1)
                    attn = _grouped_attn(q, ck, cv, mask)
                with jax.named_scope("attn_out"):
                    o = _mm(attn.reshape(B, C, n_l * d), bp["wo"], dt)
                    xc = xc + jax.lax.psum(o, ax)
                with jax.named_scope("mlp"):
                    res = xc
                    y2 = _rms_norm(xc, bp["ln2"], cfg.rms_norm_eps)
                    act = (jax.nn.silu(_mm(y2, bp["w_gate"], dt))
                           * _mm(y2, bp["w_up"], dt))
                    ffn = _mm(act, bp["w_down"], dt)
                    return res + jax.lax.psum(ffn, ax), (k, v)

        xs = (params["blocks"], kpool, vpool)
        if q8:
            xs = xs + (kscale, vscale)
        with jax.named_scope("layer_scan"):
            x, (ks, vs) = jax.lax.scan(layer, x, xs)
        return x, ks, vs

    pool_spec = P(None, None, "mp", None, None)
    scale_spec = P(None, None, "mp", None) if q8 else P()
    run = jax.shard_map(
        run_local, mesh=mesh,
        in_specs=(param_specs(cfg, pp=1), P(), pool_spec, pool_spec,
                  scale_spec, scale_spec, P(), P()),
        out_specs=(P(), P(None, None, None, "mp", None),
                   P(None, None, None, "mp", None)),
        check_vma=False)
    _spec_verify_tp_cache[ckey] = run
    return run


_spec_step_cache: dict = {}


def make_spec_step(cfg: LlamaPretrainConfig, gamma: int,
                   draft_cfg: Optional[LlamaPretrainConfig] = None,
                   kv_quant: Optional[str] = None,
                   draft_kv_quant: Optional[str] = None,
                   mesh=None, tp_allreduce: str = "fp32"):
    """ONE jitted program per SPECULATIVE serving round: the
    gamma-iteration draft scan (draft params + draft cache pages) AND
    the batched target verify run in the SAME dispatch, with the
    per-slot accept-count / done masks folded on-device — the
    speculative form of :func:`make_paged_decode_step_multi`'s
    fuse-the-loop move.  The engine pays one dispatch (and one
    blocking fetch) per round of up to gamma+1 committed tokens, and
    the chained loop state feeds round k+1's dispatch with zero host
    round-trips.

    Greedy-only by construction: verification accepts the longest
    candidate prefix that MATCHES the target argmax, then commits the
    target's own correction token — the committed stream is exactly
    ``g[:, :k+1]``, the target model's greedy continuation, which is
    what makes speculative output provably token-identical to plain
    greedy decode (the engine rejects ``temperature > 0``).

    With ``draft_cfg`` (draft-model drafting):

    ``fn(params, dparams, kpool, vpool, [kscale, vscale,] dkpool,
    dvpool, [dkscale, dvscale,] tables, dtables, lens, tok, prev,
    active, remaining, spec_on, eos, key) -> (pools..., dpools...,
    toks [C, B], dones [C, B], emits [C, B], accepts [B], tok',
    prev', lens', remaining', active')`` with ``C = gamma + 1``.

    * the draft scan runs gamma+1 micro-steps of the draft model's
      decode body: micro-step 0 is a CATCH-UP feed of ``prev``
      (= x[lens-1], the second-to-last committed token) at draft
      position lens-1 — an idempotent rewrite when the draft cache is
      already caught up, and exactly the write that realigns it after
      a full-accept round left it one position behind; micro-steps
      1..gamma chain ``tok``, d1, ..., producing the drafts.  Draft
      writes for inactive / spec-off rows steer to junk page 0 via a
      masked ``dtables`` view;
    * the verify half scores all C candidates ``[tok, d1..dgamma]``
      at per-row offsets over the cached target pages
      (:func:`_spec_verify_body` — ctx-len masking keeps stale
      beyond-lens K/V invisible) and scatters their fresh K/V into
      the target pages INSIDE the program: destination pages come
      from the on-device table gather, with inactive rows and
      beyond-capacity positions steered to junk page 0 (the engine
      pre-claims gamma+1 tokens of pages per active slot, so real
      writes always land in claimed pages);
    * the accept fold is a C-iteration scan mirroring the async
      lane's :func:`_advance_loop_state` under a per-step emit window
      ``j < accepts+1``: ``toks[j]``/``dones[j]``/``emits[j]`` are
      micro-step j's committed token / just-retired mask / validity
      mask, and rows with ``spec_on`` False commit exactly their
      plain greedy token (the accept window collapses to 1) — per
      request spec on/off composes in one batch with zero extra
      dispatches;
    * ``accepts`` is the raw per-row accepted-draft count (before
      eos/budget truncation) for the acceptance-rate instruments.

    Without ``draft_cfg`` (PROMPT-LOOKUP / any host draft source) the
    draft scan, draft pools, ``dtables`` and ``prev`` drop out and
    the candidates arrive as an input:

    ``fn(params, kpool, vpool, [kscale, vscale,] tables, lens, tok,
    drafts [B, gamma], active, remaining, spec_on, eos, key) ->
    (pools..., toks, dones, emits, accepts, tok', lens', remaining',
    active')``

    With ``mesh`` (mp>1) the draft micro-steps run through the
    :func:`_build_tp_inner` seam (``tp_allreduce="int8"`` allowed —
    quantization noise only costs acceptance, never correctness) and
    the verify through :func:`_spec_verify_body_tp` (exact-fp psums);
    scatter and fold ride GSPMD at the outer-jit level like
    :func:`make_mixed_step`.  ``kv_quant``/``draft_kv_quant`` select
    int8 pool forms independently per cache.
    """
    G = int(gamma)
    if G < 1:
        raise ValueError(f"gamma must be >= 1, got {gamma}")
    C = G + 1
    q8 = kv_quant == "int8"
    dq8 = draft_kv_quant == "int8"
    draft = draft_cfg is not None
    mesh_key = mesh if (mesh is not None
                        and mesh.shape.get("mp", 1) > 1) else None
    ckey = (_cfg_key(cfg), _cfg_key(draft_cfg) if draft else None, G,
            kv_quant, draft_kv_quant if draft else None, mesh_key,
            tp_allreduce if mesh_key is not None else "fp32")
    hit = _spec_step_cache.get(ckey)
    if hit is not None:
        return hit

    from ..ops.pallas.paged_attention import quantize_kv_token
    dt = cfg.dtype

    if mesh_key is not None:
        verify = _spec_verify_body_tp(cfg, mesh, q8)
        dbase = _build_tp_inner(draft_cfg, mesh, 0.0, draft_kv_quant,
                                0, 1.0, tp_allreduce=tp_allreduce) \
            if draft else None
    else:
        verify = _spec_verify_body(cfg, q8)
        if draft:
            dstep, dstep_q8 = _build_step_fns(draft_cfg, 0.0, False,
                                              0, 1.0)
            dbase = dstep_q8 if dq8 else dstep
        else:
            dbase = None

    def core(params, dparams, kpool, vpool, kscale, vscale, dkp, dvp,
             dksc, dvsc, tables, dtables, lens, tok, prev, drafts_in,
             active, remaining, spec_on, eos, key):
        B = tok.shape[0]
        page = kpool.shape[3]
        S_ctx = tables.shape[1] * page

        if draft:
            # draft half: gamma+1 chained micro-steps (catch-up, tok,
            # then the drafts feeding themselves); junk writes for
            # inactive / spec-off rows land on draft page 0
            dtab = jnp.where((active & spec_on)[:, None], dtables, 0)
            subs = jax.random.split(key, G + 1)
            idx = jnp.arange(G + 1, dtype=lens.dtype)

            def micro(carry, inp):
                i, sub = inp
                if dq8:
                    kp, vp, ks, vs, feed = carry
                else:
                    kp, vp, feed = carry
                f = jnp.where(i == 0, prev,
                              jnp.where(i == 1, tok, feed))
                dl = jnp.maximum(lens - 1 + i, 0)
                if dq8:
                    kp, vp, ks, vs, out = dbase(
                        dparams, kp, vp, ks, vs, dtab, dl, f, sub)
                    out = out.astype(tok.dtype)
                    return (kp, vp, ks, vs, out), out
                kp, vp, out = dbase(dparams, kp, vp, dtab, dl, f,
                                    sub)
                out = out.astype(tok.dtype)
                return (kp, vp, out), out

            carry0 = (dkp, dvp, dksc, dvsc, tok) if dq8 \
                else (dkp, dvp, tok)
            carry, outs = jax.lax.scan(micro, carry0, (idx, subs))
            if dq8:
                dkp, dvp, dksc, dvsc = carry[:4]
            else:
                dkp, dvp = carry[:2]
            d = jnp.transpose(outs[1:], (1, 0))     # [B, G]
        else:
            d = drafts_in                           # [B, G]

        # verify half: score every candidate at its row's offset over
        # the cached pages, then the shared logits tail (greedy)
        cand = jnp.concatenate([tok[:, None], d], axis=1)  # [B, C]
        sc_k = kscale if q8 else jnp.zeros((1,), jnp.float32)
        sc_v = vscale if q8 else jnp.zeros((1,), jnp.float32)
        x, ks, vs = verify(params, cand, kpool, vpool, sc_k, sc_v,
                           tables, lens)
        with jax.named_scope("logits"):
            h = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
            logits = _mm(h, params["lm_head"], dt).astype(jnp.float32)
        with jax.named_scope("sample"):
            g = jnp.argmax(logits, axis=-1).astype(tok.dtype)  # [B, C]

        # scatter the C fresh K/V per row into the target pages;
        # inactive rows and beyond-capacity positions steer to junk
        # page 0 (beyond-lens entries are masked stale until the next
        # round overwrites them)
        with jax.named_scope("kv_write"):
            pos = lens[:, None] + jnp.arange(C, dtype=lens.dtype)
            ok = active[:, None] & (pos < S_ctx)
            pidx = jnp.where(ok, pos // page, 0)
            dest_page = jnp.where(
                ok, jnp.take_along_axis(tables, pidx, axis=1), 0)
            dp = dest_page.reshape(-1)
            ds = (pos % page).reshape(-1)
            Lyr, nkv_o, d_o = ks.shape[0], ks.shape[3], ks.shape[4]
            ksf = ks.reshape(Lyr, B * C, nkv_o, d_o)
            vsf = vs.reshape(Lyr, B * C, nkv_o, d_o)
            if q8:
                ksf, ksc2 = quantize_kv_token(ksf)
                vsf, vsc2 = quantize_kv_token(vsf)
            kpool = kpool.at[:, dp, :, ds, :].set(
                jnp.transpose(ksf, (1, 0, 2, 3)).astype(kpool.dtype))
            vpool = vpool.at[:, dp, :, ds, :].set(
                jnp.transpose(vsf, (1, 0, 2, 3)).astype(vpool.dtype))
            if q8:
                kscale = kscale.at[:, dp, :, ds].set(
                    jnp.transpose(ksc2, (1, 0, 2)))
                vscale = vscale.at[:, dp, :, ds].set(
                    jnp.transpose(vsc2, (1, 0, 2)))

        # accept fold: longest matching prefix + the correction token
        # == commit g[:, :k+1]; spec-off rows collapse to 1 (their
        # plain greedy token), so on/off mixes in one batch
        match = ((d == g[:, :G]) & spec_on[:, None]
                 & active[:, None])                 # [B, G]
        k_acc = jnp.sum(
            jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        n_acc = k_acc + 1

        def fold(carry, inp):
            j, gj = inp
            tok_c, prev_c, lens_c, rem_c, alive_c = carry
            em = alive_c & (j < n_acc)
            nxt = jnp.where(em, gj, tok_c)
            prev2 = jnp.where(em, tok_c, prev_c)
            lens2 = lens_c + em.astype(lens_c.dtype)
            rem2 = rem_c - em.astype(rem_c.dtype)
            done = em & ((nxt == eos) | (rem2 <= 0))
            return ((nxt, prev2, lens2, rem2, alive_c & ~done),
                    (nxt, done, em))

        jdx = jnp.arange(C, dtype=jnp.int32)
        (tok_f, prev_f, lens_f, rem_f, act_f), (toks, dones, emits) \
            = jax.lax.scan(fold, (tok, prev, lens, remaining, active),
                           (jdx, jnp.transpose(g, (1, 0))))

        outs = [kpool, vpool]
        if q8:
            outs += [kscale, vscale]
        if draft:
            outs += [dkp, dvp]
            if dq8:
                outs += [dksc, dvsc]
        outs += [toks, dones, emits, k_acc, tok_f]
        if draft:
            outs.append(prev_f)
        outs += [lens_f, rem_f, act_f]
        return tuple(outs)

    # positional layout varies with (draft, q8, dq8); unpack
    # generically so one core serves every form
    def fn(*args):
        it = iter(args)
        params = next(it)
        dparams = next(it) if draft else None
        kpool, vpool = next(it), next(it)
        kscale = next(it) if q8 else None
        vscale = next(it) if q8 else None
        if draft:
            dkp, dvp = next(it), next(it)
            dksc = next(it) if dq8 else None
            dvsc = next(it) if dq8 else None
        else:
            dkp = dvp = dksc = dvsc = None
        tables = next(it)
        dtables = next(it) if draft else None
        lens, tok = next(it), next(it)
        prev = next(it) if draft else tok
        drafts_in = None if draft else next(it)
        active, remaining = next(it), next(it)
        spec_on, eos, key = next(it), next(it), next(it)
        return core(params, dparams, kpool, vpool, kscale, vscale,
                    dkp, dvp, dksc, dvsc, tables, dtables, lens, tok,
                    prev, drafts_in, active, remaining, spec_on, eos,
                    key)

    i = 2 if draft else 1                  # index of kpool
    don = [i, i + 1]
    i += 2
    if q8:
        don += [i, i + 1]
        i += 2
    if draft:
        don += [i, i + 1]
        i += 2
        if dq8:
            don += [i, i + 1]
    jitted = jax.jit(fn, donate_argnums=tuple(don))
    _spec_step_cache[ckey] = jitted
    return jitted


def generate_paged(cfg: LlamaPretrainConfig, params, prompt,
                   max_new_tokens: int, cache: PagedKVCache,
                   temperature: float = 0.0, seed: int = 0,
                   fused: bool = True, top_k: int = 0,
                   top_p: float = 1.0):
    """Generate with the paged cache: dense prefill (one jitted causal
    forward collecting K/V, written into each row's pages), then the
    paged decode tail — by default ONE fused scan program with
    pre-allocated pages (``fused=True``); ``fused=False`` drives the
    per-token step from the host (the continuous-batching serving
    loop).  Rows keep INDEPENDENT lengths — mixed-length prompts do not
    round up to the batch max."""
    B, S = prompt.shape
    n, d = cfg.num_attention_heads, cfg.head_dim
    nkv = cfg.num_key_value_heads
    dt = cfg.dtype
    page = cache.page
    prompt = jnp.asarray(prompt)
    lens_np = cache.lens.copy()      # caller pre-allocated via alloc_row

    x, ks, vs = _prefill(cfg)(params, prompt)
    # write prompt K/V into pages: [L, B, S, nkv, d] -> per-row pages
    q8 = cache.kv_quant == "int8"
    kscale_pool = vscale_pool = None
    if q8:
        from ..ops.pallas.paged_attention import quantize_kv_token
        ks, ks_s = quantize_kv_token(ks)     # scales [L, B, S, nkv]
        vs, vs_s = quantize_kv_token(vs)
    S_pad = ((S + page - 1) // page) * page
    ks = jnp.pad(ks, ((0, 0), (0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    vs = jnp.pad(vs, ((0, 0), (0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    npg = S_pad // page
    # [L, B, npg, page, nkv, d] -> [L, B, npg, nkv, page, d]
    ks = ks.reshape(ks.shape[0], B, npg, page, nkv, d).transpose(
        0, 1, 2, 4, 3, 5)
    vs = vs.reshape(vs.shape[0], B, npg, page, nkv, d).transpose(
        0, 1, 2, 4, 3, 5)
    # .copy(): cache.tables is mutated by ensure_capacity while this
    # eager scatter may still be in flight (numpy -> jax is zero-copy
    # on CPU; see the loop below)
    used = cache.tables[:, :npg].copy()              # [B, npg]
    kpool = cache.kpool.at[:, used].set(ks.astype(cache.kpool.dtype))
    vpool = cache.vpool.at[:, used].set(vs.astype(cache.vpool.dtype))
    if q8:
        ks_s = jnp.pad(ks_s, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)),
                       constant_values=1.0)
        vs_s = jnp.pad(vs_s, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)),
                       constant_values=1.0)
        ks_s = ks_s.reshape(ks_s.shape[0], B, npg, page,
                            nkv).transpose(0, 1, 2, 4, 3)
        vs_s = vs_s.reshape(vs_s.shape[0], B, npg, page,
                            nkv).transpose(0, 1, 2, 4, 3)
        kscale_pool = cache.kscale.at[:, used].set(ks_s)
        vscale_pool = cache.vscale.at[:, used].set(vs_s)

    # per-row last REAL token's logits (rows may be shorter than S)
    last_idx = jnp.asarray(lens_np - 1)
    h = _rms_norm(x[jnp.arange(B), last_idx], params["final_norm"],
                  cfg.rms_norm_eps)
    logits = _mm(h, params["lm_head"], dt).astype(jnp.float32)
    key = jax.random.PRNGKey(seed)
    key, sub = jax.random.split(key)
    tok = _pick_token(logits, temperature, sub, top_k, top_p)

    if fused:
        # pre-allocate every page the tail will touch -> tables are
        # constant -> the whole tail is one scan program
        saved_lens = cache.lens.copy()
        for b in range(B):
            cache.ensure_capacity(b, new_tokens=max_new_tokens)
        gen = make_paged_generate_fused(cfg, max_new_tokens,
                                        temperature,
                                        kv_quant=cache.kv_quant,
                                        top_k=top_k, top_p=top_p)
        key, sub = jax.random.split(key)
        # two DISTINCT dummies: both args are donated and donating one
        # buffer twice is an error
        kpool, vpool, ksp, vsp, toks = gen(
            params, kpool, vpool,
            kscale_pool if q8 else jnp.zeros((1,), jnp.float32),
            vscale_pool if q8 else jnp.zeros((1,), jnp.float32),
            jnp.asarray(cache.tables.copy()),
            jnp.asarray(saved_lens), tok, sub)
        cache.kpool, cache.vpool = kpool, vpool
        if q8:
            cache.kscale, cache.vscale = ksp, vsp
        cache.lens = saved_lens + max_new_tokens - 1
        return jnp.transpose(toks)                   # [B, max_new]

    step = make_paged_decode_step(cfg, temperature,
                                  kv_quant=cache.kv_quant,
                                  top_k=top_k, top_p=top_p)
    out_toks = [tok]
    ksp, vsp = (kscale_pool, vscale_pool) if q8 else (None, None)
    for _ in range(max_new_tokens - 1):
        for b in range(B):
            cache.ensure_capacity(b)
        # COPIES, not views: jnp.asarray of a numpy array is zero-copy
        # on CPU, and the step consumes it asynchronously — mutating
        # cache.lens/tables on the host while the previous step is
        # still in flight corrupts its inputs (observed as a ~20%
        # per-process wrong-decode flake before the copy)
        tables = jnp.asarray(cache.tables.copy())
        lens = jnp.asarray(cache.lens.copy())
        key, sub = jax.random.split(key)
        if q8:
            kpool, vpool, ksp, vsp, tok = step(
                params, kpool, vpool, ksp, vsp, tables, lens, tok, sub)
        else:
            kpool, vpool, tok = step(params, kpool, vpool, tables,
                                     lens, tok, sub)
        cache.lens = cache.lens + 1     # rebind, never mutate in place
        out_toks.append(tok)
    cache.kpool, cache.vpool = kpool, vpool
    if q8:
        cache.kscale, cache.vscale = ksp, vsp
    return jnp.stack(out_toks, axis=1)               # [B, max_new]


def generate_auto(cfg: LlamaPretrainConfig, params, prompts,
                  max_new_tokens: int, temperature: float = 0.0,
                  seed: int = 0, page: int = 64,
                  cache: Optional[PagedKVCache] = None):
    """ADAPTIVE decode routing (round-4 verdict item 5): one entry
    point serves both regimes the way the reference's
    ``block_multihead_attention`` does (incubate/nn/functional/
    block_multihead_attention.py:19).

    * EQUAL-length batch, no pre-existing pool -> the dense
      single-program cache (measured 1,717 vs 1,260 tok/s at b=32
      equal lengths, PERF.md "Paged KV cache decode": the paged grid/
      page overhead buys nothing when no row pads).
    * RAGGED lengths (or a caller-managed pool) -> the paged path
      (HBM ∝ sum of real lengths; 2.2x on long-tail mixes).

    ``prompts``: a list of 1-D int arrays (possibly ragged) or an
    ``[B, S]`` array (uniform).  Returns ``[B, max_new_tokens]``.
    """
    lens = [len(p) for p in prompts] if isinstance(prompts,
                                                   (list, tuple)) \
        else [prompts.shape[1]] * prompts.shape[0]
    if cache is None and len(set(lens)) == 1:
        arr = np.stack([np.asarray(p) for p in prompts])
        from .decode import make_generate
        gen = make_generate(cfg, prompt_len=int(lens[0]),
                            max_new_tokens=max_new_tokens,
                            temperature=temperature)
        return gen(params, jnp.asarray(arr), jax.random.PRNGKey(seed))
    B = len(lens)
    S = max(lens)
    padded = np.zeros((B, S), np.int64)
    for b, p in enumerate(prompts):
        padded[b, :lens[b]] = np.asarray(p)
    if cache is None:
        pages_max = (S + max_new_tokens + page - 1) // page
        total = sum((L + max_new_tokens + page - 1) // page
                    for L in lens) + 1
        cache = PagedKVCache(cfg, num_pages=total, pages_max=pages_max,
                             batch=B, page=page)
    for b, L in enumerate(lens):
        # analysis: ignore[claim-lifecycle] reason=one-shot generate: the rows ARE the product (generate_paged decodes from them); on a fault a local cache dies with the call and a caller-owned one keeps its documented release_row responsibility
        cache.alloc_row(b, L)
    return generate_paged(cfg, params, padded, max_new_tokens, cache,
                          temperature=temperature, seed=seed)
