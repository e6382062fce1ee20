"""Automatic prefix caching: content-addressed KV page sharing.

The router's default strategy scores prefix-cache overlap
(``router/strategy.py`` renders the EPP ``prefix-cache-scorer``); this
module makes that real on the engine side, vLLM-APC-style but
page-granular and host-side only (the device cache is just pages — which
page holds which content is entirely host metadata):

* Full prompt pages are content-addressed by a **hash chain**
  (``H(parent_hash, block_tokens)``) so a block's identity includes its
  whole prefix.
* A new request reuses the longest chain of cached pages (capped at
  ``len(prompt) - 1`` tokens — the last token must be recomputed for its
  logits), increments their refcounts, and prefills only the suffix.
* Released pages with a registered hash become **evictable** (LRU) but
  stay addressable until the pool actually needs them — so back-to-back
  requests with shared system prompts skip most prefill compute.

Shared pages are never written: the suffix prefill starts past them, and
generated tokens land on private pages by construction (positions beyond
the reused prefix).
"""

from __future__ import annotations

import collections
from typing import Callable, Optional

from fusioninfer_tpu.engine.kv_cache import CacheConfig, PageAllocator
from fusioninfer_tpu.utils.blockhash import block_hashes

__all__ = ["block_hashes", "PrefixCachingAllocator"]

# ``block_hashes`` moved to fusioninfer_tpu.utils.blockhash (shared with
# the router's residency-aware prefix scorer and the host KV tier —
# identical chain, identical token encoding); re-exported here so every
# historical import site keeps working.  ``namespace`` partitions the
# content address space: KV computed under different LoRA adapters is
# different content for the same tokens, so the engine passes the
# adapter name — base-model and per-adapter prefixes never cross-hit.


class PrefixCachingAllocator(PageAllocator):
    """Page allocator with content-addressed sharing.

    Page states: *free* (no content), *owned* (referenced by ≥1 sequence;
    hashed pages may be shared by several), *evictable* (hashed content,
    zero references — reusable as-is via its hash, reclaimable under
    pressure, LRU order).
    """

    def __init__(self, cache_cfg: CacheConfig):
        super().__init__(cache_cfg)
        self._hash_to_page: dict[bytes, int] = {}
        self._page_hash: dict[int, bytes] = {}
        self._refs: dict[int, int] = {}  # page -> #sequences referencing
        self._evictable: "collections.OrderedDict[int, None]" = collections.OrderedDict()
        # per sequence: pages acquired via sharing (no write permission)
        self._shared_of: dict[str, list[int]] = {}
        self.hit_tokens_total = 0
        self.query_tokens_total = 0
        # hierarchical-KV hook: called as (page, block_hash) the moment
        # an evictable hashed page is reclaimed for reuse — the LAST
        # point its content is still addressable, so the engine can
        # offload the page's KV to the host tier before the pool
        # overwrites it (engine/kv_host_tier.py).  None = HBM-only.
        self.on_reclaim: Optional[Callable[[int, bytes], None]] = None

    # -- capacity ------------------------------------------------------------

    @property
    def free_pages(self) -> int:  # evictable pages are reclaimable
        return len(self._free) + len(self._evictable)

    def utilization(self) -> float:
        total = self.cache_cfg.n_pages - 1
        used = total - self.free_pages
        return 0.0 if total == 0 else used / total

    def _take_free_page(self) -> int:
        self.pages_allocated_total["full"] += 1
        if self._free:
            return self._free.pop()
        # reclaim the least-recently-used evictable page
        page, _ = self._evictable.popitem(last=False)
        h = self._page_hash.pop(page)
        del self._hash_to_page[h]
        if self.on_reclaim is not None:
            # offload hook BEFORE the page is handed out: the caller is
            # about to overwrite it, and the hook's device-side gather
            # must be dispatched first (program order on the stream)
            self.on_reclaim(page, h)
        return page

    # -- prefix matching -----------------------------------------------------

    def _usable_chain(self, prompt_tokens: list, namespace: bytes,
                      chain: Optional[list]) -> list:
        """The prompt's block-hash chain capped at the usable block count
        (``(len(prompt) - 1) // page_size`` — the last token is always
        recomputed for its logits, so its block can never be reused).
        ``chain`` short-circuits the hash: admission computes the FULL
        chain ONCE (``NativeEngine._admission_chain``) and threads it
        through the host-tier restore consult, :meth:`can_admit`,
        :meth:`match_prefix` and :meth:`register_blocks`, which used to
        hash the same prefix up to four times per request; it is capped
        here so callers can hand the full chain everywhere."""
        ps = self.cache_cfg.page_size
        usable_blocks = max(0, (len(prompt_tokens) - 1) // ps)
        if chain is not None:
            return chain[:usable_blocks]
        return block_hashes(prompt_tokens, ps, namespace)[:usable_blocks]

    def match_prefix(self, seq_id: str, prompt_tokens: list[int],
                     namespace: bytes = b"",
                     chain: Optional[list] = None) -> int:
        """Acquire the longest cached page chain for this prompt; returns
        the number of prefix TOKENS covered (multiple of page_size, capped
        at ``len(prompt) - 1`` so the last token is always recomputed).
        ``chain`` is the prompt's precomputed usable block-hash chain
        (see :meth:`_usable_chain`)."""
        ps = self.cache_cfg.page_size
        self.query_tokens_total += len(prompt_tokens)
        shared: list[int] = []
        for h in self._usable_chain(prompt_tokens, namespace, chain):
            page = self._hash_to_page.get(h)
            if page is None:
                break
            # recency bump (dict insertion order = the residency
            # digest's MRU order): a hot chain that keeps HITTING must
            # not age out of the top-K digest just because newer blocks
            # keep REGISTERING — the scorer would read the true holder
            # as empty and route repeat-prefix traffic away from it
            self._hash_to_page[h] = self._hash_to_page.pop(h)
            shared.append(page)
        for page in shared:
            self._refs[page] = self._refs.get(page, 0) + 1
            self._evictable.pop(page, None)
        if shared:
            self._shared_of[seq_id] = list(shared)
            self._owned.setdefault(seq_id, []).extend(shared)
        self.hit_tokens_total += len(shared) * ps
        return len(shared) * ps

    # -- allocation ----------------------------------------------------------

    def can_allocate(self, n_tokens: int) -> bool:
        need = self.pages_needed(n_tokens)
        return need <= self.free_pages and need <= self.cache_cfg.max_pages_per_seq

    def _peek_match(self, prompt_tokens: list[int],
                    namespace: bytes = b"",
                    chain: Optional[list] = None) -> tuple[int, int]:
        """(matched pages, matched pages currently evictable) — a dry run
        of :meth:`match_prefix` that acquires nothing."""
        matched = evictable = 0
        for h in self._usable_chain(prompt_tokens, namespace, chain):
            page = self._hash_to_page.get(h)
            if page is None:
                break
            matched += 1
            evictable += 1 if page in self._evictable else 0
        return matched, evictable

    def can_admit(self, prompt_tokens: list, extra_tokens: int = 1,
                  namespace: bytes = b"",
                  chain: Optional[list] = None) -> bool:
        """Reuse-aware admission: a request whose prompt is mostly cached
        needs only the uncovered pages.  Matched-but-evictable pages count
        as free AND as matched, so subtract them from both sides."""
        need_total = self.pages_needed(len(prompt_tokens) + extra_tokens)
        if need_total > self.cache_cfg.max_pages_per_seq:
            return False
        matched, evictable = self._peek_match(list(prompt_tokens), namespace,
                                              chain)
        return need_total - matched <= self.free_pages - evictable

    def allocate(self, seq_id: str, n_tokens: int) -> list[int]:
        """Grow ``seq_id``'s table to cover ``n_tokens`` total (shared
        prefix pages count toward the total)."""
        have = len(self._owned.get(seq_id, []))
        need_total = self.pages_needed(n_tokens)
        extra = need_total - have
        if need_total > self.cache_cfg.max_pages_per_seq:
            raise MemoryError(
                f"sequence of {n_tokens} tokens exceeds max_pages_per_seq="
                f"{self.cache_cfg.max_pages_per_seq}"
            )
        if extra > self.free_pages:
            raise MemoryError(
                f"KV cache exhausted: need {extra} pages, have {self.free_pages}"
            )
        pages = [self._take_free_page() for _ in range(max(0, extra))]
        self._owned.setdefault(seq_id, []).extend(pages)
        return pages

    def extend(self, seq_id: str, current_tokens: int, new_tokens: int) -> list[int]:
        return self.allocate(seq_id, current_tokens + new_tokens)

    # -- publishing ----------------------------------------------------------

    def register_blocks(self, seq_id: str, prompt_tokens: list[int],
                        namespace: bytes = b"",
                        chain: Optional[list] = None) -> None:
        """Content-address this sequence's full private prompt pages so
        later requests can share them (called once after prefill).
        ``chain`` is the prompt's precomputed FULL block-hash chain
        (uncapped — the publish covers every complete page, including
        the one :meth:`_usable_chain` excludes from matching)."""
        ps = self.cache_cfg.page_size
        pages = self._owned.get(seq_id, [])
        hashes = (chain if chain is not None
                  else block_hashes(prompt_tokens, ps, namespace))
        for i, h in enumerate(hashes):
            if i >= len(pages):
                break
            page = pages[i]
            existing = self._page_hash.get(page)
            if existing is not None:
                continue  # already published (shared prefix)
            if h in self._hash_to_page:
                continue  # another sequence's page already owns this content
            self._page_hash[page] = h
            self._hash_to_page[h] = page
            self._refs[page] = self._refs.get(page, 0) + 1

    # -- hierarchical KV (host tier) -----------------------------------------

    def has_block(self, h: bytes) -> bool:
        """Is this content hash addressable in HBM right now?"""
        return h in self._hash_to_page

    def adopt_block(self, h: bytes) -> int:
        """Claim a page for RESTORED content (host tier → HBM): takes a
        free page (reclaiming LRU evictable content if needed — which
        may itself cascade an offload via ``on_reclaim``), registers the
        hash, and parks the page **evictable** so it counts as free for
        admission until a ``match_prefix`` actually pins it.  The caller
        uploads the page's KV immediately after; both run on the engine
        thread, so no consumer can observe the registered-but-unwritten
        gap.  Raises ``MemoryError`` when the pool is exhausted."""
        if h in self._hash_to_page:
            return self._hash_to_page[h]
        if not self._free and not self._evictable:
            raise MemoryError("KV cache exhausted: no page for restore")
        page = self._take_free_page()
        self._page_hash[page] = h
        self._hash_to_page[h] = page
        self._evictable[page] = None
        self._evictable.move_to_end(page)
        return page

    def touch_block(self, h: bytes) -> bool:
        """MRU-bump a resident hashed block — registration order (the
        residency digest) AND, when parked evictable, reclaim order —
        without acquiring it.  Returns whether the block was evictable:
        the restore planner uses touch + that count to keep its own
        adoptions from reclaiming the very chain it is restoring."""
        page = self._hash_to_page.get(h)
        if page is None:
            return False
        self._hash_to_page[h] = self._hash_to_page.pop(h)
        if page in self._evictable:
            self._evictable.move_to_end(page)
            return True
        return False

    def resident_block_hashes(self, limit: int = 0) -> list[bytes]:
        """Hashes addressable in HBM, most-recently-registered first
        (the residency digest the engine exports to the router);
        ``limit`` > 0 caps the list.

        Called from HTTP handler threads (``/v1/prefix_residency``)
        while the engine thread mutates the dict — the allocator is
        engine-thread-owned and deliberately lock-free, so the snapshot
        retries around a concurrent resize and degrades to an empty
        digest (the router's scorer then falls back to its history
        heuristic) rather than 500ing the scrape."""
        hashes: list[bytes] = []
        for _ in range(5):
            try:
                hashes = list(self._hash_to_page)
                break
            except RuntimeError:  # resized mid-iteration by the engine
                continue
        hashes.reverse()
        return hashes[:limit] if limit else hashes

    def resident_blocks(self) -> int:
        return len(self._hash_to_page)

    # -- release -------------------------------------------------------------

    def _drop_page_ref(self, page: int) -> None:
        """One owner lets go of ``page``: unref shared/hashed pages
        (retaining content as evictable at zero refs), free private ones.
        Base-class ``trim_window``/``release`` route every drop through
        this hook, so windowed reclamation inherits sharing semantics."""
        if page in self._refs:
            self._refs[page] -= 1
            if self._refs[page] <= 0:
                del self._refs[page]
                # retain content: evictable until the pool needs it
                self._evictable[page] = None
                self._evictable.move_to_end(page)
        else:
            self._free.append(page)

    def release(self, seq_id: str) -> None:
        self._shared_of.pop(seq_id, None)
        super().release(seq_id)

    def prefix_hit_rate(self) -> float:
        if self.query_tokens_total == 0:
            return 0.0
        return self.hit_tokens_total / self.query_tokens_total
