"""Paged KV cache.

Device-side: two stacked arrays ``[n_layers, n_kv_heads, n_pages,
page_size, head_dim]`` (k and v).  Pages are the allocation unit; a
sequence owns a list of pages recorded in a host-side page table.  The
last page index is reserved as a scratch ("trash") page so padded token
positions can write somewhere harmless while shapes stay static.

The layout is **head-major** (kv-head axis ahead of the page axis): the
paged-attention kernel DMAs one ``[page_size, head_dim]`` tile per
(sequence, kv-head) program, and with head-major storage that slice only
indexes leading dims — Mosaic requires the tiled trailing two dims stay
whole (see :mod:`fusioninfer_tpu.ops.paged_attention`).  The kv-head
axis is also the ``tp`` shard axis.

A model with latent attention (``cfg.is_mla``) caches ONE row per
position and attention, shared by every head: the pool is one array
``cache["kv"]`` of ``[n_cache_layers, 1, n_pages, page_size, W]`` (a
layer of the stack may hold two attentions: ``cfg.n_cache_layers``;
``W`` =
``cfg.latent_row_width``: the row's 576 values in whole 128-lane tiles)
behind the same pages, page tables and allocator.  A model with expert
layers also carries their counters in ``cache["moe_stats"]``
(``transformer.MOE_STATS``): the pool is the one tree every forward
threads and donates, so the counts add up on the device and ride along.

A model whose layers differ by kind (``cfg.cache_by_kind``: full and
windowed attention mixed) keeps a pool a KIND: ``cache["k"]`` /
``["v"]`` hold the full-attention layers ``[L_full, KV, n_pages, ps,
Hd]`` and ``cache["k_win"]`` / ``["v_win"]`` the windowed ones
``[L_win, KV, n_window_pages, ps, Hd]``, each with a trash page of its
own.  A sequence owns one page list a kind, both indexed by position: a
full-kind page lives as long as its sequence; a window-kind page exists
only while a query can still see it (``PageAllocator.cover_window``
materialises the pages a step writes and releases those that fell below
the window; placeholders keep the positions).  A model of ONE kind,
windowed or full, keeps the one pool under today's names.

A model with learned sparse attention (``cfg.is_sparse``) adds a third
paged array, ``cache["k_idx"]`` ``[L, n_pages, ps, W]`` (``W`` =
``cfg.index_row_width``: the key's 64 values in a whole 128-lane tile, as
the device stores a 64-wide minor dimension anyway): one indexer key a
position and layer, under the SAME page ids and page
tables as ``k`` / ``v``, so the allocator, admission and whole-page reuse
need no second list; and ``cache["dsa_stats"]``, the positions the
indexer scored and the attention chose, summed on the device as (low,
high) uint32 words.

Host-side: a free-list allocator (:class:`PageAllocator`) — allocation is
a Python-time concern, never traced.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from fusioninfer_tpu.models.config import ModelConfig


@dataclass(frozen=True)
class CacheConfig:
    n_pages: int = 256  # includes the reserved trash page
    page_size: int = 128
    max_pages_per_seq: int = 32
    # "model" = pages in the model dtype (bf16); "int8" = per-(token,
    # kv-head) symmetric int8 pages + f32 scales — half the page bytes
    # (decode attention's HBM traffic) and twice the pool for the same
    # budget.  Scales live in a SEPARATE [..., 1, page_size] array so
    # every per-page slice keeps whole trailing tiles (Mosaic-safe,
    # same argument as the head-major page layout).
    kv_dtype: str = "model"
    # the window kind's pool of a cache kept by layer kind (0 = the
    # model has one kind): its pages, trash page included, and the most
    # of them one sequence holds at a time: those its window reaches
    # plus those the longest row a step writes lands in
    n_window_pages: int = 0
    max_window_pages_per_seq: int = 0

    @property
    def trash_page(self) -> int:
        return self.n_pages - 1

    @property
    def window_trash_page(self) -> int:
        return self.n_window_pages - 1

    @property
    def by_kind(self) -> bool:
        return self.n_window_pages > 0

    @property
    def max_len(self) -> int:
        return self.max_pages_per_seq * self.page_size

    @property
    def quantized(self) -> bool:
        return self.kv_dtype == "int8"

    def validate(self) -> "CacheConfig":
        if self.page_size < 1 or self.n_pages < 2 or self.max_pages_per_seq < 1:
            raise ValueError(f"invalid cache config {self}")
        if self.kv_dtype not in ("model", "int8"):
            raise ValueError(f"unknown kv_dtype {self.kv_dtype!r}")
        usable = self.n_pages - 1  # trash page reserved
        if self.max_pages_per_seq > usable:
            # otherwise a request the engine admits (fits max_len) could need
            # more pages than exist and spin in the scheduler forever
            raise ValueError(
                f"max_pages_per_seq={self.max_pages_per_seq} exceeds usable pages "
                f"{usable} (n_pages={self.n_pages} minus the trash page)"
            )
        if self.by_kind:
            if self.quantized:
                raise ValueError("int8 pages are not available for a cache "
                                 "kept by layer kind")
            if not 1 <= self.max_window_pages_per_seq < self.n_window_pages:
                raise ValueError(
                    f"max_window_pages_per_seq={self.max_window_pages_per_seq}"
                    f" does not fit the window pool's "
                    f"{self.n_window_pages - 1} usable pages")
        return self


def init_kv_cache(cfg: ModelConfig, cache_cfg: CacheConfig) -> dict:
    from fusioninfer_tpu.models.transformer import MOE_STATS

    stats = ({"moe_stats": jnp.zeros((len(MOE_STATS),), jnp.uint32)}  # noqa:trace-dynamic-dim — fixed counter layout
             if cfg.is_moe else {})
    if cfg.is_mla:
        if cache_cfg.quantized:
            raise ValueError("int8 pages are not available for a latent "
                             "(MLA) cache")
        return {"kv": jnp.zeros(
            (cfg.n_cache_layers, 1, cache_cfg.n_pages, cache_cfg.page_size,
             cfg.latent_row_width), cfg.jax_dtype), **stats}
    if cfg.is_sparse:
        if cache_cfg.quantized:
            raise ValueError("int8 pages are not available beside an "
                             "indexer-key cache")
        stats = {**stats,
                 "k_idx": jnp.zeros(
                     (cfg.n_cache_layers, cache_cfg.n_pages,
                      cache_cfg.page_size, cfg.index_row_width),
                     cfg.jax_dtype),
                 "dsa_stats": jnp.zeros((2, 2), jnp.uint32)}
    if cfg.cache_by_kind != cache_cfg.by_kind:
        raise ValueError(
            f"model {cfg.name} keeps "
            f"{'a pool a layer kind' if cfg.cache_by_kind else 'one pool'}"
            f" but the cache config has n_window_pages="
            f"{cache_cfg.n_window_pages} (kv_cache.auto_cache_config sizes "
            f"both pools)")
    shape = (
        cfg.n_pool_layers(""),
        cfg.n_kv_heads,
        cache_cfg.n_pages,
        cache_cfg.page_size,
        cfg.head_dim,
    )
    if cache_cfg.by_kind:
        window_shape = (cfg.n_pool_layers("_win"), cfg.n_kv_heads,
                        cache_cfg.n_window_pages, cache_cfg.page_size,
                        cfg.head_dim)
        return {
            "k": jnp.zeros(shape, cfg.jax_dtype),
            "v": jnp.zeros(shape, cfg.jax_dtype),
            "k_win": jnp.zeros(window_shape, cfg.jax_dtype),
            "v_win": jnp.zeros(window_shape, cfg.jax_dtype),
            **stats,
        }
    if cache_cfg.quantized:
        scale_shape = (
            cfg.n_cache_layers,
            cfg.n_kv_heads,
            cache_cfg.n_pages,
            1,
            cache_cfg.page_size,
        )
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(scale_shape, jnp.float32),
            "v_scale": jnp.zeros(scale_shape, jnp.float32),
            **stats,
        }
    return {
        "k": jnp.zeros(shape, cfg.jax_dtype),
        "v": jnp.zeros(shape, cfg.jax_dtype),
        **stats,
    }


def page_bytes(cfg: ModelConfig, page_size: int,
               kv_dtype: str = "model", pool: str = "") -> int:
    """Device bytes one KV page costs (k + v, or the latent rows; all
    layers of the pool ``pool``: a model of one layer kind has the one
    pool ""; the indexer keys of a sparse-attention model).  A latent
    row is priced at the width it is stored at."""
    if cfg.is_mla:
        return (cfg.n_cache_layers * page_size * cfg.latent_row_width
                * jnp.dtype(cfg.jax_dtype).itemsize)
    if kv_dtype == "int8":
        per_token = cfg.head_dim * 1 + 4  # int8 values + one f32 scale
    else:
        per_token = cfg.head_dim * jnp.dtype(cfg.jax_dtype).itemsize
    index_keys = (cfg.n_cache_layers * page_size * cfg.index_row_width
                  * jnp.dtype(cfg.jax_dtype).itemsize if cfg.is_sparse else 0)
    return (2 * cfg.n_pool_layers(pool) * page_size * cfg.n_kv_heads
            * per_token + index_keys)


def window_pages_per_seq(cfg: ModelConfig, page_size: int,
                         step_span: int, max_pages_per_seq: int) -> int:
    """The most window-kind pages one sequence holds at a time: those
    that ``sliding_window`` positions behind the first token of a row
    and the ``step_span`` tokens the row writes can touch, one more for
    where the first of them falls in its page; never more than a whole
    sequence's."""
    reach = cfg.sliding_window + step_span
    return min(max_pages_per_seq, -(-reach // page_size) + 1)


def model_param_bytes(cfg: ModelConfig) -> int:
    """Weight footprint (bytes) computed from shapes — no allocation.
    Quantization-aware: int8 configs budget the quantized tree, which is
    what actually occupies HBM when the engine serves them."""
    if cfg.quantization == "int8":
        from fusioninfer_tpu.models.quantization import quantized_param_bytes

        return quantized_param_bytes(cfg)
    from fusioninfer_tpu.models.transformer import init_params

    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    return sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in jax.tree.leaves(shapes))


def auto_cache_config(
    cfg: ModelConfig,
    page_size: int,
    max_model_len: int,
    max_batch_size: int,
    hbm_utilization: float = 0.85,
    tp: int = 1,
    hbm_bytes: int | None = None,
    prefix_caching: bool = True,
    kv_dtype: str = "model",
    step_span: int | None = None,
) -> CacheConfig:
    """Size the page pool from device memory, vLLM's ``gpu_memory_utilization``
    equivalent.

    Peak *demand* is ``max_batch_size × pages_per_seq + 1`` — the HBM math
    acts as a feasibility check first: if that request-shaped pool does
    not fit the budget, fail fast at startup rather than OOM mid-serving.

    With ``prefix_caching`` (the engine default) released pages are
    retained as evictable cache, so pages beyond peak demand directly
    raise the prefix hit rate — the pool then grows into remaining HBM
    headroom, capped at 4× demand (beyond that, hit-rate returns are
    negligible while host-side page-table bookkeeping isn't free).
    Without prefix caching the pool stays demand-sized: extra pages could
    never be allocated.

    The CPU reports no memory limit and gets request-shaped sizing; an
    accelerator that reports none is an error, not a fallback — a pool
    sized blind either wastes the chip or OOMs it mid-serving.  With
    tensor parallelism both weights and KV heads are sharded, so
    per-device cost divides by ``tp`` on both sides of the subtraction.

    A cache kept by layer kind (``cfg.cache_by_kind``) is the same rule
    over the SUM of its pools: demand is ``max_batch_size × pages a
    sequence can hold in the kind × that kind's page bytes`` for each
    kind (the window kind: :func:`window_pages_per_seq` of
    ``step_span``, the longest row a step writes — the token budget;
    unknown, a whole sequence), and what is left of the budget grows
    both pools by one factor, the proportion a long sequence uses them
    in.
    """
    pages_per_seq = max(1, -(-max_model_len // page_size))
    min_pages = pages_per_seq * max_batch_size + 1
    window_per_seq = min_window = 0
    if cfg.cache_by_kind:
        window_per_seq = window_pages_per_seq(
            cfg, page_size, step_span or max_model_len, pages_per_seq)
        min_window = window_per_seq * max_batch_size + 1
    if hbm_bytes is None:
        # local_devices: under multi-process serving, devices()[0] is
        # the leader's device and MemoryStats on a non-addressable
        # device raises on every follower
        device = jax.local_devices()[0]
        hbm_bytes = (device.memory_stats() or {}).get("bytes_limit")
        if not hbm_bytes and device.platform != "cpu":
            raise RuntimeError(
                f"{device.device_kind} reports no bytes_limit: cannot size "
                "the KV page pool from device memory")
    n_pages, n_window = min_pages, min_window
    if hbm_bytes:
        budget = int(hbm_bytes * hbm_utilization) - model_param_bytes(cfg) // tp
        # bytes of one page of every pool in the proportion of demand:
        # for a model of one kind, a page's bytes
        unit = max(1, page_bytes(cfg, page_size, kv_dtype) // tp)
        if min_window:
            unit += (page_bytes(cfg, page_size, kv_dtype, "_win") // tp
                     * min_window / min_pages)
        fit = int(budget // unit)
        if fit < min_pages:
            raise ValueError(
                f"model {cfg.name} with max_model_len={max_model_len} × "
                f"max_batch_size={max_batch_size} needs {min_pages} KV pages "
                + (f"and {min_window} window-kind pages " if min_window
                   else "")
                + f"but only {max(0, int(fit))} fit in "
                f"{hbm_utilization:.0%} of {hbm_bytes / 2**30:.1f} GiB HBM "
                f"after weights; lower max_batch_size/max_model_len or raise tp"
            )
        if prefix_caching:
            n_pages = min(fit, 4 * min_pages)
            n_window = min_window * n_pages // min_pages
    return CacheConfig(
        n_pages=n_pages, page_size=page_size, max_pages_per_seq=pages_per_seq,
        kv_dtype=kv_dtype, n_window_pages=n_window,
        max_window_pages_per_seq=window_per_seq,
    ).validate()


def kv_cache_bytes(cfg: ModelConfig, cache_cfg: CacheConfig) -> int:
    return (cache_cfg.n_pages * page_bytes(cfg, cache_cfg.page_size,
                                           cache_cfg.kv_dtype)
            + cache_cfg.n_window_pages * page_bytes(
                cfg, cache_cfg.page_size, cache_cfg.kv_dtype, "_win"))


class PageAllocator:
    """Host-side free list over cache pages (trash page never handed out).

    Over a cache kept by layer kind (``cache_cfg.by_kind``; ``window``
    is then the window kind's width in positions) a sequence owns a page
    list a kind.  The full kind's is this allocator's as ever.  The
    window kind's has the same positions and holds a page only where a
    query can still see one: :meth:`cover_window` materialises the pages
    a step is about to write and lets go of those below the window
    (:meth:`trim_window` acts on this kind alone).  A sequence never
    holds more than ``max_window_pages_per_seq`` of them, and admission
    RESERVES that many (``can_allocate``): once a sequence is in,
    covering its next row cannot fail, whatever the others do."""

    def __init__(self, cache_cfg: CacheConfig, window: int | None = None):
        self.cache_cfg = cache_cfg
        self._free: list[int] = list(range(cache_cfg.n_pages - 1))
        self._owned: dict[str, list[int]] = {}
        self._trim_mark: dict[str, int] = {}  # seq -> pages already trimmed
        self.window = window
        if cache_cfg.by_kind and not window:
            raise ValueError("a cache kept by layer kind needs the window "
                             "kind's width")
        self._window_free: list[int] = list(
            range(max(0, cache_cfg.n_window_pages - 1)))
        self._window_owned: dict[str, list[int]] = {}
        # counters the engine renders (fusioninfer:kv_pages_allocated_total
        # {kind}, fusioninfer:kv_window_pages_trimmed_total)
        self.pages_allocated_total = {"full": 0, "window": 0}
        self.window_pages_trimmed_total = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.cache_cfg.n_pages - 1) - len(self._free)

    @property
    def window_used_pages(self) -> int:
        return max(0, self.cache_cfg.n_window_pages - 1) - len(
            self._window_free)

    def pages_in_use(self) -> dict[str, int]:
        """kind -> pages handed out now ("window" only over a cache
        kept by layer kind)."""
        out = {"full": (self.cache_cfg.n_pages - 1) - self.free_pages}
        if self.cache_cfg.by_kind:
            out["window"] = self.window_used_pages
        return out

    def utilization(self) -> float:
        """The share of the pool in use: of the FULLER pool over a
        cache kept by layer kind."""
        total = self.cache_cfg.n_pages - 1
        used = 0.0 if total == 0 else self.used_pages / total
        if self.cache_cfg.by_kind:
            used = max(used, self.window_used_pages
                       / (self.cache_cfg.n_window_pages - 1))
        return used

    def _window_slot_free(self) -> bool:
        """Whether the window pool can take one more sequence at the
        most a sequence ever holds there (admission reserves that)."""
        if not self.cache_cfg.by_kind:
            return True
        cc = self.cache_cfg
        return ((len(self._window_owned) + 1) * cc.max_window_pages_per_seq
                <= cc.n_window_pages - 1)

    def pages_needed(self, n_tokens: int) -> int:
        ps = self.cache_cfg.page_size
        return max(1, -(-n_tokens // ps))

    def can_allocate(self, n_tokens: int) -> bool:
        need = self.pages_needed(n_tokens)
        return (need <= len(self._free)
                and need <= self.cache_cfg.max_pages_per_seq
                and self._window_slot_free())

    def can_admit(self, prompt_tokens: list, extra_tokens: int = 1,
                  namespace: bytes = b"", chain=None) -> bool:
        """Admission check for a new request (prefix-caching subclasses
        account for reusable cached pages; ``namespace`` partitions their
        content address space, e.g. per LoRA adapter, and ``chain`` lets
        the caller pass the prompt's precomputed block-hash chain so
        admission hashes once, not per check)."""
        del namespace, chain  # no content addressing in the base allocator
        return self.can_allocate(len(prompt_tokens) + extra_tokens)

    def allocate(self, seq_id: str, n_tokens: int) -> list[int]:
        need = self.pages_needed(n_tokens)
        if need > len(self._free):
            raise MemoryError(f"KV cache exhausted: need {need} pages, have {len(self._free)}")
        if need > self.cache_cfg.max_pages_per_seq:
            raise MemoryError(
                f"sequence of {n_tokens} tokens exceeds max_pages_per_seq={self.cache_cfg.max_pages_per_seq}"
            )
        if seq_id not in self._window_owned and not self._window_slot_free():
            raise MemoryError(
                "KV cache exhausted: the window-kind pool holds "
                f"{len(self._window_owned)} sequences of "
                f"{self.cache_cfg.max_window_pages_per_seq} pages at most")
        pages = [self._free.pop() for _ in range(need)]
        self._owned.setdefault(seq_id, []).extend(pages)
        self.pages_allocated_total["full"] += need
        if self.cache_cfg.by_kind:
            self._window_owned.setdefault(seq_id, [])
        return pages

    def extend(self, seq_id: str, current_tokens: int, new_tokens: int) -> list[int]:
        """Grow a sequence's page list to cover ``current + new`` tokens."""
        have = len(self._owned.get(seq_id, []))
        need_total = self.pages_needed(current_tokens + new_tokens)
        if need_total > self.cache_cfg.max_pages_per_seq:
            raise MemoryError("sequence exceeds max_pages_per_seq")
        extra = need_total - have
        if extra <= 0:
            return []
        if extra > len(self._free):
            raise MemoryError("KV cache exhausted on extend")
        pages = [self._free.pop() for _ in range(extra)]
        self._owned[seq_id].extend(pages)
        self.pages_allocated_total["full"] += extra
        return pages

    def cover_window(self, seq_id: str, first_token: int,
                     end_token: int) -> None:
        """Ready the window kind's pages for a row of ``seq_id`` that
        writes positions ``[first_token, end_token)``: release the pages
        wholly below what the row's first query sees (``first_token -
        window + 1``), then materialise every page from there up to the
        row's last position.  No-op for a cache of one kind.  A sequence
        within ``max_window_pages_per_seq`` always succeeds (admission
        reserved it); a row that spans more raises MemoryError."""
        if not self.cache_cfg.by_kind:
            return
        ps = self.cache_cfg.page_size
        first_live = max(0, first_token - self.window + 1) // ps
        self.trim_window(seq_id, first_live)
        pages = self._window_owned[seq_id]
        end = -(-end_token // ps)
        trash = self.cache_cfg.window_trash_page
        pages.extend([trash] * (end - len(pages)))
        missing = [i for i in range(first_live, end) if pages[i] == trash]
        held = sum(p != trash for p in pages)
        if (held + len(missing) > self.cache_cfg.max_window_pages_per_seq
                or len(missing) > len(self._window_free)):
            raise MemoryError(
                f"a row of {end_token - first_token} tokens needs "
                f"{held + len(missing)} window-kind pages; the pool was "
                f"sized for {self.cache_cfg.max_window_pages_per_seq} a "
                "sequence (kv_cache.auto_cache_config's step_span)")
        for i in missing:
            pages[i] = self._window_free.pop()
        self.pages_allocated_total["window"] += len(missing)

    def pages_of(self, seq_id: str) -> list[int]:
        return list(self._owned.get(seq_id, []))

    def _drop_page_ref(self, page: int) -> None:
        """One owner lets go of ``page``.  Subclass hook: the prefix-
        caching allocator unrefs shared pages here instead of freeing."""
        self._free.append(page)

    def trim_window(self, seq_id: str, first_live_page: int) -> int:
        """Sliding-window reclamation: drop pages wholly below the window
        (indices < ``first_live_page``), replacing them with trash-page
        placeholders so page-table indices keep their position mapping.
        The attention kernels start their page loop at the window's first
        live page, so trimmed entries are never read.  A per-sequence
        watermark makes the per-step call O(pages newly below the window),
        not O(all below-window pages).  Returns the pages dropped.  Over
        a cache kept by layer kind only the WINDOW kind's pages go: the
        full-attention layers keep every position."""
        by_kind = self.cache_cfg.by_kind
        pages = (self._window_owned if by_kind else self._owned).get(seq_id)
        if not pages:
            return 0
        trash = (self.cache_cfg.window_trash_page if by_kind
                 else self.cache_cfg.trash_page)
        start = self._trim_mark.get(seq_id, 0)
        end = min(first_live_page, len(pages))
        freed = 0
        for i in range(start, end):
            if pages[i] != trash:
                if by_kind:
                    self._window_free.append(pages[i])
                else:
                    self._drop_page_ref(pages[i])
                pages[i] = trash
                freed += 1
        if end > start:
            self._trim_mark[seq_id] = end
        self.window_pages_trimmed_total += freed
        return freed

    def release(self, seq_id: str) -> None:
        trash = self.cache_cfg.trash_page
        pages = self._owned.pop(seq_id, [])
        self._trim_mark.pop(seq_id, None)
        for p in pages:
            if p != trash:
                self._drop_page_ref(p)
        window_trash = self.cache_cfg.window_trash_page
        self._window_free.extend(
            p for p in self._window_owned.pop(seq_id, [])
            if p != window_trash)

    def blank_page_tables(self, n_rows: int) -> np.ndarray:
        """``n_rows`` inert page-table rows: trash pages only, ``[n_rows,
        max_pages_per_seq]``, or ``[n_rows, 2, max_pages_per_seq]`` over a
        cache kept by layer kind (the full kind's row, then the window
        kind's, each with its pool's own trash page)."""
        cc = self.cache_cfg
        rows = np.full((n_rows, cc.max_pages_per_seq), cc.trash_page,
                       np.int32)
        if not cc.by_kind:
            return rows
        return np.stack([rows, np.full_like(rows, cc.window_trash_page)],
                        axis=1)

    def page_table_row(self, seq_id: str) -> np.ndarray:
        """Fixed-width page table row, trash-padded (a row a kind,
        ``[2, max_pages_per_seq]``, over a cache kept by layer kind)."""
        row = self.blank_page_tables(1)[0]
        pages = self._owned.get(seq_id, [])
        if not self.cache_cfg.by_kind:
            row[: len(pages)] = pages
            return row
        window = self._window_owned.get(seq_id, [])
        row[0, : len(pages)] = pages
        row[1, : len(window)] = window
        return row
