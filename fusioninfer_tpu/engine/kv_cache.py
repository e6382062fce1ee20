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

    @property
    def trash_page(self) -> int:
        return self.n_pages - 1

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
    shape = (
        cfg.n_cache_layers,
        cfg.n_kv_heads,
        cache_cfg.n_pages,
        cache_cfg.page_size,
        cfg.head_dim,
    )
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
               kv_dtype: str = "model") -> int:
    """Device bytes one KV page costs (k + v, or the latent rows; all
    layers).  A latent row is priced at the width it is stored at."""
    if cfg.is_mla:
        return (cfg.n_cache_layers * page_size * cfg.latent_row_width
                * jnp.dtype(cfg.jax_dtype).itemsize)
    if kv_dtype == "int8":
        per_token = cfg.head_dim * 1 + 4  # int8 values + one f32 scale
    else:
        per_token = cfg.head_dim * jnp.dtype(cfg.jax_dtype).itemsize
    return 2 * cfg.n_cache_layers * page_size * cfg.n_kv_heads * per_token


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
    """
    pages_per_seq = max(1, -(-max_model_len // page_size))
    min_pages = pages_per_seq * max_batch_size + 1
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
    n_pages = min_pages
    if hbm_bytes:
        budget = int(hbm_bytes * hbm_utilization) - model_param_bytes(cfg) // tp
        fit = budget // max(1, page_bytes(cfg, page_size, kv_dtype) // tp)
        if fit < min_pages:
            raise ValueError(
                f"model {cfg.name} with max_model_len={max_model_len} × "
                f"max_batch_size={max_batch_size} needs {min_pages} KV pages "
                f"but only {max(0, int(fit))} fit in "
                f"{hbm_utilization:.0%} of {hbm_bytes / 2**30:.1f} GiB HBM "
                f"after weights; lower max_batch_size/max_model_len or raise tp"
            )
        if prefix_caching:
            n_pages = min(int(fit), 4 * min_pages)
    return CacheConfig(
        n_pages=n_pages, page_size=page_size, max_pages_per_seq=pages_per_seq,
        kv_dtype=kv_dtype,
    ).validate()


def kv_cache_bytes(cfg: ModelConfig, cache_cfg: CacheConfig) -> int:
    return cache_cfg.n_pages * page_bytes(cfg, cache_cfg.page_size,
                                          cache_cfg.kv_dtype)


class PageAllocator:
    """Host-side free list over cache pages (trash page never handed out)."""

    def __init__(self, cache_cfg: CacheConfig):
        self.cache_cfg = cache_cfg
        self._free: list[int] = list(range(cache_cfg.n_pages - 1))
        self._owned: dict[str, list[int]] = {}
        self._trim_mark: dict[str, int] = {}  # seq -> pages already trimmed

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return (self.cache_cfg.n_pages - 1) - len(self._free)

    def utilization(self) -> float:
        total = self.cache_cfg.n_pages - 1
        return 0.0 if total == 0 else self.used_pages / total

    def pages_needed(self, n_tokens: int) -> int:
        ps = self.cache_cfg.page_size
        return max(1, -(-n_tokens // ps))

    def can_allocate(self, n_tokens: int) -> bool:
        need = self.pages_needed(n_tokens)
        return need <= len(self._free) and need <= self.cache_cfg.max_pages_per_seq

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
        pages = [self._free.pop() for _ in range(need)]
        self._owned.setdefault(seq_id, []).extend(pages)
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
        return pages

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
        not O(all below-window pages).  Returns the pages dropped."""
        pages = self._owned.get(seq_id)
        if not pages:
            return 0
        trash = self.cache_cfg.trash_page
        start = self._trim_mark.get(seq_id, 0)
        end = min(first_live_page, len(pages))
        freed = 0
        for i in range(start, end):
            if pages[i] != trash:
                self._drop_page_ref(pages[i])
                pages[i] = trash
                freed += 1
        if end > start:
            self._trim_mark[seq_id] = end
        return freed

    def release(self, seq_id: str) -> None:
        trash = self.cache_cfg.trash_page
        pages = self._owned.pop(seq_id, [])
        self._trim_mark.pop(seq_id, None)
        for p in pages:
            if p != trash:
                self._drop_page_ref(p)

    def page_table_row(self, seq_id: str) -> np.ndarray:
        """Fixed-width page table row, trash-padded."""
        row = np.full(self.cache_cfg.max_pages_per_seq, self.cache_cfg.trash_page, np.int32)
        pages = self._owned.get(seq_id, [])
        row[: len(pages)] = pages
        return row
