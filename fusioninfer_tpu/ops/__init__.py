"""Pallas TPU kernels for the hot attention ops, with jnp oracles.

* :mod:`flash_attention` — blockwise whole-prompt prefill / training
  attention.
* :mod:`paged_attention` — the ragged paged family over the KV cache:
  one flat token axis for decode rows, windows, chunks and suffixes,
  three grids (coalesced, per-head, KV-split) chosen by
  ``resolve_ragged_grid`` / ``pick_kv_splits``.
* :mod:`mla_attention` — ragged paged attention over latent (MLA) pages.
* :mod:`sharded` — the tensor-parallel ``shard_map`` wrappers.
* :mod:`dispatch` — trace-time kernel/reference selection.
"""

from fusioninfer_tpu.ops.dispatch import (  # noqa: F401
    flash_seq_ok,
    kernel_interpret,
    resolve_attn,
)
from fusioninfer_tpu.ops.flash_attention import (  # noqa: F401
    flash_attention,
    reference_attention,
)
from fusioninfer_tpu.ops.mla_attention import (  # noqa: F401
    mla_ragged_paged_attention,
    reference_mla_ragged_paged_attention,
)
from fusioninfer_tpu.ops.paged_attention import (  # noqa: F401
    KV_SPLIT_CHUNKS,
    RAGGED_BLOCK_Q,
    kvsplit_fits_vmem,
    pick_kv_splits,
    ragged_fits_vmem,
    ragged_paged_attention,
    ragged_paged_attention_kvsplit,
    ragged_token_rows,
    reference_ragged_paged_attention,
)
