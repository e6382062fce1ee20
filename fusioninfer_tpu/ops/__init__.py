"""Pallas TPU kernels for the hot attention ops, with jnp oracles.

* :mod:`flash_attention` — blockwise prefill/training attention.
* :mod:`paged_attention` — paged decode attention over the KV cache.
* :mod:`mla_attention` — ragged paged attention over latent (MLA) pages.
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
    paged_decode_attention,
    paged_prefill_attention,
    paged_verify_attention,
    pick_kv_splits,
    ragged_fits_vmem,
    ragged_paged_attention,
    ragged_paged_attention_kvsplit,
    ragged_token_rows,
    reference_paged_attention,
    reference_paged_prefill_attention,
    reference_paged_verify_attention,
    reference_ragged_paged_attention,
)
