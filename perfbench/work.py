"""Operations and bytes the algorithm needs, computed from the
configuration's file and the client's own record of the traffic — never
from what a kernel happened to do.  Kept with the benchmark."""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters every token is multiplied through: the layers'
    matrices and the output head (the embedding lookup is a gather)."""
    D, H, KV = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Hd, F, L, V = cfg["head_dim"], cfg["intermediate_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    per_layer = D * (H + 2 * KV) * Hd + H * Hd * D + 3 * D * F
    return L * per_layer + D * V


def token_flops(cfg: dict, context: int, with_head: bool = True) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions:
    2 per multiply-add through the matrices, plus QK^T and PV."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, Hd, L = cfg["num_attention_heads"], cfg["head_dim"], cfg["num_hidden_layers"]
    dense = 2.0 * (matmul_params(cfg) - (0 if with_head else D * V))
    return dense + 4.0 * L * H * Hd * context


def prompt_flops(cfg: dict, prompt_len: int) -> float:
    """Prefill of a whole prompt: every token through the layers, the
    head once (only the last position is projected), causal attention
    over sum(1..n) positions."""
    D, V = cfg["hidden_size"], cfg["vocab_size"]
    H, Hd, L = cfg["num_attention_heads"], cfg["head_dim"], cfg["num_hidden_layers"]
    dense = 2.0 * (matmul_params(cfg) - D * V) * prompt_len + 2.0 * D * V
    return dense + 4.0 * L * H * Hd * prompt_len * (prompt_len + 1) / 2.0


def kv_bytes_per_position(cfg: dict, kv_dtype_bytes: int = 2) -> int:
    """Bytes of keys and values one cached position holds, all layers."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * kv_dtype_bytes)


def decode_kv_bytes(cfg: dict, contexts: list[int]) -> float:
    """Bytes of KV cache that decoding one token at each of ``contexts``
    must read at the least."""
    return float(kv_bytes_per_position(cfg)) * float(sum(contexts))
