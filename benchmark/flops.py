"""Operations and bytes from shapes: the benchmark's own arithmetic, so that
no later PR moves a utilisation by recounting."""

from __future__ import annotations

from typing import Any, Dict


def llama_matmul_params_per_layer(c: Dict[str, Any]) -> int:
    """Parameters of one block that a token multiplies: q, k, v, o and the
    three SwiGLU matrices (norm weights do no matmul)."""
    h, kv = c["hidden_size"], c["num_key_value_heads"] * c["head_dim"]
    q = c["num_attention_heads"] * c["head_dim"]
    return h * q + 2 * h * kv + q * h + 3 * h * c["intermediate_size"]


def llama_param_count(c: Dict[str, Any]) -> int:
    h = c["hidden_size"]
    per_layer = llama_matmul_params_per_layer(c) + 2 * h
    head = 0 if c["tie_word_embeddings"] else c["vocab_size"] * h
    return c["num_hidden_layers"] * per_layer + c["vocab_size"] * h + head + h


def llama_train_flops_per_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward operations one trained token needs: 6 per matmul
    parameter (2 forward, 4 backward), causal attention at half the square
    (forward 2*T*d for QK^T and PV together, times 3 with the backward), and
    the untied head.  The embedding lookup multiplies nothing; recomputation
    is not counted."""
    d = c["num_attention_heads"] * c["head_dim"]
    per_layer = 6.0 * llama_matmul_params_per_layer(c) + 6.0 * seq_len * d
    head = 6.0 * c["hidden_size"] * c["vocab_size"]
    return c["num_hidden_layers"] * per_layer + head


def kv_bytes_per_position(c: Dict[str, Any], itemsize: int = 2) -> int:
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * c["head_dim"] * itemsize
