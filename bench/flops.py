"""Operations of a dense decoder-only transformer, counted from its
configuration's published widths (Hugging Face ``config.json`` keys).

Matrix multiplications count two operations per multiply-add.  Norms,
rotary embeddings, softmax and the activation function are left out:
they are a fraction of a percent at these widths.
"""
from __future__ import annotations


def _head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def layer_matmul_params(c: dict) -> int:
    """Weights of one layer's projections and gated MLP."""
    d, hd = c["hidden_size"], _head_dim(c)
    q = d * c["num_attention_heads"] * hd
    kv = 2 * d * c["num_key_value_heads"] * hd
    o = c["num_attention_heads"] * hd * d
    mlp = 3 * d * c["intermediate_size"]
    return q + kv + o + mlp


def param_count(c: dict) -> int:
    """Every parameter: embeddings, projections, MLPs and norm gains
    (two per layer, the q/k norms of a qk-norm model, the final one)."""
    d, hd = c["hidden_size"], _head_dim(c)
    embed = c["vocab_size"] * d * (1 if c["tie_word_embeddings"] else 2)
    norms = 2 * d + (2 * hd if c.get("model_type") == "qwen3" else 0)
    return (embed + c["num_hidden_layers"] * (layer_matmul_params(c) + norms)
            + d)


def forward_flops(c: dict, attended: int) -> int:
    """One token's forward pass when it attends to ``attended``
    positions (itself included): the projections and MLPs of every
    layer, the LM head, and attention's scores and weighted sum over
    the positions it really attends to, not the padded cache."""
    hd = _head_dim(c)
    per_layer = (2 * layer_matmul_params(c)
                 + 4 * c["num_attention_heads"] * hd * attended)
    head = 2 * c["vocab_size"] * c["hidden_size"]
    return c["num_hidden_layers"] * per_layer + head


def output_token_flops(c: dict, prompt_len: int, new_tokens: int) -> int:
    """The forward passes that yield one sequence's output tokens: the
    k-th (k = 0 … new_tokens - 1) comes from the token at position
    ``prompt_len - 1 + k``, which attends to ``prompt_len + k``
    positions.  The prefill's other prompt positions are not counted."""
    return sum(forward_flops(c, prompt_len + k) for k in range(new_tokens))
