"""Operations and bytes a cell's work needs, computed from its shapes.

``model_flops_per_step``: the forward and backward passes' matrix products
(6 per parameter that multiplies a token: every layer weight matrix and the
head, tied or not) plus causal attention's two products (3 x 4 x heads x
head_dim per query-key pair a causal mask keeps). Rematerialised forward
passes and elementwise work do not count: this is the numerator of MFU.

``flash_forward``: one call of the flash-attention forward kernel.
"""

from __future__ import annotations


def param_count(conf: dict) -> int:
    """Every parameter of the configuration as run (tied head counted once)."""
    d, f, V, L = (conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"],
                  conf["num_hidden_layers"])
    h, kh, hd = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    per_layer = d * h * hd * 2 + d * kh * hd * 2 + 3 * d * f + 2 * d
    if conf["attention_bias"]:
        per_layer += h * hd + 2 * kh * hd
    if conf["qk_norm"]:
        per_layer += 2 * hd
    head = 0 if conf["tie_word_embeddings"] else d * V
    return L * per_layer + V * d + head + d


def matmul_params(conf: dict) -> int:
    """Parameters that multiply every token: layer weight matrices and the head."""
    d, f, V, L = (conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"],
                  conf["num_hidden_layers"])
    h, kh, hd = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    return L * (d * h * hd * 2 + d * kh * hd * 2 + 3 * d * f) + d * V


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def model_flops_per_step(conf: dict, batch: int, seq: int) -> float:
    tokens = batch * seq
    h, hd, L = conf["num_attention_heads"], conf["head_dim"], conf["num_hidden_layers"]
    attention = 3 * 4 * h * hd * L * batch * causal_pairs(seq)
    return 6.0 * matmul_params(conf) * tokens + attention


def flash_forward(batch: int, heads: int, kv_heads: int, seq: int, head_dim: int,
                  itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one causal flash forward: q.k and p.v over the
    kept pairs; q, k, v read once and the output written once."""
    flops = 4.0 * batch * heads * head_dim * causal_pairs(seq)
    nbytes = itemsize * batch * seq * head_dim * (2 * heads + 2 * kv_heads)
    return flops, float(nbytes)
