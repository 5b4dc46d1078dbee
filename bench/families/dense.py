"""Dense decoders (Qwen2 / Qwen3): grouped-query attention with optional
q/k/v bias and q/k RMSNorm, a SwiGLU MLP in every layer and a tied or untied
head. Every layer sits in ``blocks/pos0``, stacked on a leading axis.

``flops_per_step``: the forward and backward passes' matrix products (6 per
parameter that multiplies a token: every layer weight matrix and the head,
tied or not) plus causal attention's two products (3 x 4 x heads x head_dim
per query-key pair a causal mask keeps). Rematerialised forward passes and
elementwise work do not count: this is the numerator of MFU.
"""

from __future__ import annotations

import math

from bench.flops import causal_pairs
from bench.model import BenchError

# the program's RMSNorm epsilon (models/layers.py); a configuration that
# states another one cannot be run as stated
PROGRAM_RMS_EPS = 1e-6


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    if conf["hidden_act"] != "silu":
        raise BenchError(f"{conf['name']}: hidden_act {conf['hidden_act']!r} is not run")
    if not math.isclose(conf["rms_norm_eps"], PROGRAM_RMS_EPS):
        raise BenchError(f"{conf['name']}: rms_norm_eps {conf['rms_norm_eps']} "
                         f"differs from the program's {PROGRAM_RMS_EPS}")
    return ModelConfig(
        name=conf["name"],
        family="dense",
        num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        head_dim=conf["head_dim"],
        act="silu",
        qk_norm=conf["qk_norm"],
        qkv_bias=conf["attention_bias"],
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["assumed"]["compute_dtype"],
        param_dtype=conf["assumed"]["param_dtype"],
        source=conf["source"],
    )


def init_params(conf: dict, key):
    """float32 weights from a JAX key, in the program's parameter layout:
    ``blocks/pos0`` holds every layer stacked on a leading axis."""
    import jax
    import jax.numpy as jnp

    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    h, kh, hd = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    f, V = conf["intermediate_size"], conf["vocab_size"]
    ks = iter(jax.random.split(key, 12))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def matrix(fan_in, fan_out):
        return normal((L, fan_in, fan_out), fan_in**-0.5)

    mixer = {"wq": matrix(d, h * hd), "wk": matrix(d, kh * hd),
             "wv": matrix(d, kh * hd), "wo": matrix(h * hd, d)}
    if conf["attention_bias"]:
        mixer.update(bq=normal((L, h * hd), 0.02), bk=normal((L, kh * hd), 0.02),
                     bv=normal((L, kh * hd), 0.02))
    if conf["qk_norm"]:
        mixer.update(q_norm=jnp.ones((L, hd)), k_norm=jnp.ones((L, hd)))
    block = {
        "ln1": {"scale": jnp.ones((L, d))},
        "mixer": mixer,
        "ln2": {"scale": jnp.ones((L, d))},
        "mlp": {"wi_gate": matrix(d, f), "wi_up": matrix(d, f), "wo": matrix(f, d)},
    }
    params = {
        "embed": {"tok": normal((V, d), 0.02)},
        "blocks": {"pos0": block},
        "final_norm": {"scale": jnp.ones((d,))},
    }
    if not conf["tie_word_embeddings"]:
        params["lm_head"] = {"w": normal((d, V), d**-0.5)}
    return params


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


def flops_per_step(conf: dict, batch: int, seq: int) -> float:
    tokens = batch * seq
    h, hd, L = conf["num_attention_heads"], conf["head_dim"], conf["num_hidden_layers"]
    attention = 3 * 4 * h * hd * L * batch * causal_pairs(seq)
    return 6.0 * matmul_params(conf) * tokens + attention
