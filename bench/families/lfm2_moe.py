"""LFM2-MoE (``model_type`` ``lfm2_moe``): gated short-convolution and
grouped-query attention layers as ``layer_types`` lists them, a SwiGLU MLP in
the first ``num_dense_layers`` layers and sparse SwiGLU experts in the rest,
routed by sigmoid scores with a selection bias, and a tied head.

The configuration holds ``num_experts`` of the router's
``published["num_experts"]`` experts, from ``first_expert`` on: one rank's
share of an expert-parallel deployment. The router keeps its published width.

``flops_per_step``: the forward and backward passes' matrix products (6 per
parameter that multiplies a token) plus causal attention's two products
(3 x 4 x heads x head_dim per query-key pair a causal mask keeps), where an
expert layer's experts multiply a token with the expected routing: each
token's k choices fall on held experts at k x held / published of them, one
expert's weights a token at this cell's share. The router, the projections
of every layer, the dense MLPs and the tied head count in full; the short
convolution's taps, like elementwise work, do not. Rematerialised forward
passes do not count: this is the numerator of MFU.
"""

from __future__ import annotations

from bench.flops import causal_pairs
from bench.model import BenchError

MIXERS = {"conv": "conv", "full_attention": "attn"}


def _check(conf: dict) -> None:
    wanted = {"model_type": "lfm2_moe", "conv_bias": False, "use_expert_bias": True,
              "norm_topk_prob": True}
    for key, value in wanted.items():
        if conf[key] != value:
            raise BenchError(f"{conf['name']}: {key} {conf[key]!r} is not run (only {value!r})")
    if conf["head_dim"] * conf["num_attention_heads"] != conf["hidden_size"]:
        raise BenchError(f"{conf['name']}: head_dim is hidden_size / num_attention_heads in LFM2")


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    _check(conf)
    layers = conf["num_hidden_layers"]
    return ModelConfig(
        name=conf["name"],
        family="moe",
        num_layers=layers,
        d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        head_dim=head_dim(conf),
        act="silu",
        qk_norm=True,
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=True,
        norm_eps=float(conf["norm_eps"]),
        layer_types=tuple(MIXERS[t] for t in conf["layer_types"][:layers]),
        conv_kernel=conf["conv_L_cache"],
        num_experts=conf["published"]["num_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        dense_layers=conf["num_dense_layers"],
        expert_d_ff=conf["moe_intermediate_size"],
        router="sigmoid_bias",
        routed_scale=float(conf["routed_scaling_factor"]),
        experts_held=conf["num_experts"],
        first_expert=conf["first_expert"],
        dtype=conf["assumed"]["compute_dtype"],
        param_dtype=conf["assumed"]["param_dtype"],
        source=conf["source"],
    )


def head_dim(conf: dict) -> int:
    return conf["head_dim"]


def layer_kinds(conf: dict) -> list[tuple[str, str]]:
    """(mixer, mlp) of every layer held: mixer "conv" or "attn", mlp "dense"
    or "moe"."""
    return [(MIXERS[t], "dense" if i < conf["num_dense_layers"] else "moe")
            for i, t in enumerate(conf["layer_types"][: conf["num_hidden_layers"]])]


def _period(kinds: list) -> int:
    n = len(kinds)
    return next(p for p in range(1, n + 1) if n % p == 0 and kinds == kinds[:p] * (n // p))


def stacks(conf: dict) -> list[tuple[str, list[tuple[str, str]], int]]:
    """The program's parameter layout: [(prefix, kinds, repeats)]. The
    leading dense layers are one stack (``lead``), every later layer another
    (``pos``), each as the least period that repeats to its end; position j
    of a stack sits at ``blocks/<prefix><j>``, stacked over the repeats."""
    kinds = layer_kinds(conf)
    out = []
    for prefix, part in (("lead", kinds[: conf["num_dense_layers"]]),
                         ("pos", kinds[conf["num_dense_layers"]:])):
        if part:
            p = _period(part)
            out.append((prefix, part[:p], len(part) // p))
    return out


def layer_slots(conf: dict) -> list[tuple[str, int]]:
    """(``blocks`` key, index on its stacked axis) of every layer, in order."""
    return [(f"{prefix}{j}", r) for prefix, kinds, repeats in stacks(conf)
            for r in range(repeats) for j in range(len(kinds))]


def init_params(conf: dict, key):
    """float32 weights from a JAX key, in the program's parameter layout
    (:func:`stacks`)."""
    import jax
    import jax.numpy as jnp

    d, V = conf["hidden_size"], conf["vocab_size"]
    h, kh, hd = conf["num_attention_heads"], conf["num_key_value_heads"], head_dim(conf)
    f, fe = conf["intermediate_size"], conf["moe_intermediate_size"]
    e, router_width = conf["num_experts"], conf["published"]["num_experts"]
    taps = conf["conv_L_cache"]
    embed_key, *block_keys = jax.random.split(key, conf["num_hidden_layers"] + 1)
    block_keys = iter(block_keys)

    def block(n: int, mixer: str, mlp: str) -> dict:
        ks = iter(jax.random.split(next(block_keys), 10))

        def normal(shape, std):
            return jax.random.normal(next(ks), (n, *shape), jnp.float32) * std

        def matrix(*shape):  # the fan-in is the second-last dimension
            return normal(shape, shape[-2] ** -0.5)

        if mixer == "attn":
            mix = {"wq": matrix(d, h * hd), "wk": matrix(d, kh * hd), "wv": matrix(d, kh * hd),
                   "wo": matrix(h * hd, d), "q_norm": jnp.ones((n, hd)),
                   "k_norm": jnp.ones((n, hd))}
        else:
            mix = {"wB": matrix(d, d), "wC": matrix(d, d), "wx": matrix(d, d),
                   "conv_w": normal((taps, d), taps**-0.5), "wo": matrix(d, d)}
        if mlp == "dense":
            ffn = {"wi_gate": matrix(d, f), "wi_up": matrix(d, f), "wo": matrix(f, d)}
        else:
            ffn = {"router": matrix(d, router_width), "expert_bias": normal((router_width,), 0.01),
                   "wi_gate": matrix(e, d, fe), "wi_up": matrix(e, d, fe),
                   "wo": matrix(e, fe, d)}
        return {"ln1": {"scale": jnp.ones((n, d))}, "mixer": mix,
                "ln2": {"scale": jnp.ones((n, d))}, "mlp": ffn}

    blocks = {f"{prefix}{j}": block(repeats, mixer, mlp)
              for prefix, kinds, repeats in stacks(conf) for j, (mixer, mlp) in enumerate(kinds)}
    return {
        "embed": {"tok": jax.random.normal(embed_key, (V, d), jnp.float32) * 0.02},
        "blocks": blocks,
        "final_norm": {"scale": jnp.ones((d,))},
    }


def matmul_params(conf: dict) -> float:
    """Parameters that multiply a token, experts at the expected routing."""
    d, f, fe = conf["hidden_size"], conf["intermediate_size"], conf["moe_intermediate_size"]
    h, kh, hd = conf["num_attention_heads"], conf["num_key_value_heads"], head_dim(conf)
    experts_a_token = (conf["num_experts_per_tok"] * conf["num_experts"]
                       / conf["published"]["num_experts"])
    total = d * conf["vocab_size"]
    for mixer, mlp in layer_kinds(conf):
        total += 2 * d * (h + kh) * hd if mixer == "attn" else 4 * d * d
        if mlp == "dense":
            total += 3 * d * f
        else:
            total += d * conf["published"]["num_experts"] + experts_a_token * 3 * d * fe
    return total


def flops_per_step(conf: dict, batch: int, seq: int) -> float:
    h, hd = conf["num_attention_heads"], head_dim(conf)
    attn_layers = sum(mixer == "attn" for mixer, _ in layer_kinds(conf))
    attention = 3 * 4 * h * hd * attn_layers * batch * causal_pairs(seq)
    return 6.0 * matmul_params(conf) * batch * seq + attention
