"""A toy model family and its plain reference, for the tests that show an
architecture other than the dense one reaching the harness as new files
only. It runs the program's ``moe`` layer in every ``decoder_sparse_step``-th
layer, so that the parameter layout holds more than one position: with step
2, ``blocks/pos0`` is attention + SwiGLU and ``blocks/pos1`` attention + a
router over experts stacked on their own axis.

The tests install this module as the family ``bench.families.toy_moe`` and
its ``loss_fn`` as the reference ``bench.toy_moe``; the configuration adds
``num_experts``, ``num_experts_per_tok`` and ``decoder_sparse_step`` to a
dense one's keys.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from bench import reference as R
from bench.families import dense

# the program's loss: next-token cross entropy + 0.01 x the Switch
# load-balancing loss of every expert layer (models/model.py, moe.py)
AUX_WEIGHT = 0.01


def model_config(conf: dict):
    # a capacity factor of num_experts lets every expert take every token of
    # a row: the program's capacity dispatch drops nothing, as the reference
    return dataclasses.replace(
        dense.model_config(conf), family="moe", num_experts=conf["num_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        moe_period=conf["decoder_sparse_step"],
        moe_capacity_factor=float(conf["num_experts"]))


def init_params(conf: dict, key):
    """Each position of the period is a dense layer stack over the periods;
    the last one's MLP is the experts and their router."""
    period = conf["decoder_sparse_step"]
    d, f, e = conf["hidden_size"], conf["intermediate_size"], conf["num_experts"]
    stack = dict(conf, num_hidden_layers=conf["num_hidden_layers"] // period)
    n = stack["num_hidden_layers"]
    keys = jax.random.split(key, period + 1)
    params = dense.init_params(stack, keys[0])
    for j in range(1, period):
        params["blocks"][f"pos{j}"] = dense.init_params(stack, keys[j])["blocks"]["pos0"]
    kr, kg, ku, ko = jax.random.split(keys[period], 4)
    normal = lambda k, shape, fan_in: jax.random.normal(k, shape, jnp.float32) * fan_in**-0.5
    params["blocks"][f"pos{period - 1}"]["mlp"] = {
        "router": normal(kr, (n, d, e), d), "wi_gate": normal(kg, (n, e, d, f), d),
        "wi_up": normal(ku, (n, e, d, f), d), "wo": normal(ko, (n, e, f, d), f)}
    return params


def flops_per_step(conf: dict, batch: int, seq: int) -> float:
    """The dense count, plus each expert layer's further experts per token
    and its router."""
    d, f, e = conf["hidden_size"], conf["intermediate_size"], conf["num_experts"]
    moe_layers = conf["num_hidden_layers"] // conf["decoder_sparse_step"]
    extra = moe_layers * ((conf["num_experts_per_tok"] - 1) * 3 * d * f + d * e)
    return dense.flops_per_step(conf, batch, seq) + 6.0 * extra * batch * seq


def _experts(conf, mm, h, m, experts: bool):
    """Top-k softmax routing over every expert, gates renormalised over the
    k chosen; returns the experts' output and the load-balancing loss."""
    e, k = conf["num_experts"], conf["num_experts_per_tok"]
    probs = jax.nn.softmax(mm("bsd,de->bse")(h, m["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    top = top / jnp.clip(top.sum(-1, keepdims=True), 1e-9)
    gates = jnp.sum(jax.nn.one_hot(idx, e) * top[..., None], axis=-2)  # (b, s, e)
    gate = jax.nn.silu(mm("bsd,edf->bsef")(h, m["wi_gate"]))
    up = mm("bsd,edf->bsef")(h, m["wi_up"])
    out = mm("bse,bsed->bsd")(gates, mm("bsef,efd->bsed")(gate * up, m["wo"]))
    frac_tokens = jnp.mean(jax.nn.one_hot(jnp.argmax(probs, axis=-1), e), axis=(0, 1))
    aux = e * jnp.sum(frac_tokens * jnp.mean(probs, axis=(0, 1)))
    return (out if experts else jnp.zeros_like(out)), aux


def loss_fn(conf, params, tokens, matmul="f32", fault=None, experts=True):
    """The program's loss of ``tokens`` (b, s). ``experts=False`` leaves the
    experts' output out: a reference the program must fail."""
    mm = R.mm_for(matmul)
    period, eps = conf["decoder_sparse_step"], conf["rms_norm_eps"]

    def body(carry, pp):
        x, aux = carry
        for j in range(period):
            p = pp[f"pos{j}"]
            x = R.attention_sublayer(conf, mm, x, p)
            h = R.rms(x, p["ln2"]["scale"], eps)
            if j == period - 1:
                y, a = _experts(conf, mm, h, p["mlp"], experts)
                aux = aux + a
            else:
                y = R.swiglu(mm, h, p["mlp"])
            x = x + y
        return (x, aux), None

    x = params["embed"]["tok"][tokens]
    (x, aux), _ = jax.lax.scan(jax.checkpoint(body), (x, jnp.zeros((), jnp.float32)),
                               params["blocks"])
    return R.head_loss(conf, mm, params, x, tokens, fault) + AUX_WEIGHT * aux
