"""Mixture-of-Experts MLP with dropless, sort-based dispatch.

The router scores every expert of the model (``num_experts``); the layer
computes the part of the result that its own experts give: the
``held_experts`` from ``first_expert`` on, as one rank of an expert-parallel
deployment holds them. Each (token, choice) pair routed to a held expert is
computed, however many go to one expert: the pairs are sorted by expert and
one grouped matrix product (``jax.lax.ragged_dot``) runs each expert over
its own rows. Pairs routed to experts held elsewhere sort last, fall outside
every group and add nothing here: their rows are zeros in every product's
result and input gradient (:func:`_grouped`).

Expert weights are stacked on a leading ``expert`` logical axis so they are
(a) shardable over a mesh axis (EP) and (b) first-class tensors in the
Abstract Resource View — EP reshaping (App. A.2.3 of the paper) migrates
slices of these tensors like any other.

Scopes (``jax.named_scope``): ``moe/route``, ``moe/dispatch``,
``moe/experts`` and ``moe/combine``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import _dense_init, _act, mlp_init, mlp_apply


def moe_init(rng, cfg: ModelConfig, dtype) -> tuple[dict, dict]:
    e, d, f = cfg.held_experts, cfg.d_model, cfg.resolved_expert_d_ff
    keys = jax.random.split(rng, 5)
    params = {
        "router": _dense_init(keys[0], (d, cfg.num_experts), dtype),
        "wi_gate": _dense_init(keys[1], (e, d, f), dtype, in_axis=1),
        "wi_up": _dense_init(keys[2], (e, d, f), dtype, in_axis=1),
        "wo": _dense_init(keys[3], (e, f, d), dtype, in_axis=1),
    }
    axes = {
        "router": ("embed", "expert_in"),
        "wi_gate": ("expert", "embed", "ffn"),
        "wi_up": ("expert", "embed", "ffn"),
        "wo": ("expert", "ffn", "embed"),
    }
    if cfg.router == "sigmoid_bias":
        params["expert_bias"] = jnp.zeros((cfg.num_experts,), dtype)
        axes["expert_bias"] = ("expert_in",)
    if cfg.moe_shared_expert:
        sp, sa = mlp_init(keys[4], cfg, dtype)
        params["shared"] = sp
        axes["shared"] = sa
    return params, axes


def _route(params: dict, cfg: ModelConfig, logits: jax.Array):
    """logits (..., E) f32 -> (weights (..., k), experts (..., k), aux loss)."""
    k = cfg.experts_per_token
    if cfg.router == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        bias = jax.lax.stop_gradient(params["expert_bias"].astype(jnp.float32))
        _, experts = jax.lax.top_k(scores + bias, k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-6)
        aux = jnp.zeros((), jnp.float32)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, k)
        weights = weights / jnp.clip(weights.sum(-1, keepdims=True), 1e-9)
        aux = _switch_loss(probs)
    return weights * cfg.routed_scale, experts, aux


def _switch_loss(probs: jax.Array) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style) of router probs (b, s, e)."""
    e = probs.shape[-1]
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(jax.nn.one_hot(top1, e, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    return e * jnp.sum(frac_tokens * frac_probs)


# The permutations are gathers both ways: the transpose of a gather is a
# scatter-add, and the inverse permutation gathers the same rows back.


@jax.custom_vjp
def _dispatch_rows(x, order, inv):
    """Row r of the result is token ``order[r] // k``'s row of x (t, d)."""
    k = order.shape[0] // x.shape[0]
    return x[order // k]


def _dispatch_fwd(x, order, inv):
    return _dispatch_rows(x, order, inv), (order, inv, x.shape[0])


def _dispatch_bwd(res, g):
    order, inv, t = res
    return g[inv].reshape(t, -1, g.shape[-1]).sum(1), None, None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine_rows(y, order, inv):
    """Pair p's row of the expert outputs y, which are in expert order."""
    return y[inv]


def _combine_fwd(y, order, inv):
    return y[inv], (order, inv)


def _combine_bwd(res, g):
    order, inv = res
    return g[order], None, None


_combine_rows.defvjp(_combine_fwd, _combine_bwd)


def _grouped(x, w, group_sizes, grouped_rows):
    """``jax.lax.ragged_dot(x, w, group_sizes)`` with the rows outside every
    group zero, in the result and in x's gradient. XLA's TPU kernel leaves
    those rows unwritten (the CPU's are zeros); masking the input as well
    keeps what they hold out of the backward's products."""
    def zero(a):
        return jnp.where(grouped_rows, a, 0)

    return zero(jax.lax.ragged_dot(zero(x), w, group_sizes))


def moe_apply(
    params: dict,
    cfg: ModelConfig,
    x: jax.Array,  # (b, s, d)
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (y, aux_loss, local_rows): the held experts' part of the
    layer's output, the router's auxiliary loss and the number of (token,
    choice) pairs routed to held experts."""
    b, s, d = x.shape
    k, held = cfg.experts_per_token, cfg.held_experts
    t = b * s
    with jax.named_scope("moe"):
        xt = x.reshape(t, d)
        with jax.named_scope("route"):
            logits = jnp.dot(xt, params["router"].astype(x.dtype),
                             preferred_element_type=jnp.float32)
            weights, experts, aux = _route(params, cfg, logits.reshape(b, s, -1))
        with jax.named_scope("dispatch"):
            expert = experts.reshape(t * k) - cfg.first_expert
            local = (expert >= 0) & (expert < held)
            # pairs of experts held elsewhere sort after every held group
            key = jnp.where(local, expert, held)
            order = jnp.argsort(key, stable=True)
            inv = jnp.zeros_like(order).at[order].set(
                jnp.arange(t * k, dtype=order.dtype))
            group_sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
            xs = _dispatch_rows(xt, order, inv)
        with jax.named_scope("experts"):
            grouped = (jnp.arange(t * k) < group_sizes.sum())[:, None]
            gate, up = (_grouped(xs, params[w].astype(x.dtype), group_sizes, grouped)
                        for w in ("wi_gate", "wi_up"))
            ys = _grouped(_act(cfg.act, gate) * up, params["wo"].astype(x.dtype),
                          group_sizes, grouped)
        with jax.named_scope("combine"):
            # the rows of pairs not held are zeros: they add nothing
            y = jnp.einsum("tk,tkd->td", weights.reshape(t, k).astype(x.dtype),
                           _combine_rows(ys, order, inv).reshape(t, k, d),
                           preferred_element_type=jnp.float32)
            y = y.astype(x.dtype).reshape(b, s, d)
    if cfg.moe_shared_expert:
        y = y + mlp_apply(params["shared"], x, cfg.act)
    return y, aux, group_sizes.sum()
