"""The layers LFM2-MoE brings to the program, at tiny widths in float32: the
gated short convolution, the dropless MoE dispatch with the sigmoid + bias
router, the per-layer pattern with leading dense layers, and the serving
paths that do not take a short-conv layer yet."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models import moe, short_conv
from repro.models.transformer import layer_program, segments

# LFM2-8B-A1B's published layer_types ("full_attention" -> "attn")
PUBLISHED = tuple(
    "attn" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24)
)


def _cfg(**over) -> ModelConfig:
    base = dict(
        name="tiny-lfm2", family="moe", num_layers=6, d_model=32, num_heads=4,
        num_kv_heads=2, d_ff=48, vocab_size=64, qk_norm=True, tie_embeddings=True,
        norm_eps=1e-5, layer_types=PUBLISHED[:6], num_experts=8, experts_per_token=2,
        dense_layers=2, expert_d_ff=16, router="sigmoid_bias", dtype="float32",
    )
    return ModelConfig(**{**base, **over})


def test_short_conv_is_causal():
    cfg = _cfg()
    params, _ = short_conv.short_conv_init(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model))
    y = short_conv.short_conv_apply(params, x)
    for t in (0, 5, 14):
        later = x.at[:, t + 1 :].set(jax.random.normal(jax.random.key(t), (2, 15 - t, cfg.d_model)))
        np.testing.assert_array_equal(short_conv.short_conv_apply(params, later)[:, : t + 1],
                                      y[:, : t + 1])
        # and the output at t does depend on the taps before it
        earlier = x.at[:, t].add(1.0)
        assert not np.allclose(short_conv.short_conv_apply(params, earlier)[:, t], y[:, t])


def _dense_moe(params, cfg, x):
    """Every token through each of its k chosen experts, one at a time."""
    logits = x @ params["router"]
    scores = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(scores + params["expert_bias"], cfg.experts_per_token)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-6)
    out = jnp.zeros_like(x)
    for j in range(cfg.experts_per_token):
        e = chosen[..., j]
        g = jax.nn.silu(jnp.einsum("bsd,bsdf->bsf", x, params["wi_gate"][e]))
        u = jnp.einsum("bsd,bsdf->bsf", x, params["wi_up"][e])
        out = out + w[..., j, None] * jnp.einsum("bsf,bsfd->bsd", g * u, params["wo"][e])
    return out


def test_dispatch_drops_nothing_under_a_skewed_router():
    """Expert 0's bias puts it in every token's top-k: 64 rows for it, where
    the old capacity (1.25 x k x s / e) held 10. Every pair is counted and
    computed."""
    cfg = _cfg()
    params, _ = moe.moe_init(jax.random.key(2), cfg, jnp.float32)
    params["expert_bias"] = params["expert_bias"].at[0].set(10.0)
    b, s = 2, 32
    x = jax.random.normal(jax.random.key(3), (b, s, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, aux, rows = moe.moe_apply(params, cfg, x)
        want = _dense_moe(params, cfg, x)
    assert int(rows) == b * s * cfg.experts_per_token
    assert float(aux) == 0.0
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)


def test_rows_outside_every_group_never_reach_the_result(monkeypatch):
    """XLA's TPU grouped product leaves the rows outside every group
    unwritten, in its result and in its input gradient. Filled with NaN
    here, they must change neither the layer's output nor a gradient."""
    real = jax.lax.ragged_dot

    def nan_tail(y, sizes):
        return jnp.where(jnp.arange(y.shape[0])[:, None] < sizes.sum(), y, jnp.nan)

    @jax.custom_vjp
    def unwritten(x, w, sizes):
        return nan_tail(real(x, w, sizes), sizes)

    def fwd(x, w, sizes):
        return unwritten(x, w, sizes), (x, w, sizes)

    def bwd(res, g):
        x, w, sizes = res
        dw = jax.vjp(lambda w: real(x, w, sizes), w)[1](g)[0]
        return nan_tail(real(g, jnp.swapaxes(w, 1, 2), sizes), sizes), dw, None

    unwritten.defvjp(fwd, bwd)
    cfg = _cfg(experts_held=4)  # about half the pairs fall outside every group
    params, _ = moe.moe_init(jax.random.key(2), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(3), (2, 16, cfg.d_model))
    r = jax.random.normal(jax.random.key(4), x.shape)

    def loss(p, x):
        return jnp.sum(moe.moe_apply(p, cfg, x)[0] * r)

    with jax.default_matmul_precision("highest"):
        want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
        monkeypatch.setattr(jax.lax, "ragged_dot", lambda x, w, sizes: unwritten(x, w, sizes))
        got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_the_selection_bias_has_no_gradient():
    cfg = _cfg()
    params, _ = moe.moe_init(jax.random.key(2), cfg, jnp.float32)
    params["expert_bias"] = jax.random.normal(jax.random.key(5), (cfg.num_experts,))
    x = jax.random.normal(jax.random.key(3), (2, 8, cfg.d_model))
    grads = jax.grad(lambda p: moe.moe_apply(p, cfg, x)[0].sum())(params)
    assert float(jnp.abs(grads["expert_bias"]).max()) == 0.0
    assert float(jnp.abs(grads["router"]).max()) > 0.0


def test_the_published_layer_pattern_builds():
    cfg = _cfg(num_layers=24, layer_types=PUBLISHED)
    kinds = layer_program(cfg)
    assert [m for m, _ in kinds] == list(PUBLISHED)
    assert [f for _, f in kinds] == ["dense"] * 2 + ["moe"] * 22
    # the leading dense layers are a stack of two; the rest does not repeat
    assert [(p, len(prog), n) for p, prog, n in segments(cfg)] == [("lead", 1, 2), ("pos", 22, 1)]
    params = M.init_params(cfg, jax.random.key(0))
    assert params["blocks"]["lead0"]["mlp"]["wi_gate"].shape == (2, 32, 48)
    assert params["blocks"]["pos0"]["mixer"]["wq"].shape == (1, 32, 32)
    assert params["blocks"]["pos1"]["mixer"]["conv_w"].shape == (1, 3, 32)
    assert params["blocks"]["pos19"]["mixer"]["wq"].shape == (1, 32, 32)  # layer 21
    assert params["blocks"]["pos21"]["mlp"]["wi_gate"].shape == (1, 8, 32, 16)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    loss, metrics = M.loss_fn(cfg, params, {"tokens": tokens})
    assert np.isfinite(float(loss))
    assert int(metrics["moe_local_rows"]) == 22 * 2 * 16 * cfg.experts_per_token


def test_held_experts_cut_the_expert_stack_not_the_router():
    cfg = _cfg(experts_held=2, first_expert=4)
    params = M.init_params(cfg, jax.random.key(0))
    mlp = params["blocks"]["pos1"]["mlp"]
    assert mlp["router"].shape == (1, 32, 8) and mlp["expert_bias"].shape == (1, 8)
    assert mlp["wi_gate"].shape == (1, 2, 32, 16)
    with pytest.raises(ValueError, match="not among"):
        _cfg(experts_held=2, first_expert=7)


@pytest.mark.parametrize("path", ["prefill", "decode"])
def test_serving_refuses_short_conv_layers(path):
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="short-conv"):
        if path == "prefill":
            M.prefill(cfg, params, {"tokens": tokens})
        else:
            M.decode_step(cfg, params, {}, tokens[:, :1], jnp.int32(0))
    with pytest.raises(NotImplementedError, match="leading dense"):
        M.init_cache(dataclasses.replace(cfg, layer_types=("attn",) * 6), 1, 8)


def test_a_resize_plan_numbers_the_leading_dense_layers_apart():
    """The plan streams one model layer at a time: the two leading dense
    layers (``blocks/lead0``) are layers 0 and 1 and the sparse stack's
    positions follow them, so no two stacks share a layer id."""
    from repro.configs.base import ParallelConfig
    from repro.core.reshard import plan_state_transfer

    cfg = _cfg(num_layers=24, layer_types=PUBLISHED)
    specs, plan = plan_state_transfer(cfg, ParallelConfig(tp=2), ParallelConfig(tp=4))
    by_stack = {}
    for t in plan.tasks:
        stack = t.tensor.split("/")[2] if "/blocks/" in t.tensor else None
        if stack is not None:
            by_stack.setdefault(stack, set()).add(t.layer)
    assert by_stack["lead0"] == {0, 1}
    assert by_stack["pos0"] == {2} and by_stack["pos19"] == {21} and by_stack["pos21"] == {23}
    assert plan.layers() == [-1] + list(range(24))  # -1: the embedding, final norm


def test_topology_search_keeps_a_leading_dense_stack_whole():
    """Pipeline stages do not split a leading dense stack: only pp 1 is
    offered, though the sparse stack's 8 repeats would divide by 2 and 4."""
    from repro.core.topology_search import feasible_configs

    pattern = ("conv", "conv") + ("attn", "conv") * 8
    cfg = _cfg(num_layers=18, layer_types=pattern)
    assert [(p, n) for p, _, n in segments(cfg)] == [("lead", 2), ("pos", 8)]
    assert {c.pp for c in feasible_configs(cfg, 4, global_batch=4)} == {1}
    assert {c.pp for c in feasible_configs(_cfg(num_layers=16, layer_types=pattern[2:],
                                                dense_layers=0), 4, 4)} == {1, 2, 4}
