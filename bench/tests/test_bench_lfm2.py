"""LFM2-MoE (``bench/families/lfm2_moe.py``, reference ``bench/lfm2_moe.py``)
against the program at tiny widths on the CPU, in float32: the loss, the
gradients and an AdamW step, the expert shares against the uncut layer, a
tiny cell through the harness, and the counts the cell's metrics use."""

import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import harness, lfm2_moe, model, moe_flops, reference  # noqa: E402
from bench.families import lfm2_moe as family  # noqa: E402
from bench.tests._tiny import TINY_LIMITS, tiny_tree  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
OPT = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
       "grad_clip": 1.0, "warmup_steps": 100, "total_steps": 10000, "min_lr_ratio": 0.1}


def tiny_lfm2(**over) -> dict:
    """The cell's configuration at tiny widths (8 experts, 4 held), float32."""
    conf = json.loads((BENCH / "configs" / "lfm2-8b-a1b.json").read_text())
    conf.update(name="tiny-lfm2", hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=256, num_experts=4,
                published=dict(conf["published"], num_experts=8))
    conf["assumed"] = dict(conf["assumed"], compute_dtype="float32")
    conf.update(over)
    return conf


def _tokens(conf, seed=5, batch=2, seq=32):
    return jnp.asarray(model.SeededTokens(seed, conf["vocab_size"], batch, seq).global_batch_at(0))


def test_reference_matches_program_loss_grads_and_a_step():
    from repro.distribution.step import make_train_step
    from repro.models.model import loss_fn
    from repro.optim import AdamWConfig, adamw_init

    conf = tiny_lfm2()
    cfg = family.model_config(conf)
    params = jax.jit(functools.partial(family.init_params, conf))(model.params_key(7))
    tokens = _tokens(conf)
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, {"tokens": tokens})[0])(params)
        got, g_got = jax.value_and_grad(lambda p: lfm2_moe.loss_fn(conf, p, tokens))(params)
        assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
        n_want, n_got = (model.flat_norms(model.leaf_norms(g)) for g in (g_want, g_got))
        med = float(np.median(list(n_got.values())))
        assert max(abs(n_got[k] - n_want[k]) / max(n_got[k], med) for k in n_got) < 1e-4

        # one AdamW step of the program against the trainer's
        step = make_train_step(cfg, AdamWConfig(**OPT))
        new, _, _ = jax.jit(step)(params, adamw_init(params), {"tokens": tokens})
        ref = reference.train_readings(conf, OPT, 7, [np.asarray(tokens)],
                                       device=jax.devices()[0])
    delta = model.flat_norms(model.leaf_norms(jax.tree_util.tree_map(jnp.subtract, new, params)))
    moving = [k for k, g in ref.grad_norms.items() if g >= 1e-3 * med]
    assert max(abs(delta[k] - ref.update_norms[k]) / ref.update_norms[k] for k in moving) < 1e-3


def test_the_expert_shares_add_up_to_the_uncut_layer():
    """Four worlds holding experts 0-1, 2-3, 4-5 and 6-7 of 8: the program's
    layer outputs add up to the reference's layer over all 8 experts."""
    from repro.models import moe

    conf = tiny_lfm2(num_experts=8)
    cfg = family.model_config(conf)
    params = jax.jit(functools.partial(family.init_params, conf))(model.params_key(3))
    m = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["pos1"]["mlp"])
    h = jax.random.normal(jax.random.key(4), (2, 32, conf["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        whole = lfm2_moe.experts(conf, reference.mm_for("f32"), h, m)
        parts = []
        for first in range(0, 8, 2):
            share = dict(m, **{k: m[k][first:first + 2] for k in ("wi_gate", "wi_up", "wo")})
            shard = dataclasses.replace(cfg, experts_held=2, first_expert=first)
            parts.append(moe.moe_apply(share, shard, h))
    np.testing.assert_allclose(sum(y for y, _, _ in parts), whole, rtol=1e-5, atol=1e-5)
    # every pair lands on exactly one share
    assert sum(int(rows) for _, _, rows in parts) == 2 * 32 * conf["num_experts_per_tok"]


def test_the_layout_is_the_programs():
    from repro.models.model import abstract_params

    for layers in (6, 24):  # the cell's stage, and the published depth
        conf = tiny_lfm2(num_hidden_layers=layers)
        params = jax.eval_shape(functools.partial(family.init_params, conf), jax.random.key(0))
        program = abstract_params(family.model_config(conf))
        assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(program)
        assert [a.shape for a in jax.tree_util.tree_leaves(params)] == \
            [a.shape for a in jax.tree_util.tree_leaves(program)]
    assert sorted(params["blocks"]) == sorted(["lead0"] + [f"pos{j}" for j in range(22)])


@pytest.mark.parametrize("router", ["published", "score_plus_bias"])
def test_a_tiny_cell_runs_and_a_wrong_router_is_caught(router, tmp_path, monkeypatch):
    """The cell through the harness on the CPU; against a reference that
    weights the chosen experts by score + bias (the likeliest slip in this
    router) it must read ``correct`` false."""
    import types

    conf = tiny_lfm2()
    if router != "published":
        ref = types.ModuleType("bench.lfm2_moe_wrong")
        ref.loss_fn = functools.partial(lfm2_moe.loss_fn, weigh_by=router)
        monkeypatch.setitem(sys.modules, "bench.lfm2_moe_wrong", ref)
        conf["reference"] = "lfm2_moe_wrong"
    root = tiny_tree(tmp_path, {"tiny.lfm2": ("lfm2-8b-a1b.steady-8k", {"config": conf["name"]})},
                     confs={conf["name"]: conf})
    res = harness.run_cell("tiny.lfm2", 2**33 + 13, 0.5, False, time.perf_counter(),
                           root=root, require_tpu=False)
    assert res["correct"] is (router == "published"), res["checks"]
    if router != "published":
        assert res["checks"]["grad_norm_gap"]["value"] > TINY_LIMITS["grad_norm_gap"]


def test_the_cells_counts():
    from repro.models.model import analytic_param_count

    conf = model.load("configs", "lfm2-8b-a1b")
    assert analytic_param_count(family.model_config(conf)) == 669_311_232
    per_token = family.matmul_params(conf)
    assert per_token == pytest.approx(360.96e6, rel=1e-4)
    attention = 3 * 4 * 32 * 64 * 1 * 8192 * 8193 // 2
    assert family.flops_per_step(conf, 1, 8192) == 6 * per_token * 8192 + attention
    assert family.flops_per_step(conf, 1, 8192) == pytest.approx(18.56e12, rel=1e-3)


def test_the_flash_forward_reader_finds_the_cells_attention_call():
    """``flash_fwd_roofline`` reads this cell too: its forward is the Pallas
    call of q's shape, (1, 32 heads, 8192, 64) in bfloat16."""
    from bench import flops
    from bench.metrics import flash_fwd_roofline

    asked = []

    class Trace:
        def op_time(self, device, *patterns):
            asked.append(patterns)
            return 0.5, 10

    conf = model.load("configs", "lfm2-8b-a1b")
    workload = json.loads((BENCH / "workloads" / "lfm2-8b-a1b.steady-8k.json").read_text())
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    ctx = type("Ctx", (), {"workload": workload, "conf": conf})()
    run = harness.Run(ctx=ctx, out=None, trace=Trace(), peaks=peaks)
    share = flash_fwd_roofline.read(run)
    assert asked == [(flash_fwd_roofline.PALLAS, "= bf16[1,32,8192,64]")]
    f, _ = flops.flash_forward(1, 32, 8, 8192, 64)
    assert share == pytest.approx(100 * 10 * f / peaks["bf16_flops_per_s"] / 0.5)


def test_expert_product_counts():
    # 1000 rows over 2 layer-steps of 8 experts, d 4, f 3, bf16
    flops, nbytes = moe_flops.expert_products(1000, 2, 4, 3, 8)
    assert flops == 4 * 6 * 1000 * 4 * 3
    assert nbytes == 4 * 2 * (3 * 1000 * 7 + 3 * 2 * 8 * 4 * 3)


@pytest.mark.parametrize("op_name, instruction, scope, want", [
    ("jit(wrapped)/jvp(liver/fwd)/while/body/closed_call/mlp/moe/experts/mul", "fusion.5",
     ("moe", "experts"), True),
    ("jit(wrapped)/jvp(liver/fwd)/while/body/closed_call/mlp/moe/experts/mul", "fusion.5",
     ("moe", "combine"), False),
    ("jit(wrapped)/transpose(jvp(liver/fwd))/while/body/mlp/moe/route/dot_general", "fusion.9",
     ("moe",), True),
    ("jit(wrapped)/jvp(liver/fwd)/while/body/short_conv/dot_general", "fusion.1",
     ("short_conv",), True),
    ("jit(wrapped)/jvp(liver/fwd)/while/body/attn/dot_general", "fusion.2", ("moe",), False),
    # XLA's grouped products carry no scope: they are the expert products
    ("ragged-dot-none", "ragged-dot-none.47", ("moe", "experts"), True),
    ("", "ragged-dot-metadata.3", ("moe",), True),
    ("ragged-dot-none", "ragged-dot-none.47", ("moe", "route"), False),
    ("ragged-dot-none", "ragged-dot-none.47", ("short_conv",), False),
])
def test_scope_membership(op_name, instruction, scope, want):
    from bench import scopes

    assert scopes.under(op_name, instruction, scope) is want
