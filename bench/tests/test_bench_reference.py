"""The plain reference against the program's model on the dense family's
seeded weights (CPU, tiny widths, float32 in both), for each attention and
head variant."""

import functools
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import model, reference  # noqa: E402
from bench.families import dense  # noqa: E402
from bench.tests._tiny import tiny_conf  # noqa: E402

VARIANTS = {
    "qk_norm-tied": dict(qk_norm=True, attention_bias=False, tie_word_embeddings=True),
    "qkv_bias-tied": dict(qk_norm=False, attention_bias=True, tie_word_embeddings=True),
    "qk_norm-untied": dict(qk_norm=True, attention_bias=False, tie_word_embeddings=False),
    "qkv_bias-untied": dict(qk_norm=False, attention_bias=True, tie_word_embeddings=False),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_reference_matches_program_loss_and_grads(variant):
    from repro.models.model import loss_fn

    conf = tiny_conf(**VARIANTS[variant])
    conf["assumed"] = dict(conf["assumed"], compute_dtype="float32")
    cfg = dense.model_config(conf)
    params = jax.jit(functools.partial(dense.init_params, conf))(model.params_key(7))
    tokens = jnp.asarray(model.SeededTokens(7, conf["vocab_size"], 2, 32).global_batch_at(0))

    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, {"tokens": tokens})[0])(params)
        got, g_got = jax.value_and_grad(
            lambda p: reference.loss_fn(conf, p, tokens))(params)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    for path, a in jax.tree_util.tree_flatten_with_path(g_got)[0]:
        b = g_want
        for k in path:
            b = b[k.key]
        scale = float(jnp.linalg.norm(b)) + 1e-12
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * scale, (variant, path)


def test_fault_and_control_change_the_readings():
    conf = tiny_conf()
    opt = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1,
           "grad_clip": 1.0, "warmup_steps": 100, "total_steps": 10000, "min_lr_ratio": 0.1}
    feed = model.SeededTokens(3, conf["vocab_size"], 2, 32)
    batches = [feed.global_batch_at(i) for i in range(3)]
    ref = reference.train_readings(conf, opt, 3, batches)
    same = reference.compare(ref, reference.train_readings(conf, opt, 3, batches))
    assert max(same.values()) == 0.0
    half = reference.compare(reference.train_readings(conf, opt, 3, batches, fault="half_batch"), ref)
    fp8 = reference.compare(reference.train_readings(conf, opt, 3, batches, matmul="fp8"), ref)
    assert half["loss_gap"] > 1e-3 and half["grad_norm_gap"] > 1e-2
    assert fp8["grad_norm_gap"] > 1e-2
    assert np.all(np.isfinite(list(fp8.values())))
