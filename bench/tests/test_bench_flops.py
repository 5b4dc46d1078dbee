"""FLOP and parameter counts of the cells (the dense family's) against the
program's own count."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import flops, model  # noqa: E402
from bench.families import dense  # noqa: E402
from bench.tests._tiny import tiny_conf  # noqa: E402


@pytest.mark.parametrize("name,params", [("qwen3-1.7b", 713_854_976), ("qwen2.5-1.5b", 607_757_824)])
def test_param_count_matches_the_program(name, params):
    from repro.models.model import analytic_param_count

    conf = model.load("configs", name)
    assert dense.param_count(conf) == params
    assert analytic_param_count(dense.model_config(conf)) == params


@pytest.mark.parametrize("variant", [dict(tie_word_embeddings=False), dict(attention_bias=True)])
def test_param_count_variants_match_the_program(variant):
    from repro.models.model import analytic_param_count

    conf = tiny_conf(**variant)
    assert dense.param_count(conf) == analytic_param_count(dense.model_config(conf))


def test_model_flops_of_the_cells():
    q3 = model.load("configs", "qwen3-1.7b")
    total = dense.flops_per_step(q3, 1, 4096)
    attention = 3 * 4 * 16 * 128 * 8 * 4096 * 4097 // 2
    assert total == 6 * 713_818_112 * 4096 + attention
    assert total == pytest.approx(19.19e12, rel=1e-3)
    q25 = model.load("configs", "qwen2.5-1.5b")
    assert dense.flops_per_step(q25, 4, 1024) == pytest.approx(15.25e12, rel=1e-3)


def test_flash_forward_counts():
    f, b = flops.flash_forward(1, 16, 8, 4096, 128)
    assert f == 4 * 16 * 128 * (4096 * 4097 // 2)
    assert b == 2 * 4096 * 128 * (2 * 16 + 2 * 8)
