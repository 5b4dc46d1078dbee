"""A configuration reaches the harness through the model family it names.
An architecture with another parameter layout than the dense one (a toy of
the program's sparse-expert layer, ``_toy_moe``) runs a cell end to end as
new files only, and a reference that leaves its experts out is caught. Tiny
widths on the CPU; the look for a chip is skipped."""

import functools
import sys
import time
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import harness, model  # noqa: E402
from bench.tests import _toy_moe  # noqa: E402
from bench.tests._tiny import tiny_conf, tiny_tree  # noqa: E402

TOY = tiny_conf(name="tiny-toy-moe", family="toy_moe", reference="toy_moe",
                num_hidden_layers=4, num_experts=4, num_experts_per_tok=2,
                decoder_sparse_step=2)
# float32 compute: in bfloat16 the router flips near-tied top-k choices
# against the float32 reference and its gradient moves past the tiny cells'
# limits, which were set for the dense family; this test is of the layout
TOY["assumed"] = dict(TOY["assumed"], compute_dtype="float32")


@pytest.mark.parametrize("experts", ["kept", "left_out"])
def test_a_new_family_runs_as_new_files(experts, tmp_path, monkeypatch):
    ref = types.ModuleType("bench.toy_moe")
    ref.loss_fn = functools.partial(_toy_moe.loss_fn, experts=experts == "kept")
    monkeypatch.setitem(sys.modules, "bench.families.toy_moe", _toy_moe)
    monkeypatch.setitem(sys.modules, "bench.toy_moe", ref)
    root = tiny_tree(tmp_path, {"tiny.toy-moe": ("qwen3-1.7b.steady-4k", {"config": TOY["name"]})},
                     confs={TOY["name"]: TOY})
    res = harness.run_cell("tiny.toy-moe", 2**33 + 11, 0.5, False, time.perf_counter(),
                           root=root, require_tpu=False)
    assert res["correct"] is (experts == "kept"), res["checks"]


def test_the_toy_layout_has_two_positions_and_stacked_experts():
    import jax

    from repro.models.model import abstract_params

    params = jax.eval_shape(functools.partial(_toy_moe.init_params, TOY), jax.random.key(0))
    program = abstract_params(_toy_moe.model_config(TOY))
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(program)
    assert [a.shape for a in jax.tree_util.tree_leaves(params)] == \
        [a.shape for a in jax.tree_util.tree_leaves(program)]
    assert sorted(params["blocks"]) == ["pos0", "pos1"]
    assert params["blocks"]["pos1"]["mlp"]["wi_gate"].shape == (2, 4, 64, 128)


@pytest.mark.parametrize("family,match", [(None, "the key 'family'"),
                                          ("no_such_family", "unknown family")])
def test_a_configuration_without_a_known_family_is_refused(family, match, tmp_path):
    conf = tiny_conf(name="tiny-bad")
    if family is None:
        del conf["family"]
    else:
        conf["family"] = family
    root = tiny_tree(tmp_path, {}, confs={"tiny-bad": conf})
    with pytest.raises(model.BenchError, match=match) as err:
        model.load("configs", "tiny-bad", root)
    assert "tiny-bad.json" in str(err.value)
    with pytest.raises(model.BenchError, match=match):
        model.family(conf)
