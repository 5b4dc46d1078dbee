"""A run with its timed path broken underneath reports ``correct`` false:
a step that returns its state unchanged, a step that applies one leaf's
update twice, half of the batch left out, and, across chips, the exchange
of a resize left out. Tiny widths on the CPU;
the look for a chip is skipped."""

import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
from bench import harness  # noqa: E402
from bench.tests._tiny import tiny_tree  # noqa: E402


def _unchanged_state(monkeypatch):
    from repro.distribution import step

    make = step.make_train_step

    def make_frozen(*a, **k):
        real = make(*a, **k)

        def frozen(params, opt_state, batch):
            _, _, metrics = real(params, opt_state, batch)
            return params, opt_state, metrics

        return frozen

    monkeypatch.setattr(step, "make_train_step", make_frozen)


def _doubled_update(monkeypatch):
    """One leaf's update applied twice where the step produces it."""
    import jax

    from repro.distribution import step

    make = step.make_train_step

    def make_doubled(*a, **k):
        real = make(*a, **k)

        def doubled(params, opt_state, batch):
            old = jax.tree_util.tree_map(lambda x: x.copy(), params["blocks"]["pos0"]["mlp"]["wo"])
            new, opt_state, metrics = real(params, opt_state, batch)
            mlp = new["blocks"]["pos0"]["mlp"]
            mlp["wo"] = 2 * mlp["wo"] - old
            return new, opt_state, metrics

        return doubled

    monkeypatch.setattr(step, "make_train_step", make_doubled)


def _half_batch(monkeypatch):
    from repro.models import model

    loss_fn = model.loss_fn

    def half(cfg, params, batch, **k):
        tokens = batch["tokens"]
        return loss_fn(cfg, params, {"tokens": tokens[: tokens.shape[0] // 2]}, **k)

    monkeypatch.setattr(model, "loss_fn", half)


FAULTS = {"unchanged_state": _unchanged_state, "doubled_update": _doubled_update,
          "half_batch": _half_batch}


@pytest.mark.parametrize("fault", ["none", *FAULTS])
def test_steady_fault_is_caught(fault, tmp_path, monkeypatch):
    if fault in FAULTS:
        FAULTS[fault](monkeypatch)
    root = tiny_tree(tmp_path, {"tiny.steady": ("qwen3-1.7b.steady-4k", {})})
    res = harness.run_cell("tiny.steady", 1234567890123, 0.5, False, time.perf_counter(),
                           root=root, require_tpu=False)
    assert res["correct"] is (fault == "none"), res["checks"]


RESIZE = textwrap.dedent("""
    import sys, time
    sys.path[:0] = [{repo!r}, {src!r}]
    from pathlib import Path
    from bench import harness
    from bench.calibrate import cross_chip_moves_dropped

    root = Path({root!r})
    sound = harness.run_cell("tiny.resize", 99, 3.0, False, time.perf_counter(),
                             root=root, require_tpu=False)
    with cross_chip_moves_dropped():
        broken = harness.run_cell("tiny.resize", 99, 3.0, False, time.perf_counter(),
                                  root=root, require_tpu=False)
    print("RESULT", sound["correct"], broken["correct"])
""")


def test_resize_without_the_exchange_is_caught(tmp_path):
    root = tiny_tree(tmp_path, {"tiny.resize": ("qwen3-1.7b.resize-tp2-tp4", {})})
    code = RESIZE.format(repo=str(REPO), src=str(REPO / "src"), root=str(root))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT")][-1]
    assert line.split()[1:] == ["True", "False"], p.stderr[-3000:]
