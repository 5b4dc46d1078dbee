"""The control and the faults read above the sound program, at a size a
test can hold (CPU, tiny widths): the same table ``calibrate.py`` takes on
the chip at each cell's size to set its limits."""

import sys
import time
from pathlib import Path

import jax

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from bench import calibrate, harness, model  # noqa: E402
from bench.drivers import common  # noqa: E402
from bench.tests._tiny import tiny_tree  # noqa: E402


def test_control_and_half_batch_read_above_the_program(tmp_path):
    root = tiny_tree(tmp_path, {"tiny.steady": ("qwen3-1.7b.steady-4k", {})})
    wl = model.load("workloads", "tiny.steady", root)
    conf = model.load("configs", wl["config"], root)
    ctx = harness.RunContext(name="tiny.steady", workload=wl, conf=conf,
                             cfg=model.family(conf).model_config(conf), seed=1, seconds=0,
                             trace=False, devices=jax.devices(), chips=1, t0=time.perf_counter(),
                             counter=common.WindowCounters())
    rows, summary = calibrate.table(ctx, [1, 2, 3], [1, 2, 3])
    assert [r["kind"] for r in rows].count("program") == 3
    sound = summary["program"]
    for kind in ("control_fp8", "half_batch"):
        assert any(summary[kind][k] > 3 * sound[k] for k in sound), (kind, summary)
    assert all(sound[k] <= wl["limits"][k] for k in sound), summary
