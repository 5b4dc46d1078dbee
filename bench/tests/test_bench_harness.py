"""The harness finds cells, configurations, drivers and metric readers by
name, refuses what it cannot run, and prints the result line the contract
asks for."""

import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
from bench import harness, model  # noqa: E402
from bench.tests._tiny import tiny_tree  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_a_dropped_workload_file_is_found_by_name(tmp_path):
    root = tiny_tree(tmp_path, {"tiny.new-cell": ("qwen3-1.7b.steady-4k", {})})
    wl = model.load("workloads", "tiny.new-cell", root)
    assert wl["config"] == "tiny-qwen3-1.7b"
    assert model.load("configs", wl["config"], root)["hidden_size"] == 64
    with pytest.raises(model.BenchError, match="no workloads entry"):
        model.load("workloads", "tiny.absent", root)


def test_an_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(model.BenchError, match="no peaks"):
        harness.peaks_for("cpu")


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.steady-4k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_json_matches_the_files():
    bench = harness.load_benchmark()
    for c in bench["configs"]:
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert c["reduced"] == conf["reduced"]
        assert all(conf[k] != conf["published"][k] for k in c["reduced"])
    for w in bench["workloads"]:
        wl = model.load("workloads", w["name"])
        assert wl["config"] == w["config"] and wl["chips"] == w["chips"]
        assert (REPO / "bench" / "drivers" / f"{wl['driver']}.py").is_file()
        assert len(w["why"]) <= 200
    for m in bench["per_layer"]:
        reader = __import__(f"bench.metrics.{m['name']}", fromlist=["read"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == (m["layer"], m["unit"], m["moves"])
    names = [x["name"] for s in ("configs", "workloads", "end_to_end", "per_layer") for x in bench[s]]
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))


def test_result_line_has_the_contract_keys(tmp_path):
    root = tiny_tree(tmp_path, {"tiny.steady": ("qwen3-1.7b.steady-4k", {})})
    res = harness.run_cell("tiny.steady", 2**33 + 7, 0.5, False, time.perf_counter(),
                           root=root, require_tpu=False)
    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0 and res["attempted"] > 0
    # tiny.steady is not a cell of BENCHMARK.json: only the metrics every cell reports
    assert set(res["metrics"]) == {"train_tokens_per_s", "step_ms_p95", "setup_s"}
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(res["checks"]) == {"loss_gap", "grad_norm_gap", "update_norm_gap"}
    json.dumps(res)
