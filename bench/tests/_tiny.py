"""A tiny copy of the benchmark's files for CPU tests: the same keys as the
cells' configurations and workloads at widths a test can hold."""

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=512, num_hidden_layers=2)
# at these widths sound runs read up to ~0.003 and the fp8 control ~0.03 on
# the gradient norms (test_bench_calibrate): the cells' limits are set for
# their own widths
TINY_LIMITS = {"loss_gap": 0.01, "grad_norm_gap": 0.02, "update_norm_gap": 0.02}


def tiny_conf(base: str = "qwen3-1.7b", **over) -> dict:
    conf = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    conf.update(TINY, name=f"tiny-{base}")
    conf.update(over)
    return conf


def tiny_tree(tmp: Path, cells: dict[str, tuple[str, dict]],
              confs: dict[str, dict] | None = None) -> Path:
    """``tmp/BENCHMARK.json`` (the real one) and ``tmp/bench/{configs,
    workloads}`` holding ``cells``: name -> (workload file to copy, overrides),
    beside the tiny configurations and ``confs``: name -> configuration."""
    root = tmp / "bench"
    for d in ("configs", "workloads"):
        (root / d).mkdir(parents=True, exist_ok=True)
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for base in ("qwen3-1.7b", "qwen2.5-1.5b"):
        (root / "configs" / f"tiny-{base}.json").write_text(json.dumps(tiny_conf(base)))
    for name, conf in (confs or {}).items():
        (root / "configs" / f"{name}.json").write_text(json.dumps(conf))
    for name, (src, over) in cells.items():
        wl = json.loads((BENCH / "workloads" / f"{src}.json").read_text())
        wl.update(config=f"tiny-{wl['config']}", global_batch=2, seq_len=32,
                  limits=TINY_LIMITS)
        wl.update(over)
        (root / "workloads" / f"{name}.json").write_text(json.dumps(wl))
    return root
