"""What a cell is made of: its files, its seeded weights and its seeded batches.

Everything here is the benchmark's own. The weights and token batches are
made from ``--seed`` by this module, so the program under test and the plain
reference (``reference.py``) start from the same numbers without the
reference taking anything the program made.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
# the program's RMSNorm epsilon (models/layers.py); a configuration that
# states another one cannot be run as stated
PROGRAM_RMS_EPS = 1e-6


class BenchError(Exception):
    """A cell, configuration or device the benchmark cannot run."""


def load(kind: str, name: str, root: Path = BENCH) -> dict:
    """``<root>/<kind>/<name>.json``: a configuration or a workload by name."""
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} entry named {name!r} ({path})")
    return json.loads(path.read_text())


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    if conf["hidden_act"] != "silu":
        raise BenchError(f"{conf['name']}: hidden_act {conf['hidden_act']!r} is not run")
    if not math.isclose(conf["rms_norm_eps"], PROGRAM_RMS_EPS):
        raise BenchError(f"{conf['name']}: rms_norm_eps {conf['rms_norm_eps']} "
                         f"differs from the program's {PROGRAM_RMS_EPS}")
    return ModelConfig(
        name=conf["name"],
        family="dense",
        num_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        head_dim=conf["head_dim"],
        act="silu",
        qk_norm=conf["qk_norm"],
        qkv_bias=conf["attention_bias"],
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=conf["tie_word_embeddings"],
        dtype=conf["assumed"]["compute_dtype"],
        param_dtype=conf["assumed"]["param_dtype"],
        source=conf["source"],
    )


def seed_words(seed: int) -> tuple[int, int]:
    """Two 31-bit words from any whole-number seed (the driver's exceed 32 bits)."""
    a, b = np.random.SeedSequence(int(seed)).generate_state(2)
    return int(a) & 0x7FFFFFFF, int(b) & 0x7FFFFFFF


class SeededTokens:
    """Token batches by step: uniform over the vocabulary, every row of every
    step different, the same for the same seed. Duck-types the program's
    ``SyntheticLM.global_batch_at``."""

    def __init__(self, seed: int, vocab: int, batch: int, seq: int):
        self.key = seed_words(seed)[1]
        self.vocab, self.batch, self.seq = vocab, batch, seq

    def global_batch_at(self, step: int) -> np.ndarray:
        g = np.random.Generator(np.random.Philox(key=self.key, counter=[0, 0, 0, step]))
        return g.integers(0, self.vocab, size=(self.batch, self.seq), dtype=np.int32)


def init_params(conf: dict, key):
    """float32 weights from a JAX key, in the program's parameter layout:
    ``blocks/pos0`` holds every layer stacked on a leading axis."""
    import jax
    import jax.numpy as jnp

    L, d = conf["num_hidden_layers"], conf["hidden_size"]
    h, kh, hd = conf["num_attention_heads"], conf["num_key_value_heads"], conf["head_dim"]
    f, V = conf["intermediate_size"], conf["vocab_size"]
    ks = iter(jax.random.split(key, 12))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def matrix(fan_in, fan_out):
        return normal((L, fan_in, fan_out), fan_in**-0.5)

    mixer = {"wq": matrix(d, h * hd), "wk": matrix(d, kh * hd),
             "wv": matrix(d, kh * hd), "wo": matrix(h * hd, d)}
    if conf["attention_bias"]:
        mixer.update(bq=normal((L, h * hd), 0.02), bk=normal((L, kh * hd), 0.02),
                     bv=normal((L, kh * hd), 0.02))
    if conf["qk_norm"]:
        mixer.update(q_norm=jnp.ones((L, hd)), k_norm=jnp.ones((L, hd)))
    block = {
        "ln1": {"scale": jnp.ones((L, d))},
        "mixer": mixer,
        "ln2": {"scale": jnp.ones((L, d))},
        "mlp": {"wi_gate": matrix(d, f), "wi_up": matrix(d, f), "wo": matrix(f, d)},
    }
    params = {
        "embed": {"tok": normal((V, d), 0.02)},
        "blocks": {"pos0": block},
        "final_norm": {"scale": jnp.ones((d,))},
    }
    if not conf["tie_word_embeddings"]:
        params["lm_head"] = {"w": normal((d, V), d**-0.5)}
    return params


def params_key(seed: int):
    import jax

    return jax.random.key(seed_words(seed)[0])


def leaf_norms(tree) -> dict:
    """Per-leaf L2 norms, one per layer for the stacked ``blocks`` leaves.
    Traceable; returns ``{path: (layers,) or ()}``."""
    import jax
    import jax.numpy as jnp

    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = leaf.astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if name.startswith("blocks/") else None
        out[name] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return out


def flat_norms(norms: dict) -> dict[str, float]:
    """``{path: array}`` -> ``{path[i]: float}`` on the host."""
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            out.update({f"{name}[{i}]": float(x) for i, x in enumerate(v)})
    return out
