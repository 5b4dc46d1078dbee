"""What a cell is made of: its files, its model family, its seed's keys and
its seeded batches.

Everything here is the benchmark's own. The weights (the family's
``init_params`` under ``params_key``) and token batches are made from
``--seed`` by the benchmark, so the program under test and the plain
reference (``reference.py``) start from the same numbers without the
reference taking anything the program made.
"""

from __future__ import annotations

import importlib
import json
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent


class BenchError(Exception):
    """A cell, configuration or device the benchmark cannot run."""


def load(kind: str, name: str, root: Path = BENCH) -> dict:
    """``<root>/<kind>/<name>.json``: a configuration or a workload by name.
    A configuration has to name a family that ``family`` finds."""
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} entry named {name!r} ({path})")
    entry = json.loads(path.read_text())
    if kind == "configs":
        family(entry, where=str(path))
    return entry


def family(conf: dict, where: str = ""):
    """The module of ``bench/families/`` that the configuration's ``family``
    key names: its ``model_config``, ``init_params`` and ``flops_per_step``."""
    where = where or f"configuration {conf.get('name')!r}"
    name = conf.get("family")
    if not isinstance(name, str) or not name.isidentifier():
        raise BenchError(f"{where}: the key 'family' must name a module of bench/families/, "
                         f"not {name!r}")
    module = f"bench.families.{name}"
    try:
        return importlib.import_module(module)
    except ModuleNotFoundError as e:
        if e.name != module:
            raise
        raise BenchError(f"{where}: unknown family {name!r} "
                         f"(no bench/families/{name}.py)") from None


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
