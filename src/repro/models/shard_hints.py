"""Trace-time activation-sharding hints (beyond-paper, DESIGN.md §8).

GSPMD propagates shardings from weights alone, which leaves several
pathologies in the baseline HLO (full logits all-gathers, replicated MoE
dispatch compute, FSDP param gathers on the decode path). A step builder
wraps its body in ``active({...})`` with NamedShardings; the model code
calls ``constrain(x, "logits")`` etc. at the annotated points. The
contextvar is thread-local, so concurrent shadow-world traces are safe;
with no active hints every call is a no-op (the paper-faithful baseline).
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Optional

import jax

_HINTS: contextvars.ContextVar[dict] = contextvars.ContextVar(
    "shard_hints", default={}
)

# annotated points (documented for the perf log):
#   activation      (b, s, d)       embedding output / residual stream
#   logits          (b, s, vocab)   pre-CE logits
#   attn_qkv        (b, s, h, hd)   q/k/v after head reshape


@contextlib.contextmanager
def active(hints: Optional[dict]):
    tok = _HINTS.set(hints or {})
    try:
        yield
    finally:
        _HINTS.reset(tok)


def constrain(x: Any, name: str):
    h = _HINTS.get().get(name)
    if h is None:
        return x
    return jax.lax.with_sharding_constraint(x, h)


def make_train_hints(mesh, version: str) -> dict:
    """Pre-baked hint sets used by the §Perf iterations."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = batch_axes if batch_axes else None
    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    if version == "v1":  # vocab-sharded logits + batch-sharded activations
        return {
            "activation": ns(bspec, None, None),
            "logits": ns(bspec, None, "model"),
        }
    if version == "v2":  # v1 + TP attention activations
        return {
            **make_train_hints(mesh, "v1"),
            "attn_qkv": ns(bspec, None, "model", None),
        }
    if version == "v4":  # v2 + sequence-parallel residual stream
        return {
            **make_train_hints(mesh, "v2"),
            "activation": ns(bspec, "model", None),
        }
    raise KeyError(version)
