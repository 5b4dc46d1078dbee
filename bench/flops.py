"""Operations and bytes of the kernels a cell's work runs, computed from
their shapes. A whole step's model FLOPs depend on the architecture and are
its family's ``flops_per_step`` (``bench/families/``).

``flash_forward``: one call of the flash-attention forward kernel.
"""

from __future__ import annotations


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def flash_forward(batch: int, heads: int, kv_heads: int, seq: int, head_dim: int,
                  itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one causal flash forward: q.k and p.v over the
    kept pairs; q, k, v read once and the output written once."""
    flops = 4.0 * batch * heads * head_dim * causal_pairs(seq)
    nbytes = itemsize * batch * seq * head_dim * (2 * heads + 2 * kv_heads)
    return flops, float(nbytes)
