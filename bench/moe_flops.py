"""Operations and bytes of the MoE layers' expert products, counted from the
(token, choice) pairs that were routed to the experts held (the program's
``moe_local_rows``), so that the same routing reads the same work whatever
computes the grouped products.

Each expert layer runs three products over its rows: gate and up (d -> f)
and down (f -> d), 2 x rows x d x f operations each. A train step under full
remat runs them in the forward, again in the recompute and, for each, an
input and a weight gradient in the backward: 4 x 6 x rows x d x f. Bytes:
each product reads its rows and its experts' weights and writes its rows
once (the backward's two products a forward product, the same again each).
"""

from __future__ import annotations


def expert_products(rows: int, layer_steps: int, d: int, f: int, experts: int,
                    itemsize: int = 2) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of a train step's expert products: ``rows`` pairs
    over all layers and steps, ``layer_steps`` expert layers times steps, each
    with ``experts`` held experts of width ``f`` on a model of width ``d``."""
    passes = 4  # forward, remat recompute, backward's input and weight products
    flops = passes * 6.0 * rows * d * f
    elements = 3 * rows * (d + f) + 3 * layer_steps * experts * d * f
    return flops, float(passes * itemsize * elements)
