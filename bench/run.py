"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object; the
numbers ``correct`` was decided on are the last lines of standard error.
Exits non-zero, with no result, where JAX finds no TPU or fewer chips than
the cell needs. JAX's compilation cache is kept at ``<checkout>/.jax_cache``.
"""

import os
import sys
import time

T0 = time.perf_counter()
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    from bench import harness

    sys.exit(harness.main(sys.argv[1:], T0))
