"""Per-layer metric readers, one module per metric name in ``BENCHMARK.json``.

Each declares ``LAYER``, ``UNIT`` and ``MOVES`` (the end-to-end metric it
should move) and has ``read(run) -> float | None``, where ``run`` is a
``harness.Run``. A reader that finds nothing to read returns None and the
metric is left out of the line.
"""
