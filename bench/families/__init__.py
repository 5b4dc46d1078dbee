"""Model families, one module per ``family`` name of a configuration file.

Each has three functions of the configuration file's dict:

- ``model_config(conf)``: the program's ``ModelConfig``;
- ``init_params(conf, key)``: the seed's float32 weights from a JAX key, in
  the program's parameter layout (traceable, so that they are made on the
  device in one jitted call);
- ``flops_per_step(conf, batch, seq)``: the model FLOPs of one training step,
  the numerator of ``step_mfu_pct``.

The plain reference that a configuration is compared with is the module
that its ``reference`` key names (``bench/<reference>.py``); it needs only a
``loss_fn`` and reuses the trainer in ``bench/reference.py``.
"""
