"""Roofline share of the MoE layers' expert products on chip 0: their FLOPs
and bytes over the window, counted from the ``moe_local_rows`` the program
reported for its steps (``bench/moe_flops.py``), against the larger of the
compute and the HBM bound, over the self time of the ops under
``moe/experts`` (``bench/scopes.py``)."""

from bench import moe_flops, scopes

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    conf, wl = run.ctx.conf, run.ctx.workload
    if run.peaks is None or "grow_to" in wl:
        return None
    rows, secs = scopes.window_rows(run), scopes.seconds(run, "moe", "experts")
    if not rows or not secs:
        return None
    moe_layers = conf["num_hidden_layers"] - conf["num_dense_layers"]
    flops, nbytes = moe_flops.expert_products(
        rows, moe_layers * len(run.out.step_s), conf["hidden_size"],
        conf["moe_intermediate_size"], conf["num_experts"])
    bound = max(flops / run.peaks["bf16_flops_per_s"], nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / secs
