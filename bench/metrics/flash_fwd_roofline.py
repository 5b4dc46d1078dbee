"""Roofline share of the flash-attention forward kernel on chip 0: its
FLOPs and bytes per call (``flops.flash_forward``) times its calls in the
trace, over the summed device time of those calls, against the larger of
the compute and the HBM bound. At the cells' shapes compute bounds it."""

from bench import flops

LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s"
# a Pallas kernel's trace event carries no kernel name, only its HLO text:
# the flash forward is the Pallas call whose output is q's shape
PALLAS = 'custom_call_target="tpu_custom_call"'


def read(run):
    wl, conf = run.ctx.workload, run.ctx.conf
    if run.trace is None or run.peaks is None or "grow_to" in wl:
        return None
    b, h, s, hd = (wl["global_batch"], conf["num_attention_heads"], wl["seq_len"],
                   conf["head_dim"])
    dtype = {"bfloat16": "bf16", "float32": "f32"}[conf["assumed"]["compute_dtype"]]
    secs, calls = run.trace.op_time(0, PALLAS, f"= {dtype}[{b},{h},{s},{hd}]")
    if not calls or secs <= 0:
        return None
    f, nbytes = flops.flash_forward(b, h, conf["num_key_value_heads"], s, hd)
    bound = max(calls * f / run.peaks["bf16_flops_per_s"],
                calls * nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * bound / secs
