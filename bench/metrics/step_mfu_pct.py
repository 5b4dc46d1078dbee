"""Model FLOP/s utilization of the whole train step: model FLOPs per step
(the configuration's family's ``flops_per_step``) times steps completed over
the window's host-clock time, over the chips' bf16 peak. Only where the
layout is fixed."""

from bench import model

LAYER = "model step"
UNIT = "%"
MOVES = "train_tokens_per_s"


def read(run):
    wl, out = run.ctx.workload, run.out
    if run.peaks is None or "grow_to" in wl or not out.step_s:
        return None
    conf = run.ctx.conf
    per_step = model.family(conf).flops_per_step(conf, wl["global_batch"], wl["seq_len"])
    achieved = per_step * len(out.step_s) / out.window_s
    return 100.0 * achieved / (run.peaks["bf16_flops_per_s"] * run.ctx.chips)
