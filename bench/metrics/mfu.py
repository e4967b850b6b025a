"""mfu: model FLOPs of the traced steps over their time, over the chips'
bf16 peak.  Model FLOPs per token are ``flops.train_flops_per_token``
(6 per matmul parameter, the tied LM head included, plus causal attention;
recomputation not counted).  The time is the traced window on the host
clock, from the first step's dispatch to the last step's outputs."""


def read(run):
    per_token = run.flops.train_flops_per_token(run.config, run.mix["seq_len"])
    work = per_token * run.tokens_per_step * run.n_steps
    return 100.0 * work / (run.trace.window_s() * run.chips
                           * run.peak.bf16_flops)
