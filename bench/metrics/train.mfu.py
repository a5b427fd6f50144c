"""Model FLOPs of the iterations the traced window completed (a forward
per env step in the rollout, forward and backward per learner sample, no
recompute; the configuration's flops_per_iter) over window x chips x the
chip's peak FLOP/s (bf16, bench/peaks.json)."""


def read(ctx):
    red = ctx["reduced"]
    return 100.0 * ctx["flops"] / (red["window_s"] * ctx["chips"]
                                   * ctx["peaks"]["flops_per_s"])
