"""Forward FLOPs of the valid (unpadded) rows served in the traced window
(the configuration's serve_flops per row) over window x chips x the
chip's peak FLOP/s (bf16, bench/peaks.json)."""


def read(ctx):
    red = ctx["reduced"]
    return 100.0 * ctx["flops"] / (red["window_s"] * ctx["chips"]
                                   * ctx["peaks"]["flops_per_s"])
