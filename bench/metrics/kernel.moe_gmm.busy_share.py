"""moe_gmm kernel's (the experts' grouped matmul, forward and backward)
device time over the chip's busy time in the traced window (both summed
over the cell's chips). No kernel time found: no reading."""

KERNEL = "moe_gmm"


def read(ctx):
    red = ctx["reduced"]
    t = red["kernels"].get(KERNEL, {}).get("s", 0.0)
    if not t:
        return None
    return 100.0 * t / (red["busy_s"] * red["chips"])
