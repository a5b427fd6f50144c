"""Share of the traced window in which no operation ran on the chip:
1 - (union of the chip's operation intervals) / window, averaged over the
cell's chips, from the device trace."""


def read(ctx):
    red = ctx["reduced"]
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
