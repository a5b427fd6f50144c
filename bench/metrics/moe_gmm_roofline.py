"""moe_gmm kernel (the experts' grouped matmul, XLA's ragged dot, forward
and backward): the least time its work needs on one chip (the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, from the
configuration's `gmm_work` with rows at tokens x top-k x held /
published experts) over the kernel's device time per chip in the
trace. The bound that applies is printed on standard error. No kernel
time found: no reading."""
import sys

from bench import roofline

KERNEL = "moe_gmm"


def read(ctx):
    t = ctx["reduced"]["kernels"].get(KERNEL, {}).get("s", 0.0) / ctx["chips"]
    work = ctx["kernels"].get(KERNEL)
    if not t or not work:
        return None
    least, bound = roofline.least_time_s(work, ctx["peaks"])
    sys.stderr.write(f"{KERNEL}_roofline: {bound} bound\n")
    return 100.0 * least / t
