"""Work functions of the kernels: the FLOPs and bytes an operation needs,
counted from its shapes, whatever implements it. A later change to the
algorithm inside a kernel is read against the same yardstick.

A roofline share is the least time the chip could take, the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s, over the time the
kernel took on the device.
"""
F32 = 4
I32 = 4


def flash_attention_fwd(B, H, KVH, S, D, itemsize=F32):
    """Causal attention forward at the unpadded length S: scores and
    mixing, 4 B H S^2 D FLOPs (the full square, as the kernel's grid
    visits it); q and o at H heads, k and v at KVH heads, each read or
    written once."""
    return {"flops": 4 * B * H * S * S * D,
            "bytes": itemsize * B * S * D * (2 * H + 2 * KVH)}


def vtrace(T, B):
    """V-trace over (T, B): five inputs read (four (T, B), one (B,)) and
    two (T, B) outputs written, in float32; about ten FLOPs a cell."""
    return {"flops": 10 * T * B, "bytes": F32 * (6 * T * B + B)}


def prioritized_sample(C, n):
    """Draw n of C slots by Gumbel-top-k: one read of the C priorities
    and the C Gumbel values, n indices and n weights written; about ten
    FLOPs a slot for the logits, the key and the partition sum."""
    return {"flops": 10 * C, "bytes": F32 * 2 * C + (I32 + F32) * n}


def least_time_s(work, peak):
    """(least seconds, the bound that sets it: "compute" or "memory")."""
    t_c = work["flops"] / peak["flops_per_s"]
    t_m = work["bytes"] / peak["bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
