"""Work and FLOP functions against hand counts at small shapes. CPU only."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, roofline  # noqa: E402
from bench.reference import trunk  # noqa: E402

PEAK = {"flops_per_s": 100.0, "bytes_per_s": 10.0}


def test_flash_attention_fwd_hand_count():
    # B=2, H=4, KVH=2, S=3, D=8: 4*2*4*9*8 FLOPs; q,o at 4 heads and
    # k,v at 2, each 2*3*8 floats of 4 bytes
    w = roofline.flash_attention_fwd(2, 4, 2, 3, 8)
    assert w["flops"] == 2304
    assert w["bytes"] == 4 * 2 * 3 * 8 * (4 + 4 + 2 + 2)


def test_vtrace_and_sample_hand_count():
    w = roofline.vtrace(5, 3)
    assert w["bytes"] == 4 * (4 * 15 + 3 + 2 * 15) and w["flops"] == 150
    s = roofline.prioritized_sample(1024, 16)
    assert s["bytes"] == 2 * 4 * 1024 + 16 * 8 and s["flops"] == 10240


def test_least_time_names_its_bound():
    assert roofline.least_time_s({"flops": 1000, "bytes": 1}, PEAK) == (
        10.0, "compute")
    assert roofline.least_time_s({"flops": 1, "bytes": 1000}, PEAK) == (
        100.0, "memory")


SMALL = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 4, "d_ff": 16, "obs_dim": 3, "n_actions": 2}


def test_trunk_forward_flops_hand_count():
    # per position and layer: q 8*8, k and v 8*4 each, o 8*8, SwiGLU
    # 3*8*16, two FLOPs a multiply-add
    layer = 2 * (64 + 32 + 32 + 64 + 384)
    assert trunk.layer_flops(SMALL) == layer
    attn = 4 * 2 * 3 * 3 * 4
    assert trunk.forward_flops(SMALL) == (
        2 * (3 * layer + attn) + 2 * 3 * 8 + 2 * 8 * 3)


def test_training_flops_per_iteration():
    cell = harness.cell("impala-drltrunk.learn")
    t = dict(cell["traffic"], n_envs=4, unroll=2)
    fwd = trunk.forward_flops(cell["sizes"])
    # rollout 8 forwards, learner 8 x (forward + backward), boot 4
    assert cell["code"].flops_per_iter(cell["sizes"], t) == fwd * (8 + 24 + 4)
    dq = harness.cell("dqn-mlp.replay1m")
    mlp = 2 * (4 * 64 + 64 * 64 + 64 * 2)
    n = 256 * 8 + 5 * 512
    assert dq["code"].flops_per_iter(dq["sizes"], dq["traffic"]) == mlp * n


def test_kernel_work_counts_calls():
    cell = harness.cell("impala-drltrunk.learn")
    t = dict(cell["traffic"], n_envs=4, unroll=2)
    k = cell["code"].kernels(cell["sizes"], t, iters=3)
    # per iteration and layer: 2 rollout steps, the learner, the bootstrap
    assert k["flash_attention"]["calls"] == 3 * 4 * 4
    assert k["vtrace"]["calls"] == 3
    per = roofline.flash_attention_fwd(4, 4, 2, 4, 64)
    big = roofline.flash_attention_fwd(8, 4, 2, 4, 64)
    assert k["flash_attention"]["flops"] == 3 * 4 * (3 * per["flops"]
                                                    + big["flops"])
