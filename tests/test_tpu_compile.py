"""Compile every Pallas kernel of the main path for a described TPU v5e.

Nothing runs: each test lowers a kernel at deployment widths for one
chip of a `v5e:2x2` topology that is described, not attached, and
checks that the TPU compiler accepted it and kept the Pallas kernel
(`tpu_custom_call`) rather than a reference. This catches what
interpret mode cannot (ops Mosaic does not lower, VMEM overflows)
before any chip time is spent.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and under
pytest-xdist every worker imports this file.
"""
import functools
import importlib

import jax
import jax.numpy as jnp
import pytest

KERNEL_MODULES = ("advantages", "flash_attention", "replay_sample",
                  "vtrace")

# paper-drl-trunk attention widths (configs/paper_drl.py)
H, KVH, D = 4, 2, 64
REPLAY_C, REPLAY_N = 2 ** 20, 512      # Nature-DQN replay capacity
# LFM2-8B-A1B experts (configs/lfm2_8b_a1b.py): one chip's 8, hidden 2048,
# expert width 1792; rows = tokens x top-4 (the learn cell's rollout 128
# tokens, learner 4,096)
GMM_G, GMM_D, GMM_F = 8, 2048, 1792


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_tpu(topo):
    """-> compile(fn, *shapes) returning the compiled HLO text for one
    described chip. Kernels run compiled (not interpreted) and the
    persistent cache is off: a TPU executable written here could not be
    read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])
    cache_was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        for name in KERNEL_MODULES:
            mod = importlib.import_module(f"repro.kernels.{name}.kernel")
            mp.setattr(mod, "interpret_mode", lambda: False)
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        jax.clear_caches()  # drop traces made in interpret mode

        def compile_(fn, *shapes):
            args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                    for s, dt in shapes]
            return jax.jit(fn).lower(*args).compile().as_text()

        yield compile_
        jax.clear_caches()  # keep compiled-mode traces out of CPU tests
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


F32 = jnp.float32


def _assert_kernel(hlo):
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("B,S", [(8192, 4), (8, 512), (256, 4), (16, 4),
                                 (1, 4)])
def test_flash_forward_compiles(compile_tpu, B, S):
    from repro.kernels.flash_attention.ops import flash_attention
    G = H // KVH
    _assert_kernel(compile_tpu(
        flash_attention, ((B, S, KVH, G, D), F32), ((B, S, KVH, D), F32),
        ((B, S, KVH, D), F32)))


@pytest.mark.parametrize("B,S", [(8192, 4), (8, 512), (256, 4), (16, 4),
                                 (1, 4)])
def test_flash_grad_compiles(compile_tpu, B, S):
    from repro.kernels.flash_attention.ops import flash_attention

    def loss(qg, k, v):
        return jnp.sum(flash_attention(qg, k, v) ** 2)

    G = H // KVH
    _assert_kernel(compile_tpu(
        jax.grad(loss, argnums=(0, 1, 2)), ((B, S, KVH, G, D), F32),
        ((B, S, KVH, D), F32), ((B, S, KVH, D), F32)))


@pytest.mark.parametrize("M", [128 * 4, 4096 * 4])
@pytest.mark.parametrize("d,f", [(GMM_D, GMM_F), (GMM_F, GMM_D)])
def test_gmm_forward_compiles(compile_tpu, M, d, f):
    """The experts' grouped matmul (XLA's ragged dot) is a TPU kernel."""
    from repro.models.moe import moe_gmm
    hlo = compile_tpu(moe_gmm, ((M, d), F32), ((GMM_G, d, f), F32),
                      ((GMM_G,), jnp.int32))
    _assert_kernel(hlo)
    assert "ragged-dot" in hlo


@pytest.mark.parametrize("M", [128 * 4, 4096 * 4])
def test_gmm_grad_compiles(compile_tpu, M):
    from repro.models.moe import moe_gmm

    def loss(x, w, gs):
        return jnp.sum(moe_gmm(x, w, gs) ** 2)

    _assert_kernel(compile_tpu(
        jax.grad(loss, argnums=(0, 1)), ((M, GMM_D), F32),
        ((GMM_G, GMM_D, GMM_F), F32), ((GMM_G,), jnp.int32)))


def test_vtrace_compiles(compile_tpu):
    from repro.kernels.vtrace.ops import vtrace
    T, B = 32, 1024
    tb = ((T, B), F32)
    _assert_kernel(compile_tpu(vtrace, tb, tb, tb, tb, ((B,), F32)))


@pytest.mark.parametrize("B", [1024, 4096])
def test_gae_compiles(compile_tpu, B):
    from repro.kernels.advantages.ops import gae
    tb = ((32, B), F32)
    _assert_kernel(compile_tpu(gae, tb, tb, ((32, B), jnp.bool_),
                               ((B,), F32)))


def test_nstep_return_compiles(compile_tpu):
    from repro.kernels.advantages.ops import nstep_return
    T, B = 32, 1024
    _assert_kernel(compile_tpu(nstep_return, ((T, B), F32),
                               ((T, B), jnp.bool_), ((B,), F32)))


def test_prioritized_sample_compiles(compile_tpu):
    from repro.kernels.replay_sample.ops import prioritized_sample
    fn = functools.partial(prioritized_sample, n=REPLAY_N)
    _assert_kernel(compile_tpu(fn, ((REPLAY_C,), F32), ((), jnp.int32),
                               ((REPLAY_C,), F32)))


def test_shard_topk_compiles(compile_tpu):
    from repro.kernels.replay_sample.ops import shard_topk
    fn = functools.partial(shard_topk, k=REPLAY_N)
    _assert_kernel(compile_tpu(fn, ((REPLAY_C,), F32), ((), jnp.int32),
                               ((REPLAY_C,), F32)))
