"""Jit'd wrappers: lay (C,) priorities out as the fused Pallas
prioritized-sampling kernels' (R, 128) tiles."""
import jax.numpy as jnp

from repro.kernels.replay_sample.kernel import (_NEG, layout,
                                                prioritized_sample_c,
                                                shard_topk_c)


def prioritized_sample(prio, size, gumbel, n, alpha=0.6, beta=0.4,
                       eps=1e-6):
    """prio (C,) raw priorities, size scalar int32, gumbel (C,) standard
    Gumbel noise. Returns (idx (n,) int32, w (n,) f32)."""
    idx, w = prioritized_sample_c(
        layout(prio), layout(gumbel),
        jnp.asarray(size, jnp.int32).reshape(1, 1),
        n=n, alpha=float(alpha), beta=float(beta), eps=float(eps))
    return idx[0], w[0]


def shard_topk(prio, nvalid, gumbel, k, alpha=0.6, eps=1e-6):
    """prio (chunk,) raw priorities of ONE replay shard, nvalid scalar
    int32 LOCAL valid count, gumbel (chunk,) this shard's slice of the
    global Gumbel noise. Returns (scores (k,) f32, idx (k,) int32).
    The kernel masks with the finite _NEG stand-in; restore -inf here
    so the candidate scores match shard_gumbel_topk_ref bitwise."""
    s, idx = shard_topk_c(
        layout(prio), layout(gumbel),
        jnp.asarray(nvalid, jnp.int32).reshape(1, 1),
        k=k, alpha=float(alpha), eps=float(eps))
    s = s[0]
    return jnp.where(s == jnp.float32(_NEG), -jnp.inf, s), idx[0]
