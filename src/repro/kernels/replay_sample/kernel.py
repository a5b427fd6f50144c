"""Pallas-TPU fused prioritized-sampling kernel.

priorities -> α-scaled log-weights -> Gumbel-top-k draw -> IS weights,
all in one kernel invocation. The capacity-C priority and Gumbel
vectors arrive as dense (R, 128) tiles (ops.py pads C and folds it into
rows; flat slot = row * 128 + lane), so C = 2^20 slots take 4 MiB of
VMEM per input instead of the 32 MiB a (1, C) row pads to. The scores
live in one VMEM scratch of that shape; the partition function reduces
to one scalar and only the n chosen logits are exponentiated for
weights, so no capacity-sized softmax is ever materialized.

The top-n draw is n rounds over the scores, each one blocked pass of
(BR, 128) rows that masks the previous round's pick and keeps, per tile
position, the running best score, its flat index and its priority.
The round's pick is the lowest flat index holding the maximum, which
is `jax.lax.top_k`'s tie-break, so kernel and ref draw the same slots.
Every value stays in vector registers: no argmax, no scatter, no
dynamic slice of an in-register array (Mosaic lowers none of them).

With fewer filled slots than n (avoid it — the draw is no longer
without-replacement), surplus positions repeat the top draw exactly as
the ref oracle does: unfilled slots are never returned.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import interpret_mode

_NEG = -3.4e38  # -inf stand-in: avoids inf-inf NaNs on the VPU
LANES = 128
BR = 64  # rows per block of the draw pass: 8 vregs per carried tile


def _blocks(R):
    """Rows per block and number of blocks for an (R, 128) tile."""
    br = min(BR, R)
    return br, R // br


def _flat(b, br):
    """Flat slot index of each element of row block b."""
    row = jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 1)
    return (b * br + row) * LANES + lane


def _fill_scores(prio_ref, gumbel_ref, s_ref, nvalid, *, alpha, eps):
    """s_ref <- masked α log(p + ε) + g (the ref's `scores`)."""
    br, nb = _blocks(prio_ref.shape[0])

    def block(b, carry):
        rows = pl.ds(pl.multiple_of(b * br, br), br)
        valid = _flat(b, br) < nvalid
        logits = jnp.where(valid, alpha * jnp.log(prio_ref[rows, :] + eps),
                           _NEG)
        s_ref[rows, :] = jnp.where(valid, logits + gumbel_ref[rows, :], _NEG)
        return carry

    jax.lax.fori_loop(0, nb, block, 0)


def _draw(prio_ref, s_ref, n):
    """n rounds of max+mask over s_ref. -> (idx, best score, priority of
    the pick), each (1, n)."""
    br, nb = _blocks(prio_ref.shape[0])
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def round_(i, carry):
        prev, idxs, maxes, prios = carry

        def block(b, best):
            top, top_idx, top_p = best
            rows = pl.ds(pl.multiple_of(b * br, br), br)
            flat = _flat(b, br)
            s = jnp.where(flat == prev, _NEG, s_ref[rows, :])
            s_ref[rows, :] = s
            up = s > top                       # strict: lower index wins
            return (jnp.where(up, s, top), jnp.where(up, flat, top_idx),
                    jnp.where(up, prio_ref[rows, :], top_p))

        init = (jnp.full((br, LANES), -jnp.inf, jnp.float32),
                jnp.zeros((br, LANES), jnp.int32),
                jnp.zeros((br, LANES), jnp.float32))
        top, top_idx, top_p = jax.lax.fori_loop(0, nb, block, init)
        m = jnp.max(top, axis=(0, 1), keepdims=True)              # (1,1)
        j = jnp.min(jnp.where(top == m, top_idx, jnp.iinfo(jnp.int32).max),
                    axis=(0, 1), keepdims=True)
        pj = jnp.sum(jnp.where(top_idx == j, top_p, 0.0), axis=(0, 1),
                     keepdims=True)
        here = pos == i
        return (j, jnp.where(here, j, idxs), jnp.where(here, m, maxes),
                jnp.where(here, pj, prios))

    init = (jnp.full((1, 1), -1, jnp.int32), jnp.zeros((1, n), jnp.int32),
            jnp.zeros((1, n), jnp.float32), jnp.zeros((1, n), jnp.float32))
    _, idxs, maxes, prios = jax.lax.fori_loop(0, n, round_, init)
    return idxs, maxes, prios


def _log_partition(prio_ref, nvalid, *, alpha, eps):
    """(max logit m, Σ exp(logit − m)) over the filled slots, (1,1)
    each."""
    br, nb = _blocks(prio_ref.shape[0])

    def logits(b):
        rows = pl.ds(pl.multiple_of(b * br, br), br)
        valid = _flat(b, br) < nvalid
        return valid, jnp.where(
            valid, alpha * jnp.log(prio_ref[rows, :] + eps), _NEG)

    def mx(b, acc):
        return jnp.maximum(acc, logits(b)[1])

    m = jnp.max(jax.lax.fori_loop(0, nb, mx,
                                  jnp.full((br, LANES), _NEG, jnp.float32)),
                axis=(0, 1), keepdims=True)

    def z(b, acc):
        valid, lg = logits(b)
        return acc + jnp.where(valid, jnp.exp(lg - m), 0.0)

    Z = jnp.sum(jax.lax.fori_loop(0, nb, z,
                                  jnp.zeros((br, LANES), jnp.float32)),
                axis=(0, 1), keepdims=True)
    return m, Z


def _kernel(prio_ref, gumbel_ref, size_ref, idx_ref, w_ref, s_ref,
            *, n, alpha, beta, eps):
    nvalid = jnp.maximum(size_ref[...], 1)                        # (1,1)
    _fill_scores(prio_ref, gumbel_ref, s_ref, nvalid, alpha=alpha, eps=eps)
    idxs, _, prios = _draw(prio_ref, s_ref, n)
    chosen = alpha * jnp.log(prios + eps)
    # n > size fallback: the first `size` positions hold every filled
    # slot (their scores dominate _NEG); surplus positions repeat the
    # top draw — matches ref.py, never returns an unfilled slot
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    surplus = pos >= nvalid
    idxs = jnp.where(surplus, idxs[:, :1], idxs)
    chosen = jnp.where(surplus, chosen[:, :1], chosen)

    m, Z = _log_partition(prio_ref, nvalid, alpha=alpha, eps=eps)
    p = jnp.exp(chosen - m) / Z
    w = (nvalid.astype(jnp.float32) * p + 1e-12) ** (-beta)
    idx_ref[...] = idxs
    w_ref[...] = w / jnp.maximum(jnp.max(w, axis=1, keepdims=True), 1e-12)


def _topk_kernel(prio_ref, gumbel_ref, nvalid_ref, idx_ref, s_out_ref,
                 s_ref, *, k, alpha, eps):
    """Per-shard candidate draw for the sharded replay service: the
    masking/score arithmetic of `_kernel` minus the weight epilogue
    (the service computes weights against the GLOBAL priority mass).
    `nvalid_ref` is the LOCAL valid count; the global max(size, 1)
    guard stays with the caller, so an empty shard yields only _NEG
    candidates."""
    nvalid = nvalid_ref[...]                                      # (1,1)
    _fill_scores(prio_ref, gumbel_ref, s_ref, nvalid, alpha=alpha, eps=eps)
    idxs, vals, _ = _draw(prio_ref, s_ref, k)
    # surplus positions (k > nvalid): the draw loop picks _NEG slots
    # once the filled ones are spent, but top_k over the flat vector
    # walks the remaining -inf slots in index order — indices nvalid,
    # nvalid+1, ..., i.e. position i holds index i. Rewrite to match
    # the ref bitwise; the merge never selects these unless the batch
    # itself is degenerate (overwritten by the caller's global-guard
    # rule anyway).
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)
    surplus = pos >= nvalid
    idx_ref[...] = jnp.where(surplus, pos, idxs)
    s_out_ref[...] = jnp.where(surplus, _NEG, vals)


def _call(kernel, prio, gumbel, count, n, out_dtypes):
    R = prio.shape[0]
    spec = pl.BlockSpec((R, LANES), lambda: (0, 0))
    out_spec = pl.BlockSpec((1, n), lambda: (0, 0))
    # prio + gumbel + score scratch, plus room for double buffering
    vmem = 5 * R * LANES * 4 + (8 << 20)
    return pl.pallas_call(
        kernel,
        grid=(),
        in_specs=[spec, spec, pl.BlockSpec((1, 1), lambda: (0, 0))],
        out_specs=(out_spec, out_spec),
        out_shape=tuple(jax.ShapeDtypeStruct((1, n), dt)
                        for dt in out_dtypes),
        scratch_shapes=[pltpu.VMEM((R, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(vmem, 32 << 20)),
        interpret=interpret_mode(),
    )(prio, gumbel, count)


@functools.partial(jax.jit, static_argnames=("k", "alpha", "eps"))
def shard_topk_c(prio, gumbel, nvalid, k, alpha=0.6, eps=1e-6):
    """prio/gumbel (R,128) f32 (see `layout`), nvalid (1,1) int32 LOCAL
    valid count. -> (scores (1,k) f32 descending with _NEG for invalid,
    idx (1,k) i32)."""
    kernel = functools.partial(_topk_kernel, k=k, alpha=alpha, eps=eps)
    idx, s = _call(kernel, prio, gumbel, nvalid, k,
                   (jnp.int32, jnp.float32))
    return s, idx


@functools.partial(jax.jit,
                   static_argnames=("n", "alpha", "beta", "eps"))
def prioritized_sample_c(prio, gumbel, size, n, alpha=0.6, beta=0.4,
                         eps=1e-6):
    """prio/gumbel (R,128) f32 (see `layout`), size (1,1) int32. ->
    (idx (1,n) i32, w (1,n) f32)."""
    kernel = functools.partial(_kernel, n=n, alpha=alpha, beta=beta,
                               eps=eps)
    return _call(kernel, prio, gumbel, size, n, (jnp.int32, jnp.float32))


def layout(x):
    """(C,) -> (R, 128): zero-pad C to whole blocks of rows. The padded
    slots sit past every valid count, so the kernels mask them."""
    rows = -(-x.shape[0] // LANES)
    rows = -(-rows // 8) * 8                       # whole sublane tiles
    if rows > BR:
        rows = -(-rows // BR) * BR
    x = jnp.pad(x.astype(jnp.float32), (0, rows * LANES - x.shape[0]))
    return x.reshape(rows, LANES)
