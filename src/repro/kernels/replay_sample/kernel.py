"""Pallas-TPU fused prioritized-sampling kernel.

priorities -> α-scaled log-weights -> Gumbel-top-k draw -> IS weights,
all in one kernel invocation. The capacity-C priority and Gumbel
vectors arrive as dense (R, 128) tiles (ops.py pads C and folds it into
rows; flat slot = row * 128 + lane), so C = 2^20 slots take 4 MiB of
VMEM per input instead of the 32 MiB a (1, C) row pads to. The scores
live in one VMEM scratch of that shape; the partition function reduces
to one scalar and only the n chosen logits are exponentiated for
weights, so no capacity-sized softmax is ever materialized.

The top-n draw is a two-level tournament, so that its passes over C do
not grow with n. The pass that writes the scores also keeps each
block's best: the scores fall into blocks of TB whole rows (TB x 128
slots), and for each block its maximum score and the lowest flat index
that holds it go into two small lane-dense (SR, 128) arrays, one entry
a block (1,024 blocks of 8 rows are one vreg each at C = 2^20). The
winner is the maximum m of the block bests and the lowest flat index j
among the entries equal to m: the lowest index of the global maximum,
which is `jax.lax.top_k`'s tie-break, so kernel and ref draw the same
slots in the same order, ties included. Each of the n rounds then
  1. records the winner j, m and the priority of slot j;
  2. loads block j // (TB * 128) alone, writes _NEG into slot j,
     re-scans the block for its new best and puts that back into the
     small arrays with an iota `where`;
  3. takes the next winner from that new best and the other blocks'
     best (reduced beside the re-scan), the lower index on a tie.
A round thus touches one block and the small arrays, not all of C.
The one dynamic access is the block's load and store: j is reduced to a
scalar and gives an aligned row start into the scratch. Everything else
is compare-and-select against iotas, so there is no scatter, no argmax
and no dynamic slice of an in-register array (Mosaic lowers none of
them).

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
_IMAX = jnp.iinfo(jnp.int32).max
LANES = 128
BR = 64  # rows per chunk of the fill and partition passes: 8 vregs
TB = 8   # rows per block of the draw's tournament: one vreg


def _blocks(R, br=BR):
    """Rows per block and number of blocks for an (R, 128) tile. `layout`
    makes R a multiple of 8, and of BR past BR, so each block is whole."""
    br = min(br, R)
    return br, R // br


def _flat(b, br):
    """Flat slot index of each element of row block b."""
    row = jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (br, LANES), 1)
    return (b * br + row) * LANES + lane


def _best(s, idx):
    """(maximum of s, lowest index of idx where s holds it), (1,1) each:
    a block's best from its scores and flat indices, or the winner of
    the small arrays from the block bests."""
    m = jnp.max(s, axis=(0, 1), keepdims=True)
    return m, jnp.min(jnp.where(s == m, idx, _IMAX), axis=(0, 1),
                      keepdims=True)


def _at(sr, b):
    """Mask of block b's entry in the (sr, 128) small arrays (one entry a
    block, row-major)."""
    return (jax.lax.broadcasted_iota(jnp.int32, (sr, LANES), 0) * LANES
            + jax.lax.broadcasted_iota(jnp.int32, (sr, LANES), 1)) == b


def _fill_scores(prio_ref, gumbel_ref, s_ref, nvalid, *, alpha, eps):
    """s_ref <- masked α log(p + ε) + g (the ref's `scores`), in chunks
    of BR rows. -> the tournament's block bests (score, flat index),
    (SR, 128) each; the entries past the last block hold -inf and never
    win. A chunk's blocks are reduced side by side, not one per step."""
    R = prio_ref.shape[0]
    br, nc = _blocks(R)
    tb, nb = _blocks(R, TB)
    sr = -(-nb // LANES)

    def chunk(c, bests):
        rows = pl.ds(pl.multiple_of(c * br, br), br)
        flat = _flat(c, br)
        valid = flat < nvalid
        logits = jnp.where(valid, alpha * jnp.log(prio_ref[rows, :] + eps),
                           _NEG)
        s = jnp.where(valid, logits + gumbel_ref[rows, :], _NEG)
        s_ref[rows, :] = s
        best, arg = bests
        for k in range(br // tb):
            m, j = _best(s[k * tb:(k + 1) * tb], flat[k * tb:(k + 1) * tb])
            at = _at(sr, c * (br // tb) + k)
            best, arg = jnp.where(at, m, best), jnp.where(at, j, arg)
        return best, arg

    init = (jnp.full((sr, LANES), -jnp.inf, jnp.float32),
            jnp.full((sr, LANES), _IMAX, jnp.int32))
    return jax.lax.fori_loop(0, nc, chunk, init)


def _draw(prio_ref, s_ref, bests, n):
    """n tournament rounds over s_ref and its block bests. -> (idx, best
    score, priority of the pick), each (1, n).

    The carry holds the current winner (m, j). A round re-scans j's
    block and, beside it, reduces the other blocks' bests; the next
    winner is the better of the two, the lower index on a tie. So each
    round waits on two reductions, not four."""
    tb, _ = _blocks(prio_ref.shape[0], TB)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

    def round_(i, carry):
        (best, arg), (m, j), idxs, maxes, prios = carry
        b = j[0, 0] // (tb * LANES)
        rows = pl.ds(pl.multiple_of(b * tb, tb), tb)
        flat = _flat(b, tb)
        hit = flat == j
        pj = jnp.sum(jnp.where(hit, prio_ref[rows, :], 0.0), axis=(0, 1),
                     keepdims=True)
        s = jnp.where(hit, _NEG, s_ref[rows, :])
        s_ref[rows, :] = s
        mb, jb = _best(s, flat)
        at = _at(best.shape[0], b)
        # with one block, every entry of the rest is -inf and jr is
        # stale; mb >= _NEG then wins alone
        mr, jr = _best(jnp.where(at, -jnp.inf, best), arg)
        top = jnp.maximum(mb, mr)
        nxt = jnp.minimum(jnp.where(mb == top, jb, _IMAX),
                          jnp.where(mr == top, jr, _IMAX))
        here = pos == i
        return ((jnp.where(at, mb, best), jnp.where(at, jb, arg)),
                (top, nxt), jnp.where(here, j, idxs),
                jnp.where(here, m, maxes), jnp.where(here, pj, prios))

    init = (bests, _best(*bests), jnp.zeros((1, n), jnp.int32),
            jnp.zeros((1, n), jnp.float32), jnp.zeros((1, n), jnp.float32))
    _, _, idxs, maxes, prios = jax.lax.fori_loop(0, n, round_, init)
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
    bests = _fill_scores(prio_ref, gumbel_ref, s_ref, nvalid, alpha=alpha,
                         eps=eps)
    idxs, _, prios = _draw(prio_ref, s_ref, bests, n)
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
    bests = _fill_scores(prio_ref, gumbel_ref, s_ref, nvalid, alpha=alpha,
                         eps=eps)
    idxs, vals, _ = _draw(prio_ref, s_ref, bests, k)
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
