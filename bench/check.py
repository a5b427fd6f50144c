"""The comparison that decides `correct`: numbers of the program against
the plain reference, each worst case taken over leaves or steps."""
import statistics

import jax
import jax.numpy as jnp
import numpy as np


def leaf_norms(tree):
    """Float32 norm of every leaf, in tree order, as host floats."""
    leaves = jax.tree_util.tree_leaves(tree)
    norms = jax.jit(lambda ls: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in ls])(leaves)
    return [float(x) for x in jax.device_get(norms)]


def change_norms(new, old):
    """Per-leaf norm of new - old."""
    leaves_n = jax.tree_util.tree_leaves(new)
    leaves_o = jax.tree_util.tree_leaves(old)
    norms = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(a, b)])(leaves_n, leaves_o)
    return [float(x) for x in jax.device_get(norms)]


def moving_leaves(ref_grad_norms, share=1e-3):
    """Indices of the leaves whose reference gradient is not nought to
    rounding: at least `share` of the median leaf's."""
    med = statistics.median(ref_grad_norms)
    return [i for i, g in enumerate(ref_grad_norms) if g >= share * med]


def worst_leaf_gap(prog, ref, keep):
    """max over kept leaves of |prog - ref| / max(ref, median kept ref):
    the gap between two norms, measured against the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    med = statistics.median([ref[i] for i in keep])
    return max(abs(prog[i] - ref[i]) / max(ref[i], med, 1e-30)
               for i in keep)


def worst_rel_gap(prog, ref):
    """max_i |prog_i - ref_i| / |ref_i| over paired scalars."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref) / np.maximum(np.abs(ref), 1e-30)))
