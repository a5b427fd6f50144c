"""Attention on the learner hot path — public API.

House ref/kernel/ops convention (same seam as core/vtrace.py): the
model-side grouped-query layout (B, S, KVH, G, D) dispatches to the
Pallas flash-attention kernel (kernels/flash_attention/ops.py) on TPU
and to the pure-jnp oracle (kernels/flash_attention/ref.py) elsewhere,
so the transformer policy trunk (networks.TrunkPolicy) trains through
one call site on every backend. Both paths share the oracle; parity is
pinned in tests/test_kernels.py.
"""
from repro.kernels.common import interpret_mode
from repro.kernels.flash_attention.ref import attention_ref_grouped


def attention(qg, k, v, *, causal=True, window=0, use_kernel=False):
    """Grouped-query attention over the model layout.

    qg: (B, S, KVH, G, D) queries grouped per kv head; k, v:
    (B, S, KVH, D). Returns (B, S, KVH, G, D). `window` > 0 keeps only
    the trailing `window` keys per query (sliding-window attention)."""
    if use_kernel and not interpret_mode():
        from repro.kernels.flash_attention.ops import flash_attention
        return flash_attention(qg, k, v, causal=causal, window=window)
    return attention_ref_grouped(qg, k, v, causal=causal, window=window)
