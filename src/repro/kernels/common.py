"""Shared kernel helper: interpret-mode selection."""
import jax


def interpret_mode() -> bool:
    """Pallas-TPU kernels execute in interpret mode off-TPU (CPU CI)."""
    return jax.default_backend() != "tpu"
