"""Pallas TPU kernels (``flash_attention``, ``rmsnorm``) with pure-jnp
references in ``ref.py`` and jitted wrappers in ``ops.py``."""
import jax


def default_interpret() -> bool:
    """Pallas lowers to Mosaic only on a TPU backend; on any other
    backend a kernel runs in the Pallas interpreter."""
    return jax.default_backend() != "tpu"
