"""Pallas kernels: each package holds kernel.py, its ops.py wrapper and a
pure-jnp ref.py oracle."""
import jax


def interpret_mode() -> bool:
    """Whether Pallas kernels run interpreted on the current platform:
    compiled on TPU, interpreted on CPU (tests and rehearsals).  Any other
    platform is an error rather than a silent fall back to the slow
    interpreter."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels target TPU (or CPU in interpret "
                       f"mode); no mode for platform {platform!r}")
