"""Pallas TPU kernels, each with ``ops.py`` (dispatch) and ``ref.py``
(the pure-jnp oracle tests compare against)."""
import jax


def interpret_mode() -> bool:
    """True on the CPU backend, where kernels run in Pallas interpret mode
    (tests, CPU rehearsals); False on TPU, where they compile.  Any other
    backend is refused: an interpreter there would serve on the host and
    hide the device."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels target TPU (or CPU interpret mode); "
                       f"backend {backend!r} is not supported")
