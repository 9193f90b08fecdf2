"""repro.kernels — Pallas TPU kernels (RF inference, wire quantization,
SSD scan) with jnp oracles in `ref.py`; call through `ops.py`.

Only the platform decides interpret mode: a kernel runs in the Pallas
interpreter where the backend is the CPU and compiles everywhere else.
A kernel that the TPU compiler refuses raises; nothing falls back to the
interpreter or to the jnp reference.
"""
import jax


def interpret_default() -> bool:
    """True only on the CPU backend (the kernels' `interpret=None`)."""
    return jax.default_backend() == "cpu"
