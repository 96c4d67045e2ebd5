"""Pallas kernels: the ECC/parity scrub and encode codecs, bit-flip
injection, and the graph push/frontier steps.

Every kernel entry point takes ``interpret=None``, which follows the
backend: the Pallas interpreter on CPU, compiled Mosaic on a TPU. The
backend is read when a kernel is traced, never while a module is imported.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret=None) -> bool:
    """Resolve a kernel's ``interpret=`` argument. ``None`` selects the
    interpreter exactly on the CPU backend; ``True`` on a TPU is refused, so
    no kernel on the chip runs the interpreter by mistake."""
    backend = jax.default_backend()
    if interpret is None:
        return backend == "cpu"
    if interpret and backend == "tpu":
        raise ValueError("interpret=True on the TPU backend: kernels on the "
                         "chip must run compiled")
    return bool(interpret)
