"""Dispatch rule for the Pallas kernels: on a TPU the model code always
takes the kernel; elsewhere it runs the jnp path (identical math,
XLA-fused).  Interpret mode runs only where a caller asks for it — the
kernel tests pass ``interpret=True`` to the kernels directly.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"
