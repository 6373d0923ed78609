"""Kernel backend selection shared by every ``ops.py`` wrapper.

  'pallas'    -- compiled Pallas kernel (TPU runtime)
  'interpret' -- Pallas interpret mode (CPU validation of the kernel body)
  'xla'       -- pure-jnp reference
  'auto'      -- 'pallas' when JAX's default device is a TPU, else 'xla'
"""
from __future__ import annotations

import jax


def resolve(backend: str) -> str:
    """Map 'auto' to the concrete backend; other names pass through.

    Nothing is caught: a device that fails to initialise raises here
    instead of passing for a CPU and quietly selecting the XLA path.
    """
    if backend != "auto":
        return backend
    return "pallas" if jax.devices()[0].platform == "tpu" else "xla"
