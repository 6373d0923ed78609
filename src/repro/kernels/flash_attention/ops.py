"""Public jit'd wrapper for causal GQA flash attention."""
from __future__ import annotations

from repro.kernels.backend import resolve
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


def flash_attention(q, k, v, *, scale=None, softcap: float = 0.0,
                    window: int = 0, backend: str = "auto"):
    """Causal GQA attention; see ref.py for exact semantics."""
    backend = resolve(backend)
    if backend == "pallas":
        return flash_attention_pallas(q, k, v, scale=scale, softcap=softcap,
                                      window=window)
    if backend == "interpret":
        return flash_attention_pallas(q, k, v, scale=scale, softcap=softcap,
                                      window=window, interpret=True)
    if backend == "xla":
        return flash_attention_ref(q, k, v, scale=scale, softcap=softcap,
                                   window=window)
    raise ValueError(f"unknown backend {backend!r}")
