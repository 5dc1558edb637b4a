"""The one backend-dispatch rule every kernel wrapper follows.

``impl='auto'`` is the Pallas kernel on a TPU and the XLA reference
elsewhere; a Pallas kernel runs compiled on a TPU and in interpret mode
elsewhere (how the CPU test suite validates the kernels).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(impl: str, valid: Sequence[str]) -> str:
    """``impl`` checked against ``valid``; 'auto' is 'pallas' on a TPU and
    'xla' elsewhere."""
    if impl not in valid:
        raise ValueError(f"impl={impl!r} not in {tuple(valid)}")
    if impl == "auto":
        return "pallas" if on_tpu() else "xla"
    return impl


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Explicit ``interpret`` wins; otherwise interpret only off-TPU."""
    return (not on_tpu()) if interpret is None else interpret


PAGED_IMPLS = ("auto", "pallas", "xla")


def resolve_paged(impl: str, quantized: bool) -> str:
    """The implementation a paged-attention call runs. 'auto' is the kernel
    on a TPU for int8 pages; float pages have no kernel and run the
    reference. An explicit 'pallas' over float pages is an error, never a
    silent fallback."""
    resolved = resolve(impl, PAGED_IMPLS)
    if quantized:
        return resolved
    if impl == "pallas":
        raise ValueError("the Pallas paged kernel needs int8 pages with "
                         "scales; got float pages (k_scale is None)")
    return "xla"
