"""Where a Pallas kernel runs: compiled for the accelerator, interpreted
on the CPU.

Every kernel entry point takes ``interpret: bool | None = None`` and
resolves it here, so a kernel can only be interpreted on a TPU when the
caller asks for it explicitly.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` interprets only where the default backend is the CPU (the
    Pallas TPU lowering cannot run there); an explicit bool wins."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
