"""Where a Pallas kernel runs: compiled through Mosaic, or interpreted.

One rule for every kernel entry point, applied when the call is traced
(never at import): interpret only when the backend running the call is
the CPU.  A TPU never interprets.  Code that picks between a kernel and
an XLA formulation of the same result asks ``compiles_with_mosaic``, so
it takes the kernel only where the kernel compiles.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``None`` → interpret iff the default backend is the CPU.

    ``False`` is honoured everywhere (a compile for a described chip
    from a CPU process passes it); ``True`` off the CPU is an error.
    """
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            f"Pallas interpret mode requested on backend "
            f"{jax.default_backend()!r}; kernels interpret only on the CPU")
    return bool(interpret)


def compiles_with_mosaic(backend: str) -> bool:
    """Whether the kernels compile through Mosaic on ``backend``: on a
    TPU only (the CPU interprets them)."""
    return backend == "tpu"
