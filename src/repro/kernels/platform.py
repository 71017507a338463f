"""Where the Pallas kernels run: compiled on a TPU, interpreted on a CPU.

Every launch wrapper resolves its ``interpret`` argument here, so the
choice is made from the backend in one place and nothing on the TPU
path can pick the interpreter by default.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None = None) -> bool:
    """``None`` -> interpret iff the default backend is the CPU.

    An explicit ``True``/``False`` is honoured as given.  Any backend
    other than ``tpu`` or ``cpu`` is refused: the kernels use Mosaic TPU
    features, and no other backend compiles them.
    """
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}")
