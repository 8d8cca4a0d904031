"""Where the Pallas kernels run: compiled on the TPU, interpreted elsewhere.

Every kernel wrapper in this repo resolves its ``interpret`` argument
here, so one rule holds for all of them: off the TPU a kernel runs in
interpret mode (the CPU test substrate); on the TPU it runs compiled,
and a request for interpret mode there is an error rather than a silent
slow path.  A backend that fails to initialise raises from
``jax.devices()`` instead of being read as "not a TPU".
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when JAX's default device is a TPU."""
    return jax.devices()[0].platform == "tpu"


def resolve_interpret(interpret=None) -> bool:
    """``None`` -> interpret exactly when not on a TPU.  An explicit
    ``False`` is honoured anywhere (compiling for a described TPU from a
    CPU host); an explicit ``True`` on the TPU raises."""
    tpu = on_tpu()
    if interpret is None:
        return not tpu
    if interpret and tpu:
        raise ValueError("Pallas interpret mode is for runs off the TPU; "
                         "on the chip every kernel runs compiled")
    return bool(interpret)
