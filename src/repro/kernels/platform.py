"""Where a Pallas kernel runs: Mosaic on the TPU, the interpreter on the CPU.

Every kernel entry point takes ``interpret: bool | None = None`` and resolves
it here.  ``None`` decides from the platform the call runs on, so a kernel on
the chip always lowers to Mosaic and can never fall back to the Python
interpreter; an explicit ``True``/``False`` is honoured as given (the
compile-rehearsal tests pass ``False`` to lower for a described TPU from a
CPU host).
"""
from __future__ import annotations

import jax

from repro.core import errors


def interpret_for_platform(platform: str) -> bool:
    """``True`` (interpret) on ``"cpu"``, ``False`` (Mosaic) on ``"tpu"``;
    any other platform has no Pallas lowering here and raises."""
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise ValueError(errors.ERR_PALLAS_PLATFORM.format(platform=platform))


def resolve_interpret(interpret: bool | None) -> bool:
    """The kernel's ``interpret`` flag: the caller's, else the platform's."""
    if interpret is not None:
        return bool(interpret)
    return interpret_for_platform(jax.default_backend())
