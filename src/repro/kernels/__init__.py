"""Pallas kernels for the serving hot path, their pure-jnp oracles
(``ref``) and the wrappers the model calls (``ops``)."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(platform: Optional[str] = None) -> bool:
    """Whether Pallas kernels run in interpret mode on ``platform`` (default:
    JAX's backend).  Only the CPU interprets — that is where the tests run;
    a TPU compiles them with Mosaic.  Any other platform is refused rather
    than silently interpreted."""
    platform = platform or jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the CPU; "
        f"platform {platform!r} is neither"
    )


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """A kernel's ``interpret`` argument: an explicit value wins (a compile
    rehearsal for a described TPU passes False on a CPU host), None asks
    the platform."""
    return interpret_mode() if interpret is None else interpret
