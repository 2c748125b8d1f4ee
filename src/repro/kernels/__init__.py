"""Pallas kernels for the row-centric hot spots (``conv2d_rows``,
``swa_attention``, ``ssd_chunk``), their lax references (``ref``) and the
jitted public wrappers (``ops``).

Interpret mode is decided by the platform: the Pallas interpreter runs on
every backend except a TPU, where the kernels always compile.  An explicit
``interpret=True|False`` (e.g. a plan's ``KernelSpec.interpret``) wins.
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(flag: Optional[bool] = None) -> bool:
    """Tri-state ``interpret`` -> the concrete ``pallas_call`` flag:
    ``None`` means interpret everywhere but on a TPU."""
    if flag is None:
        return jax.default_backend() != "tpu"
    return bool(flag)
