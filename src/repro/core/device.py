"""The platform a call runs on, read at call time.

Code that takes a different form on the chip than on the host (Pallas
interpret mode, the per-host sums' contraction) reads the platform here,
so one helper decides both and a test can fix it in one place.
"""
from __future__ import annotations

import jax


def call_platform() -> str:
    """Platform of the device the call runs on: the device pinned by
    `jax.default_device(...)` if one is, otherwise the default backend
    ("cpu", "tpu", "gpu").  Read at call time, not import time, so late
    backend selection (jax.config, distributed init) is honoured."""
    pinned = jax.config.jax_default_device
    if pinned is None:
        return jax.default_backend()
    if isinstance(pinned, str):
        return pinned
    return pinned.platform
