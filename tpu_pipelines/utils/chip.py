"""One process per chip: what THIS process already holds.

An accelerator belongs to one process at a time.  A runner process that has
trained or transformed on the chip holds it until it exits, so a child it
starts cannot open the chip (it fails, or waits forever), and a ``fork`` of
it inherits the runtime's threads' locks mid-flight.  Code that is about to
start a process asks here first.
"""

from __future__ import annotations

import sys
from typing import Optional


def held_accelerator() -> Optional[str]:
    """The platform (``"tpu"``, ``"gpu"``) once this process has initialised
    a non-CPU XLA backend, else None.  Asks only what is already loaded:
    never imports jax and never initialises a backend itself."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    platform = jax.default_backend()
    return None if platform == "cpu" else platform
