"""Test env: force an 8-device CPU mesh BEFORE any jax computation runs.

SURVEY.md §4: multi-device sharding/collective semantics are tested on a
virtual CPU mesh (`--xla_force_host_platform_device_count=8`).  The suite
never runs on an accelerator: the chip is driven by ``chip_smoke.py``
alone, one process per chip.  What the TPU's compiler says about the
kernels is checked without a chip in tests/test_tpu_compile.py.
"""

import os

# Hermetic tests: the framework's default-on persistent compile cache
# (utils/compile_cache.py) must never fill the checkout's .cache from the
# suite — slow mesh-test compiles would persist there and make later
# timings non-reproducible.  Cache-specific tests opt back in explicitly
# (tests/test_compile_cache.py).
os.environ.setdefault("TPP_COMPILE_CACHE", "0")

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
