"""
Repo-root pytest bootstrap: force the XLA-CPU backend with 8 virtual devices
(the "fake TPU" test backend; SURVEY.md §4) before any jax computation runs.

The override goes through jax.config as well as the environment: the unit
tests need the eight-device CPU mesh whatever ``JAX_PLATFORMS`` says, and on a
machine with a chip they must not take it (a chip belongs to one process at a
time; ``chip_smoke.py`` is what runs there).
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
