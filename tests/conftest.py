"""Test harness configuration.

Tests run on the CPU backend with 8 virtual devices, so multi-chip sharding
logic is exercised without TPU hardware (SURVEY.md §4). Environment
variables select the platform (``JAX_PLATFORMS=cpu`` plus
``--xla_force_host_platform_device_count=8`` in ``XLA_FLAGS``); the
provisioning recipe is shared with the multi-chip dry run
(``__graft_entry__._provision_cpu_devices``), which also sets
``jax.config`` for the case where jax was imported before the variables
were set.

Nothing here reaches a chip. What needs one is asked of the chip's compiler
in ``tests/test_chip_compile.py`` (no chip attached) or run through the
chip tool by ``chip_smoke.py``.
"""

from __graft_entry__ import _provision_cpu_devices

_provision_cpu_devices(8)

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
