"""Test bootstrap: JAX on a virtual 8-device CPU mesh.

The suite runs on the CPU: sharding/parallelism tests run against
``--xla_force_host_platform_device_count=8`` CPU devices, the standard
JAX pattern for testing Mesh/pjit code paths, and kernels run under
the Pallas interpreter. A developer who exported ``JAX_PLATFORMS``
keeps their choice. What only the chip can show is ``chip_smoke.py``'s
job; what its compiler refuses is ``tests/test_tpu_compile.py``'s.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("GOFR_TELEMETRY", "false")

# cpu unless the developer exported a platform
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402

# Silent rank promotion ((B,) op (B, N) broadcasting by accident) is a
# classic source of wrong-but-plausible numerics in ops/models — make
# it a hard error under test. Production code is unaffected; this is a
# test-harness invariant, the static sibling of gofrlint's rules.
jax.config.update("jax_numpy_rank_promotion", "raise")

# Opt-in NaN tripwire: GOFR_DEBUG_NANS=1 makes every jitted op re-run
# eagerly and raise at the op that produced a NaN (jax_debug_nans) —
# too slow for CI default, invaluable when hunting a numeric bug.
if os.environ.get("GOFR_DEBUG_NANS", "").lower() in ("1", "true", "yes"):
    jax.config.update("jax_debug_nans", True)
