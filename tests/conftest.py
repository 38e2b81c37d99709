"""Test configuration: force an 8-device virtual CPU platform.

Multi-chip hardware is not available in CI; sharding tests run on a virtual
8-device CPU mesh (jax.sharding semantics are identical; only perf differs).
The tier-1 command sets ``JAX_PLATFORMS=cpu``; the shared
fantoch_tpu.hostenv.force_cpu_platform helper sets the same switch (for
the subprocesses tests start) plus the virtual device count, before any
backend is initialized.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from fantoch_tpu.hostenv import force_cpu_platform

force_cpu_platform(n_devices=8)

from fantoch_tpu.core.compile_cache import ensure_compile_cache  # noqa: E402

# persistent XLA compile cache (entries are keyed by topology+program so
# the 8-device test mesh never collides with 1-device programs):
# mesh-step compiles dominate suite wall time and repeat identically
# across runs
ensure_compile_cache()
