import os
import sys
from pathlib import Path

# make `repro` (src/) and `benchmarks` (repo root) importable regardless of
# how pytest was invoked; mirrors pyproject's tool.pytest.ini_options
_ROOT = Path(__file__).resolve().parents[1]
for p in (str(_ROOT / "src"), str(_ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# the suite runs on a >= 4-way logical CPU device pool so the sharded jit
# gates (tests/test_device_sharding.py, tests/harness.py cases) execute
# in-process; any inherited XLA_FLAGS (a forced device count of another
# size, say) is dropped first, then the pool is forced before jax
# initializes.  REPRO_DEVICES (the CI device matrix) can only
# widen the pool; it is deliberately NOT defaulted here, so the engine's
# device *default* stays 1 and sharding in tests is always explicit.
os.environ.pop("XLA_FLAGS", None)
from repro.runtime.device_config import (configure_host_devices,  # noqa: E402
                                         default_device_count)

configure_host_devices(max(4, default_device_count()))

# gate the optional `hypothesis` dependency: on bare images fall back to the
# deterministic shim so the property tests still collect and run
try:
    import hypothesis  # noqa: F401
except ImportError:  # pragma: no cover - depends on image
    from repro._compat import hypothesis_fallback
    hypothesis_fallback.install()

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
