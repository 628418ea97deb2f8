"""On-chip benchmark of the MESC serving path (``python3 -m bench.run``).

See ``PERF.md`` for the cells and metrics, and ``BENCHMARK.json`` for
the names that find each cell's configuration, traffic mix and metric
readers under this directory.
"""

import sys as _sys
from pathlib import Path as _Path

# the system under test, ``repro``, lives in the checkout's ``src``
_SRC = str(_Path(__file__).resolve().parents[1] / "src")
if _SRC not in _sys.path:
    _sys.path.insert(0, _SRC)
