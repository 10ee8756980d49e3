import os
import sys

# ensure src/ is importable when pytest is run without PYTHONPATH
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# NOTE: do NOT set XLA_FLAGS / host device count here — smoke tests and
# benches must see 1 CPU device; only the SPMD checks force more, each in
# its own process.

# runtime enforcement layer: @pytest.mark.runtime_guard / sync_free markers
# and the `runtime_guard` fixture (see repro.analysis.pytest_plugin)
from repro.analysis.pytest_plugin import *  # noqa: E402,F401,F403
