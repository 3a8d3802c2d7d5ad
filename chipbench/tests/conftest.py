"""The chip benchmark's own CPU tests. They sit outside the repository's
``tests/`` (which ``pyproject.toml`` gives pytest), so run them by name:

    python -m pytest -q chipbench/tests

Four host devices stand in for a four-chip host; JAX runs on the CPU.
"""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=4").strip()

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
CHECKOUT = BENCH.parent
for p in (str(BENCH), str(CHECKOUT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
