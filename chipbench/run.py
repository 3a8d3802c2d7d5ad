"""The chip benchmark's command: one run of one cell.

    python3 chipbench/run.py --workload function.als --seed 7 --seconds 20 --trace 0

Run from the root of a checkout on a machine with the chips the cell asks
for. The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: each number compared with the
reference beside its limit). The last lines of standard error repeat the
checks. Without a TPU, or with fewer chips than the cell asks for, it
prints no result and exits 2. JAX's persistent compilation cache is kept
in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def configure_environment() -> None:
    """Compile cache inside the checkout, at a fixed path; every program
    cached, however quick to compile; the TPU runtime's logs under the
    run's own temporary directory; the program's sources importable."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        tempfile.gettempdir(), "chipbench_tpu_logs"))
    for p in (str(HERE), str(CHECKOUT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    configure_environment()
    import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T0)
    except harness.NoChip as e:
        harness.log(f"chipbench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
