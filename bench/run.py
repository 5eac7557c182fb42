"""Run one benchmark cell on the accelerator this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress and the numbers it checked on standard error, and as its
last line on standard output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer ones), ``device``, with ``--trace 1`` ``breakdown``, then
``compiles_in_window`` and ``checks``. Without an accelerator, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
import sys
import time

T0 = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from bench.lib.harness import main
    main(t0=T0)
