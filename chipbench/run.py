"""Run one cell of the on-chip serving benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json`` at the checkout's root, sets up the
program on the chip, drives its traffic for ``--seconds``, checks what was
served against the float32 reference, and prints one JSON object as the
last line of standard output. With ``--trace 0`` its metrics are the
cell's end-to-end metrics; with ``--trace 1`` the per-layer metrics read
from a profiler trace of a slice of the window. Without a TPU, with fewer
chips than the cell asks for, or with a device kind that
``chipbench/peaks.json`` lacks, it exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()  # set-up is timed from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    # libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
    # nothing outside its checkout and the directories it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import harness

    cell = harness.load_cell(args.workload)
    harness.set_compile_cache()
    try:
        result = harness.execute(cell, args.seed, args.seconds,
                                 bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
