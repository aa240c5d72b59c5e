"""Readings that set a cell's correctness limit, on the chip.

    python chipbench/control.py --workload <name> --seconds <s> --seeds <n> [<n> ...] [--fault <name>]

For each seed, in one process: set the cell up, serve its traffic for
``--seconds`` at the cell's own load, then read the widest logit gap of the
served tokens against the float32 reference (the program's reading) and
that of the tokens the fp8 reference puts first at the same positions (the
control's reading). Each reading goes through the decision a benchmark run
makes (``harness.decide``) against the configuration's limit: the program's
gives ``correct``, the control's ``control_correct``, which has to come out
false. The largest program reading is the lower end of the limit; the
smallest control reading is the upper end.

With ``--fault <name>`` (one of ``chipbench.faults.FAULTS``) the fault is
planted in the timed path after warm-up and only the program's reading is
taken: ``correct`` has to come out false.

One JSON line per seed, then a summary line. The benchmark's own runs do
not run this.
"""

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def reading(run, control: bool) -> dict:
    """The check of a run whose window has closed, decided as a benchmark
    run decides it, and for the control as well where asked."""
    from chipbench import harness

    run.release_program()
    t = time.perf_counter()
    found = run.compare(control=control)
    found.update(seed=run.seed, window_compiles=run.window_compiles,
                 compare_s=time.perf_counter() - t)
    found["correct"], _ = harness.decide(
        found["max_logit_gap"], run.limit, run.window_compiles,
        found["served_tokens"])
    if control:
        found["control_correct"], _ = harness.decide(
            found["control_max_logit_gap"], run.limit, run.window_compiles,
            found["served_tokens"])
    return found


def summary(workload: str, rows: list[dict], limit, fault) -> dict:
    out = {"workload": workload, "fault": fault, "limit": limit,
           "seeds": len(rows),
           "lower": max(r["max_logit_gap"] for r in rows),
           "all_correct": all(r["correct"] for r in rows)}
    if fault is None:
        out.update(upper=min(r["control_max_logit_gap"] for r in rows),
                   control_any_correct=any(r["control_correct"] for r in rows))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--fault", default=None)
    args = p.parse_args(argv)

    from chipbench import harness
    from chipbench.faults import FAULTS

    if args.fault is not None and args.fault not in FAULTS:
        p.error(f"--fault: one of {sorted(FAULTS)}")
    cell = harness.load_cell(args.workload)
    harness.set_compile_cache()
    try:
        harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    hook = FAULTS[args.fault] if args.fault else None
    rows = []
    for seed in args.seeds:
        run = harness.Run(cell, seed, args.seconds, False, engine_hook=hook)
        run.setup()
        run.window()
        rows.append(reading(run, control=hook is None))
        print(json.dumps(rows[-1]), flush=True)
        del run
        gc.collect()
    print(json.dumps(summary(args.workload, rows,
                             cell.config["correct"]["max_logit_gap"],
                             args.fault)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
