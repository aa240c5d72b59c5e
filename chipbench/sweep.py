"""Find the knee of an open-loop cell: the highest rate the system
sustains, by one sweep on the chip.

    python chipbench/sweep.py --workload <name> --seconds <s> --seed <n> --rates <r> [<r> ...]

Serves the cell's traffic at each rate in turn, in one process, with the
rate put in place of the mix file's, and prints one JSON line per rate:
the tokens/s offered and completed, the time-to-first-token percentiles,
and the requests still queued at the end. Past the knee the completed
rate stops following the offered one and the queue grows.
"""

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)

    from chipbench import harness
    from chipbench.traffic import Traffic

    cell = harness.load_cell(args.workload)
    harness.set_compile_cache()
    try:
        harness.device_info(cell.chips)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    for rate in args.rates:
        cell.mix["rate_per_s"] = rate
        run = harness.Run(cell, args.seed, args.seconds, False)
        run.setup()
        run.setup_s = 0.0
        run.window()
        offered = sum(d.max_new_tokens for d in Traffic(
            cell.mix, args.seed, args.seconds, run.model_cfg.vocab_size
        ).open_schedule()) / args.seconds
        m = run.client_values()
        print(json.dumps({
            "rate_per_s": rate, "offered_tok_s": offered,
            "output_tok_s": m["output_tok_s"],
            "ttft_p50_ms": m["ttft_p50_ms"], "ttft_p90_ms": m["ttft_p90_ms"],
            "itl_p99_ms": m["itl_p99_ms"],
            "finished": sum(r.done_at is not None for r in run.records),
            "sent": len(run.records), "queued_at_end": len(run.engine.queue),
            "live_at_end": len(run.engine.live_slots),
            "late_p50_ms": harness.percentile(run.late_s, 50) * 1e3}),
            flush=True)
        run.release_program()
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
