"""Model FLOPs of the prompt tokens prefilled (causal attention over the
earlier prompt tokens; no output head), over the time of the prefill
programs together with the idle gap each leaves before the next program,
over the chip's bf16 peak, in %."""


def read(run, peaks):
    runs = run.trace_data.prefill_programs()
    if not runs or not run.counters.prefill_flops:
        return None
    seconds = run.trace_data.until_next_program(runs) * 1e-9
    return 100.0 * run.counters.prefill_flops / seconds / peaks["bf16_flop_per_s"]
