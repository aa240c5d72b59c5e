"""Model FLOPs of the live tokens decoded (masked slots left out; attention
over each token's own context), over the time of the decode programs
together with the idle gap each leaves before the next program, over the
chip's bf16 peak, in %."""


def read(run, peaks):
    runs = run.trace_data.decode_programs()
    if not runs or not run.counters.decode_flops:
        return None
    seconds = run.trace_data.until_next_program(runs) * 1e-9
    return 100.0 * run.counters.decode_flops / seconds / peaks["bf16_flop_per_s"]
