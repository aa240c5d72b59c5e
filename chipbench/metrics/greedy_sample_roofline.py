"""Share of its roofline that the Pallas greedy-sampling kernel reaches, in
%: the least time reading (slots x vocab) float32 logits needs at the
chip's peaks, over the kernel's device time, summed over its calls."""

from chipbench import flops


def read(run, peaks):
    calls = run.trace_data.sampling_ops()
    if not calls:
        return None
    ops, nbytes = flops.greedy_sample_cost(run.cell.mix["engine"]["slots"],
                                           run.cell.config["vocab_size"])
    least = flops.least_time_s(ops, nbytes, peaks["bf16_flop_per_s"],
                               peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(calls) / (sum(e.dur for e in calls) * 1e-9)
