"""Device time of the prefill-chunk programs per prompt token they
advanced, in ms (the last prompt token of each request goes through the
first decode step and is not counted)."""


def read(run, peaks):
    runs = run.trace_data.prefill_programs()
    tokens = run.counters.prefill_tokens
    if not runs or not tokens:
        return None
    return sum(r.dur for r in runs) * 1e-6 / tokens
