"""Mean device time of one decode-and-sample program, in ms."""


def read(run, peaks):
    runs = run.trace_data.decode_programs()
    if not runs:
        return None
    return sum(r.dur for r in runs) * 1e-6 / len(runs)
