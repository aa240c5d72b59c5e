"""Mean device idle time between consecutive decode programs, in ms: the
exposed set-up time of each decode launch. Gaps in which the client was
waiting for an arrival (``chipbench.wait``) are left out, and work of
other programs inside a gap is not idle."""


def read(run, peaks):
    t = run.trace_data
    runs = t.decode_programs()
    waits = [(e.start, e.end) for e in t.spans if e.name == "chipbench.wait"]
    idle = []
    for a, b in zip(runs, runs[1:]):
        if any(s < b.start and e > a.end for s, e in waits):
            continue
        idle.append((b.start - a.end) - t.busy_within(a.end, b.start))
    if not idle:
        return None
    return sum(idle) / len(idle) * 1e-6
