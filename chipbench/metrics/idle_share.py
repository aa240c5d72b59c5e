"""Share of the traced slice in which no operation ran on the device, in
%: one minus the union of the device's operation intervals."""


def read(run, peaks):
    t = run.trace_data
    if not t.ops or not t.window_ns:
        return None
    return 100.0 * (1.0 - t.busy_ns() / t.window_ns)
