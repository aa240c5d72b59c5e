"""Host time in ``ServingEngine._admit`` per prompt token it admitted, in
ms: the ``chipbench.admit`` spans of the traced slice over the prompt
tokens of the requests those calls took into slots."""


def read(run, peaks):
    tokens = run.counters.admitted_prompt_tokens
    if not tokens:
        return None
    return run.trace_data.span_time("chipbench.admit") * 1e-6 / tokens
