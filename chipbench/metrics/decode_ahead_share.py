"""Share of decode launches staged ahead of the read of the launch before
them: the engine's count (``ServingEngine.ahead``) over its decode
launches (``ServingEngine.h2d["decode"]``), since the engine was made.
``None`` for an engine that keeps no such count."""


def read(run, peaks):
    engine = getattr(run, "engine", None)
    ahead = getattr(engine, "ahead", None)
    counts = getattr(engine, "h2d", None)
    decode = counts.get("decode") if isinstance(counts, dict) else None
    if ahead is None or decode is None or not decode.launches:
        return None
    return ahead.launches / decode.launches
