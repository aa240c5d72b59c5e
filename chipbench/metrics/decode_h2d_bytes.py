"""Mean host-to-device bytes a decode launch copies: the engine's count
(``ServingEngine.h2d["decode"]``) of the numpy leaves its decode launches
passed through ``jnp.asarray``, over the launches, since the engine was
made. ``None`` for an engine that keeps no such count."""


def read(run, peaks):
    counts = getattr(getattr(run, "engine", None), "h2d", None)
    decode = counts.get("decode") if isinstance(counts, dict) else None
    if decode is None or not decode.launches:
        return None
    return decode.bytes / decode.launches
