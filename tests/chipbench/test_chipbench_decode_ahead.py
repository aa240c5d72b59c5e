"""The ``decode_ahead_share`` reader of the engine's count of decode
launches staged ahead."""

import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import harness  # noqa: E402
from repro.serving.engine import H2DCount  # noqa: E402

PEAKS = harness.load_json(harness.HERE / "peaks.json")["TPU v5 lite"]


def test_decode_ahead_share_reads_the_engines_count():
    read = harness.load_reader("decode_ahead_share")
    engine = SimpleNamespace(ahead=SimpleNamespace(launches=96, dropped_rows=1),
                             h2d={"decode": H2DCount(launches=100, copies=400,
                                                     bytes=8000)})
    assert read(SimpleNamespace(engine=engine), PEAKS) == 0.96
    # no decode launch yet: nothing to read
    engine.h2d["decode"] = H2DCount()
    assert read(SimpleNamespace(engine=engine), PEAKS) is None
    # an engine that stages nothing ahead (the parent's), or none at all
    parent = SimpleNamespace(h2d={"decode": H2DCount(launches=3, copies=12, bytes=240)})
    assert read(SimpleNamespace(engine=parent), PEAKS) is None
    assert read(SimpleNamespace(engine=SimpleNamespace()), PEAKS) is None
    assert read(SimpleNamespace(engine=None), PEAKS) is None
    assert read(SimpleNamespace(), PEAKS) is None
