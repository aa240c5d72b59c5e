"""CPU rehearsal of ``chip_smoke.py``: its serve-and-check routine at
reduced width with the sampling kernel in Pallas interpret mode, and its
refusal to report a result where JAX finds no TPU."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get

ROOT = Path(__file__).resolve().parents[1]


def test_serve_and_check_reduced_interpret(chip_smoke):
    r = chip_smoke.serve_and_check(get("qwen2-0.5b").reduced(),
                                   sample_backend="pallas_interpret")
    assert r["requests"] == chip_smoke.N_REQUESTS > chip_smoke.MAX_SLOTS
    assert r["tokens"] == chip_smoke.N_REQUESTS * chip_smoke.MAX_NEW_TOKENS
    assert r["window_compiles"] == 0
    assert r["setup_compiles"] > 0
    assert r["top1_agreement"] >= chip_smoke.MIN_TOP1
    # interpret mode lowers the kernel to plain HLO: no Mosaic custom call
    assert r["kernel_on_decode_path"] is False


@pytest.mark.parametrize("where", ["checkout", "script_alone"])
def test_main_fails_without_tpu(where, tmp_path):
    script = ROOT / "chip_smoke.py"
    if where == "script_alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
