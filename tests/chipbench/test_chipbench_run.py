"""``chipbench/run.py`` as the benchmark's command runs it, and the
harness's discovery of configurations, traffic mixes and metric readers by
name."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2**31 + 7),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    assert proc.returncode != 0
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    assert '"correct"' not in last


def test_run_refuses_without_a_tpu():
    proc = _run(ROOT)
    _no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's paths
    lacks the program: no result."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.NoChip):
        harness.load_peaks("TPU v0 imaginary")
    assert harness.load_peaks("TPU v5 lite")["bf16_flop_per_s"] == 197e12


def test_every_name_in_the_benchmark_resolves_to_its_file():
    for c in BENCH["configs"]:
        assert harness.config_file(c["name"]) == ROOT / c["file"]
        assert (ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.config == harness.load_json(harness.config_file(w["config"]))
        assert cell.mix["engine"]["max_len"] > 0
        assert harness.load_reference(cell.config["family"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
    for m in BENCH["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def test_program_config_follows_the_file():
    for c in BENCH["configs"]:
        cfg = harness.load_json(ROOT / c["file"])
        mc = harness.program_config(cfg)
        assert mc.rope_theta == cfg["rope_theta"]
        assert mc.norm_eps == cfg["rms_norm_eps"]
        bad = dict(cfg, hidden_size=cfg["hidden_size"] + 1)
        with pytest.raises(ValueError):
            harness.program_config(bad)


def test_new_files_are_enough_for_a_new_cell_and_metric(tmp_path, monkeypatch):
    """A later PR adds a cell and a metric by adding a configuration file, a
    traffic file and a reader, and naming them in BENCHMARK.json."""
    here = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cfg = harness.load_json(here / "configs" / "qwen2-0.5b.json")
    (here / "configs" / "new-model.json").write_text(json.dumps(cfg))
    mix = harness.load_json(here / "traffic" / "longgen.json")
    (here / "traffic" / "new-mix.json").write_text(json.dumps(dict(mix, clients=3)))
    (here / "metrics" / "new_metric.py").write_text(
        "def read(run, peaks):\n    return 42.0\n")
    bench = dict(BENCH)
    bench["workloads"] = [{"name": "new-model.new-mix", "config": "new-model",
                           "traffic": "new-mix", "chips": 1, "why": "test"}]
    bench["per_layer"] = [{"name": "new_metric", "unit": "ms", "better": "lower",
                           "source": "device_trace", "layer": "x",
                           "moves": "output_tok_s"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "HERE", here)
    cell = harness.load_cell("new-model.new-mix", tmp_path / "BENCHMARK.json")
    assert cell.mix["clients"] == 3 and cell.config_name == "new-model"
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert harness.load_reader("new_metric")(None, None) == 42.0
