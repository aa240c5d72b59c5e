"""``chipbench/run.py`` as the benchmark's command runs it, and the
harness's discovery of configurations, traffic mixes and metric readers by
name."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from _chipbench_small import small_cell  # noqa: E402
from chipbench import harness  # noqa: E402
from repro.configs import get  # noqa: E402
from repro.models.model import Model  # noqa: E402
from repro.serving import Request  # noqa: E402

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


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """A copy of ``chipbench/`` that the harness reads in the repo's place,
    and a function that writes a BENCHMARK.json beside it with the given
    entries changed, and returns its path."""
    here = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    monkeypatch.setattr(harness, "HERE", here)

    def write_bench(**changes) -> Path:
        path = tmp_path / "BENCHMARK.json"
        path.write_text(json.dumps(dict(BENCH, **changes)))
        return path

    return here, write_bench


def test_new_files_are_enough_for_a_new_cell_and_metric(bench_copy):
    """A later PR adds a cell and a metric by adding a configuration file, a
    traffic file and a reader, and naming them in BENCHMARK.json."""
    here, write_bench = bench_copy
    cfg = harness.load_json(here / "configs" / "qwen2-0.5b.json")
    (here / "configs" / "new-model.json").write_text(json.dumps(cfg))
    mix = harness.load_json(here / "traffic" / "longgen.json")
    (here / "traffic" / "new-mix.json").write_text(json.dumps(dict(mix, clients=3)))
    (here / "metrics" / "new_metric.py").write_text(
        "def read(run, peaks):\n    return 42.0\n")
    bench = write_bench(
        workloads=[{"name": "new-model.new-mix", "config": "new-model",
                    "traffic": "new-mix", "chips": 1, "why": "test"}],
        per_layer=[{"name": "new_metric", "unit": "ms", "better": "lower",
                    "source": "device_trace", "layer": "x",
                    "moves": "output_tok_s"}])
    cell = harness.load_cell("new-model.new-mix", bench)
    assert cell.mix["clients"] == 3 and cell.config_name == "new-model"
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert harness.load_reader("new_metric")(None, None) == 42.0
    # the metric split by cell is read by the same reader
    assert harness.load_reader("new_metric.split")(None, None) == 42.0


# A family file as a later PR would add it, for a family the harness has
# no code of: draws of the family's own leaves by laws of its own, its
# widths, FLOP counts this test knows, and a reference class (a stub: the
# run below is not checked).
FAMILY_FILE = """
import jax
import jax.numpy as jnp

DRAWN = set()
CALLS = {"prefill": [], "decode": []}
PREFILL_PER_TOKEN = 1_000_003
DECODE_PER_TOKEN = 7_919
WIDTHS = @WIDTHS@
ROTARY = @ROTARY@


def _matrix(key, shape, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(shape[-2] ** -0.5, dtype)


def _a_log(key, shape, dtype):  # A = -exp(a_log) in [-16, -1]
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias(key, shape, dtype):  # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _d_skip(key, shape, dtype):
    return 1 + 0.1 * jax.random.normal(key, shape, dtype)


LAWS = {"router": _matrix, "in_proj": _matrix, "conv_w": _matrix, "x_proj": _matrix,
        "out_proj": _matrix, "a_log": _a_log, "dt_bias": _dt_bias, "d_skip": _d_skip}


def _named(name):
    def draw(key, shape, dtype):
        DRAWN.add(name)
        return LAWS[name](key, shape, dtype)
    return draw


DRAWS = {name: _named(name) for name in LAWS}


def prefill_flops(cfg, prompt_len):
    CALLS["prefill"].append(prompt_len)
    return PREFILL_PER_TOKEN * prompt_len


def decode_flops(cfg, contexts):
    contexts = list(contexts)
    CALLS["decode"].append(contexts)
    return DECODE_PER_TOKEN * len(contexts)


class @CLASS@Reference:
    def __init__(self, cfg, params, precision="float32"):
        self.cfg, self.params = cfg, params
"""

FAMILIES = {
    "moe": ("phi3.5-moe-42b-a6.6b", {"num_local_experts": "n_experts",
                                     "num_experts_per_tok": "experts_per_token"},
            {"router"}),
    "hybrid": ("jamba-1.5-large-398b",
               {"attn_layer_period": "attn_period", "expert_layer_period": "moe_period",
                "num_experts": "n_experts", "num_experts_per_tok": "experts_per_token",
                "mamba_d_state": "ssm_state_dim", "mamba_d_conv": "ssm_conv_dim",
                "mamba_expand": "ssm_expand"},
               {"router", "in_proj", "conv_w", "x_proj", "out_proj", "a_log",
                "dt_bias", "d_skip"}),
}


def _family_config(repo_id: str, widths: dict) -> dict:
    """A configuration file of the repo's entry ``repo_id`` at its own
    widths; it states no rotary base."""
    mc = get(repo_id)
    cfg = {"repo_config": repo_id, "family": mc.family,
           "hidden_size": mc.d_model, "intermediate_size": mc.d_ff,
           "num_hidden_layers": mc.n_layers, "num_attention_heads": mc.n_heads,
           "num_key_value_heads": mc.n_kv_heads, "head_dim": mc.head_dim_,
           "vocab_size": mc.vocab_size, "rms_norm_eps": 1e-5,
           "tie_word_embeddings": mc.tie_embeddings, "qkv_bias": mc.qkv_bias,
           "engine": {"sampling": "fused", "sample_backend": "pallas",
                      "prefill_chunk": 8},
           "correct": {"max_logit_gap": 0.15}}
    cfg.update({key: getattr(mc, attr) for key, attr in widths.items()})
    return cfg


def _write_family(here: Path, family: str, widths: dict, rotary: bool,
                  text: str = FAMILY_FILE):
    (here / "reference" / f"{family}.py").write_text(
        text.replace("@WIDTHS@", repr(widths)).replace("@ROTARY@", repr(rotary))
        .replace("@CLASS@", family.capitalize()))


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_new_files_are_enough_for_a_new_family(family, bench_copy):
    """A later PR adds a cell of a family the harness has no code of by
    adding its family file and a configuration file: weights, width checks
    and FLOP counts come from the family file, through the normal harness."""
    repo_id, widths, own_leaves = FAMILIES[family]
    here, write_bench = bench_copy
    _write_family(here, family, widths, rotary=False)
    cfg = _family_config(repo_id, widths)
    (here / "configs" / f"{repo_id}.json").write_text(json.dumps(cfg))
    bench = write_bench(workloads=[{"name": f"{repo_id}.longgen", "config": repo_id,
                                    "traffic": "longgen", "chips": 1, "why": "test"}])
    fam = harness.load_family(family)
    assert harness.load_reference(family).__name__ == f"{family.capitalize()}Reference"

    # the widths: the family's own are checked, and a file with no rotary
    # base keeps the repo entry's
    cell = harness.load_cell(f"{repo_id}.longgen", bench)
    mc = harness.program_config(cell.config)
    assert mc.rope_theta == get(repo_id).rope_theta
    for key in widths:
        with pytest.raises(ValueError):
            harness.program_config(dict(cfg, **{key: cfg[key] + 1}))

    # set-up and a window at .reduced() widths: every leaf is drawn
    cell, mc = small_cell(repo_id, "longgen")
    run = harness.Run(cell, 2**31 + 21, 1.0, False, model_cfg=mc)
    run.setup()
    assert fam.DRAWN == own_leaves
    layout = [(s.shape, s.dtype) for s in jax.tree.leaves(
        jax.eval_shape(Model(mc).init, jax.random.key(0)))]
    assert [(x.shape, x.dtype) for x in jax.tree.leaves(run.params)] == layout
    if family == "hybrid":
        a_log = np.asarray(run.params["groups"]["mamba"]["a_log"])
        assert a_log.min() >= 0 and a_log.max() <= np.log(16) + 1e-6
    run.window()
    assert run.records

    # the counters take the family's FLOPs, not the dense count
    eng = run.engine
    eng.run_until_done()
    eng.submit(Request(uid=-2, prompt=list(range(1, 20)), max_new_tokens=4))
    fam.CALLS["prefill"].clear()
    fam.CALLS["decode"].clear()
    unwrap = run._wrap()
    eng.step()
    eng.step()
    unwrap()
    c = run.counters
    assert fam.CALLS == {"prefill": [19], "decode": [[19], [20]]}
    assert c.admitted_prompt_tokens == 19 and c.decode_launches == 2
    assert c.prefill_flops == fam.PREFILL_PER_TOKEN * 19
    assert c.decode_flops == fam.DECODE_PER_TOKEN * 2


def test_a_family_file_without_its_flops_is_an_error(bench_copy):
    here, write_bench = bench_copy
    _write_family(here, "moe", {}, rotary=True,
                  text=FAMILY_FILE.replace("def decode_flops", "def _decode"))
    cfg = dict(_family_config("phi3.5-moe-42b-a6.6b", {}), rope_theta=10000.0)
    (here / "configs" / "moe-model.json").write_text(json.dumps(cfg))
    bench = write_bench(workloads=[{"name": "moe-model.longgen", "config": "moe-model",
                                    "traffic": "longgen", "chips": 1, "why": "test"}])
    with pytest.raises(ValueError, match="decode_flops"):
        harness.load_cell("moe-model.longgen", bench)


def test_program_config_keeps_the_repo_rotary_base_where_the_file_states_none(bench_copy):
    """Where the family's attention does not rotate, a file without a
    rotary base runs the repo entry's; where it rotates, leaving the base
    out is an error, so that no file runs a rotation it does not state."""
    here, _ = bench_copy
    repo_id, widths, _ = FAMILIES["hybrid"]
    _write_family(here, "hybrid", widths, rotary=False)
    cfg = _family_config(repo_id, widths)
    mc = harness.program_config(cfg)
    assert mc.rope_theta == get(repo_id).rope_theta
    assert mc.norm_eps == cfg["rms_norm_eps"]
    assert harness.program_config(dict(cfg, rope_theta=5e5)).rope_theta == 5e5
    dense = harness.load_json(harness.config_file("qwen2-0.5b"))
    del dense["rope_theta"]
    with pytest.raises(ValueError, match="rope_theta"):
        harness.program_config(dense)
