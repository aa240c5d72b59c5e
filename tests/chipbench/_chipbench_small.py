"""A benchmark cell at ``.reduced()`` widths, for tests on the CPU."""

import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import harness  # noqa: E402
from repro.configs import get  # noqa: E402

E2E = [("output_tok_s", "tokens/s"), ("ttft_p50_ms", "ms"), ("itl_p99_ms", "ms"),
       ("ttft_p50_ms.open_loop", "ms"), ("setup_s", "s")]


def small_config(name: str):
    """The configuration file of ``name`` at the repo's ``.reduced()``
    widths, the family's own among them, with the Pallas kernel in
    interpret mode, and the matching program configuration."""
    cfg = harness.load_json(harness.config_file(name))
    mc = get(cfg["repo_config"]).reduced()
    cfg.update(hidden_size=mc.d_model, intermediate_size=mc.d_ff,
               num_hidden_layers=mc.n_layers, num_attention_heads=mc.n_heads,
               num_key_value_heads=mc.n_kv_heads, head_dim=mc.head_dim_,
               vocab_size=mc.vocab_size)
    cfg.update({key: getattr(mc, attr) for key, attr
                in harness.load_family(cfg["family"]).WIDTHS.items()})
    cfg["engine"] = dict(cfg["engine"], sample_backend="pallas_interpret")
    mc = dataclasses.replace(mc, rope_theta=cfg.get("rope_theta", mc.rope_theta),
                             norm_eps=cfg["rms_norm_eps"], remat="none")
    return cfg, mc


def small_cell(config: str, mix: str, metrics=(), slots: int = 4):
    """A cell of ``config`` under ``mix``, cut to ``slots`` slots of 384
    rows."""
    cfg, mc = small_config(config)
    m = harness.load_json(harness.mix_file(mix))
    m["engine"] = {"slots": slots, "max_len": 384}
    m["prompt_tokens"]["max"] = min(m["prompt_tokens"]["max"], 160)
    m["prompt_tokens"]["min"] = min(m["prompt_tokens"]["min"], 16)
    m["prompt_tokens"]["median"] = min(m["prompt_tokens"]["median"], 48)
    m["output_tokens"]["max"] = min(m["output_tokens"]["max"], 48)
    m["output_tokens"]["min"] = min(m["output_tokens"]["min"], 16)
    if "median" in m["output_tokens"]:
        m["output_tokens"]["median"] = min(m["output_tokens"]["median"], 32)
    if m["loop"] == "open":
        m["rate_per_s"] = 4.0
    e2e = [{"name": n, "unit": u} for n, u in E2E]
    per_layer = [{"name": n, "unit": "x"} for n in metrics]
    return harness.Cell(f"{config}.{mix}", config, cfg, mix, m, 1, e2e, per_layer), mc
