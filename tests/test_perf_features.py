"""Tests for the §Perf hillclimb features: chunked attention, shard_map MoE,
policy-aware sharding, gradient compression, and the HLO collective parser."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get
from repro.models import layers as L
from repro.models.model import Model


# ----------------------------------------------------------- chunked attn


@pytest.mark.parametrize("s,chunk", [(64, 16), (128, 32)])
@pytest.mark.parametrize("causal", [True, False])
def test_chunked_attention_matches_naive(s, chunk, causal):
    b, hq, hkv, d = 2, 4, 2, 16
    q = jax.random.normal(jax.random.key(1), (b, s, hq, d), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(jax.random.key(3), (b, s, hkv, d), jnp.float32)
    got = L.chunked_attention(q, k, v, hkv, causal=causal, chunk=chunk)
    scores = L.gqa_scores(q, k, hkv).astype(jnp.float32)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    want = L.gqa_combine(jax.nn.softmax(scores, -1).astype(q.dtype), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-4, atol=3e-4)


def test_attn_chunk_config_end_to_end():
    cfg = dataclasses.replace(get("qwen2-0.5b").reduced(), remat="none")
    cfg_c = dataclasses.replace(cfg, attn_chunk=8)
    m0, m1 = Model(cfg), Model(cfg_c)
    params = m0.init(jax.random.key(0))
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    }
    batch["labels"] = batch["tokens"]
    l0, _ = jax.jit(m0.forward)(params, batch)
    l1, _ = jax.jit(m1.forward)(params, batch)
    np.testing.assert_allclose(
        np.asarray(l0, np.float32), np.asarray(l1, np.float32), rtol=0.05, atol=0.1
    )


# ----------------------------------------------------------- shard_map MoE


def test_moe_shard_map_falls_back_without_mesh():
    cfg = dataclasses.replace(
        get("phi3.5-moe-42b-a6.6b").reduced(), remat="none", moe_impl="shard_map"
    )
    m = Model(cfg)
    p = m.init(jax.random.key(0))
    batch = {"tokens": jnp.ones((2, 8), jnp.int32), "labels": jnp.ones((2, 8), jnp.int32)}
    logits, _ = jax.jit(m.forward)(p, batch)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


@pytest.mark.skipif(jax.device_count() < 4, reason="needs >=4 devices")
def test_moe_shard_map_matches_gspmd():
    cfg0 = dataclasses.replace(
        get("phi3.5-moe-42b-a6.6b").reduced(), remat="none", capacity_factor=4.0
    )
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (4, 16), 0, cfg0.vocab_size)
    }
    batch["labels"] = batch["tokens"]
    m0 = Model(cfg0)
    p = m0.init(jax.random.key(0))
    with jax.set_mesh(mesh):
        l0, _ = jax.jit(m0.forward)(p, batch)
        m1 = Model(dataclasses.replace(cfg0, moe_impl="shard_map"))
        l1, _ = jax.jit(m1.forward)(p, batch)
    np.testing.assert_allclose(
        np.asarray(l0, np.float32), np.asarray(l1, np.float32), rtol=0.05, atol=0.1
    )


# ----------------------------------------------------- policy-aware specs


class FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


def test_pure_dp_replicates_everything():
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import _spec_for_param

    cfg = dataclasses.replace(get("qwen2-0.5b"), pure_dp=True)
    spec = _spec_for_param(FakeMesh(), ("layers", "attn", "wq"), (24, 896, 896), cfg)
    assert spec == P(None, None, None)


def test_tp_attention_off_replicates_attention_only():
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import _spec_for_param

    cfg = dataclasses.replace(get("qwen2-0.5b"), tp_attention=False)
    assert _spec_for_param(
        FakeMesh(), ("layers", "attn", "wk"), (24, 896, 128), cfg
    ) == P(None, None, None)
    # MLPs keep TP
    assert _spec_for_param(
        FakeMesh(), ("layers", "mlp", "wi"), (24, 896, 4864), cfg
    ) == P(None, None, "model")


def test_fsdp_adds_data_dim():
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import _spec_for_param

    cfg = dataclasses.replace(get("qwen2.5-32b"), fsdp=True)
    spec = _spec_for_param(FakeMesh(), ("layers", "mlp", "wi"), (64, 5120, 27648), cfg)
    assert "data" in spec and "model" in spec


def test_fsdp_skips_experts_under_shard_map():
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import _spec_for_param

    cfg = dataclasses.replace(
        get("kimi-k2-1t-a32b"), fsdp=True, moe_impl="shard_map"
    )
    spec = _spec_for_param(
        FakeMesh(), ("layers", "moe", "wi"), (61, 384, 7168, 2048), cfg
    )
    assert spec == P(None, "model", None, None)  # EP only: shard_map in_specs


# --------------------------------------------------------- HLO analysis


def test_collective_parser_result_shapes_and_groups():
    from repro.launch.hlo_analysis import collective_bytes

    hlo = """
ENTRY %main (p: f32[16]) -> f32[16] {
  %all-reduce.1 = f32[512,512]{1,0} all-reduce(%dot), replica_groups=[2,4]<=[8], to_apply=%add
  %all-gather.2 = bf16[16,4096,448]{1,0,2} all-gather(%x), replica_groups=[32,8]<=[256], dimensions={2}
  %collective-permute.3 = f32[16,4096,1,8]{3,2,1,0} collective-permute(%y), source_target_pairs={{0,1}}
}
"""
    st = collective_bytes(hlo)
    # all-reduce: 2 × 512·512·4 × (3/4)
    assert st.bytes_by_kind["all-reduce"] == int(2 * 512 * 512 * 4 * 3 / 4)
    # all-gather: result bytes × (7/8)
    assert st.bytes_by_kind["all-gather"] == int(16 * 4096 * 448 * 2 * 7 / 8)
    # collective-permute: result bytes (no groups)
    assert st.bytes_by_kind["collective-permute"] == 16 * 4096 * 8 * 4


def test_collective_parser_weights_while_bodies():
    from repro.launch.hlo_analysis import collective_bytes_weighted

    hlo = """
%cond (c: s32[]) -> pred[] {
  %bound = s32[] constant(24)
  %cmp = pred[] compare(%c, %bound), direction=LT
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %all-reduce.9 = f32[128,128]{1,0} all-reduce(%g), replica_groups=[1,4]<=[4], to_apply=%add
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %w = (s32[], f32[8]) while(%init), condition=%cond, body=%body
  %all-reduce.1 = f32[64]{0} all-reduce(%z), replica_groups=[1,4]<=[4], to_apply=%add
}
"""
    st = collective_bytes_weighted(hlo, default_trip=1)
    one_body = int(2 * 128 * 128 * 4 * 3 / 4)
    one_main = int(2 * 64 * 4 * 3 / 4)
    assert st.bytes_by_kind["all-reduce"] == 24 * one_body + one_main
    assert st.count_by_kind["all-reduce"] == 25


# ----------------------------------------------------- gradient compression


def test_grad_compression_bf16_still_trains():
    from repro.launch.steps import build_train_step
    from repro.optim import AdamW

    cfg = dataclasses.replace(
        get("qwen2-0.5b").reduced(), remat="none", n_layers=2,
        grad_compression="bf16",
    )
    model = Model(cfg)
    optimizer = AdamW()
    params = model.init(jax.random.key(0))
    opt = optimizer.init(params)
    batch = {"tokens": jnp.ones((2, 8), jnp.int32), "labels": jnp.ones((2, 8), jnp.int32)}
    step = jax.jit(build_train_step(model, optimizer))
    p2, o2, metrics = step(params, opt, batch)
    assert jnp.isfinite(metrics["loss"])
    assert jnp.isfinite(metrics["grad_norm"])
