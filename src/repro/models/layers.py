"""Neural building blocks shared by every architecture in the pool.

Pure-function style: each block is ``init(key, cfg) -> params`` plus
``apply(params, x, ...) -> y`` over plain dict pytrees, so layers stack via
``jax.lax.scan`` (small HLO even for 72-layer trunks) and shard via
``NamedSharding`` trees computed from param paths (``repro.distributed``).

Compute dtype is bf16 with fp32 normalization/softmax/logits; this matches
TPU MXU-native mixed precision.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig

Array = jax.Array
COMPUTE_DTYPE = jnp.bfloat16


def _dense_init(key, shape, scale_axis: int = 0):
    scale = 1.0 / jnp.sqrt(jnp.asarray(shape[scale_axis], jnp.float32))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(COMPUTE_DTYPE)


# --------------------------------------------------------------------------
# Norms & positional encodings
# --------------------------------------------------------------------------


def rms_norm(x: Array, weight: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * weight).astype(x.dtype)


def layer_norm(x: Array, weight: Array, bias: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * weight + bias).astype(x.dtype)


def rope(x: Array, positions: Array, theta: float = 10_000.0) -> Array:
    """Rotary embedding. x: (B, S, H, D); positions: (B, S) or (S,)."""
    d = x.shape[-1]
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, d, 2, dtype=jnp.float32) / d
    )  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[..., None, :]  # (B, S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Attention (MHA / GQA, optional QKV bias, optional KV cache)
# --------------------------------------------------------------------------


def attention_init(key, cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.head_dim_
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    params = {
        "wq": _dense_init(ks[0], (d, hq * hd)),
        "wk": _dense_init(ks[1], (d, hkv * hd)),
        "wv": _dense_init(ks[2], (d, hkv * hd)),
        "wo": _dense_init(ks[3], (hq * hd, d)),
    }
    if cfg.qkv_bias:
        params["bq"] = jnp.zeros((hq * hd,), COMPUTE_DTYPE)
        params["bk"] = jnp.zeros((hkv * hd,), COMPUTE_DTYPE)
        params["bv"] = jnp.zeros((hkv * hd,), COMPUTE_DTYPE)
    return params


def _split_heads(x: Array, n_heads: int) -> Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1)


def gqa_scores(q: Array, k: Array, n_kv: int) -> Array:
    """q: (B,S,Hq,D), k: (B,T,Hkv,D) -> scores (B,Hkv,G,S,T)."""
    b, s, hq, d = q.shape
    g = hq // n_kv
    qg = q.reshape(b, s, n_kv, g, d)
    return jnp.einsum("bsngd,btnd->bngst", qg, k) / jnp.sqrt(float(d))


def gqa_combine(probs: Array, v: Array) -> Array:
    """probs: (B,Hkv,G,S,T), v: (B,T,Hkv,D) -> (B,S,Hq,D)."""
    b, n, g, s, _t = probs.shape
    out = jnp.einsum("bngst,btnd->bsngd", probs, v)
    return out.reshape(b, s, n * g, -1)


def chunked_attention(
    q: Array, k: Array, v: Array, n_kv: int, *, causal: bool, chunk: int
) -> Array:
    """Online-softmax attention over KV chunks (flash-style, XLA path).

    Never materializes the S×T score matrix — the jnp twin of the Pallas
    flash kernel, used when a cell is memory-bound on the naive einsum path.
    q: (B,S,Hq,D); k,v: (B,T,Hkv,D) -> (B,S,Hq,D).
    """
    b, s, hq, d = q.shape
    t = k.shape[1]
    assert t % chunk == 0, (t, chunk)
    g = hq // n_kv
    qg = q.reshape(b, s, n_kv, g, d).astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    nchunks = t // chunk
    kc = jnp.moveaxis(k.reshape(b, nchunks, chunk, n_kv, d), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nchunks, chunk, n_kv, d), 1, 0)
    rows = jnp.arange(s)

    def body(carry, inputs):
        m, l, acc = carry
        kj, vj, j = inputs
        sc = jnp.einsum(
            "bsngd,btnd->bngst", qg, kj.astype(jnp.float32)
        ) * scale  # (B,n,g,S,chunk)
        if causal:
            cols = j * chunk + jnp.arange(chunk)
            mask = rows[:, None] >= cols[None, :]
            sc = jnp.where(mask[None, None, None], sc, -1e30)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bngst,btnd->bngsd", p, vj.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((b, n_kv, g, s, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, s, 1), jnp.float32)
    a0 = jnp.zeros((b, n_kv, g, s, d), jnp.float32)
    (m, l, acc), _ = lax.scan(
        body, (m0, l0, a0), (kc, vc, jnp.arange(nchunks))
    )
    out = (acc / l).astype(q.dtype)  # (B,n,g,S,D)
    return jnp.moveaxis(out, 3, 1).reshape(b, s, hq, d)


def quantize_kv(x: Array) -> tuple[Array, Array]:
    """Per-(token, head) absmax int8 quantization. x: (B,S,H,D)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_kv(q: Array, scale: Array) -> Array:
    return q.astype(COMPUTE_DTYPE) * scale.astype(COMPUTE_DTYPE)


def _project_qkv(params: dict, cfg: ModelConfig, x: Array, src: Array):
    """Query heads from ``x``, key/value heads from ``src``: (B,S,H,D) each."""
    q = x @ params["wq"]
    k = src @ params["wk"]
    v = src @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    return (_split_heads(q, cfg.n_heads), _split_heads(k, cfg.n_kv_heads),
            _split_heads(v, cfg.n_kv_heads))


def attention_apply(
    params: dict,
    cfg: ModelConfig,
    x: Array,
    positions: Array,
    *,
    causal: bool = True,
    use_rope: bool = True,
    kv: Array | None = None,  # cross-attention source (B, T, d)
    cache: dict | None = None,  # {"k","v": (B, S_max, Hkv, D)} decode cache
    cache_pos: Array | None = None,  # scalar (or (B,) vector) decode position
) -> tuple[Array, dict | None]:
    hkv = cfg.n_kv_heads
    q, k, v = _project_qkv(params, cfg, x, x if kv is None else kv)

    per_slot = cache_pos is not None and getattr(cache_pos, "ndim", 0) == 1

    if use_rope and kv is None:
        q = rope(q, positions, cfg.rope_theta)
        if cache_pos is None:
            k = rope(k, positions, cfg.rope_theta)
        elif per_slot:  # continuous batching: each row at its own position
            k = rope(k, jnp.broadcast_to(cache_pos[:, None], k.shape[:2]), cfg.rope_theta)
        else:
            k = rope(k, jnp.full(k.shape[:2], cache_pos), cfg.rope_theta)

    new_cache = None
    if cache is not None:
        if cache_pos is not None:  # single-token decode: append to the cache
            quant = "k_scale" in cache
            if quant:
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
            else:
                kq, ks, vq, vs = k, None, v, None

            def upd(buf, val):
                if per_slot:  # scatter one row per sequence at its position
                    b = val.shape[0]
                    return buf.at[jnp.arange(b), cache_pos].set(
                        val[:, 0].astype(buf.dtype)
                    )
                return lax.dynamic_update_slice_in_dim(
                    buf, val.astype(buf.dtype), cache_pos, axis=1
                )

            new_cache = {"k": upd(cache["k"], kq), "v": upd(cache["v"], vq)}
            if quant:
                new_cache["k_scale"] = upd(cache["k_scale"], ks)
                new_cache["v_scale"] = upd(cache["v_scale"], vs)
                k = dequantize_kv(new_cache["k"], new_cache["k_scale"])
                v = dequantize_kv(new_cache["v"], new_cache["v_scale"])
            else:
                k, v = new_cache["k"], new_cache["v"]
        else:  # prefill: cache is returned filled with this call's K/V
            new_cache = {"k": k, "v": v}

    b, s = x.shape[:2]
    if cfg.attn_chunk and cache is None and q.shape[1] > cfg.attn_chunk:
        # adjust to the largest divisor of T not exceeding the request
        # (e.g. S=4672 with chunk 512 -> 292; S=1500 -> 500)
        t_len = k.shape[1]
        chunk = next(c for c in range(min(cfg.attn_chunk, t_len), 0, -1) if t_len % c == 0)
        if chunk > 1:
            # flash-style online softmax: no S×T score materialization
            out = chunked_attention(
                q, k, v, hkv, causal=causal and kv is None, chunk=chunk
            )
            return out.reshape(b, s, -1) @ params["wo"], new_cache

    scores = gqa_scores(q, k, hkv).astype(jnp.float32)
    t = k.shape[1]
    if cache is not None and cache_pos is not None:
        # mask out cache slots past the current position
        if per_slot:
            valid = jnp.arange(t)[None, :] <= cache_pos[:, None]  # (B, T)
            scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
        else:
            valid = jnp.arange(t) <= cache_pos
            scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    elif causal and kv is None:
        s_q = q.shape[1]
        mask = jnp.tril(jnp.ones((s_q, t), bool))
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(COMPUTE_DTYPE)
    out = gqa_combine(probs, v)
    return out.reshape(b, s, -1) @ params["wo"], new_cache


def attention_chunk(
    params: dict,
    cfg: ModelConfig,
    x: Array,
    positions: Array,
    past: dict,
) -> tuple[Array, dict]:
    """Causal self-attention of one sequence's chunk of T new tokens against
    its cached rows.

    x: (1, T, d); positions: (T,) int32, consecutive, token i at
    ``positions[i]``; past: the sequence's cache rows of this layer,
    ``{"k","v"[,"k_scale","v_scale"]}`` each (S_max, Hkv, D|1). Query i sees
    the cached rows before ``positions[0]`` and the chunk's tokens up to
    itself. The chunk's K/V go through the cache's dtype (int8 quantized per
    token, as decode stores them) before they are attended to, so each token
    sees the keys decode would see. Returns ``(out (1, T, d), rows)`` with
    ``rows`` the chunk's K/V in the cache's layout, (T, Hkv, D|1) each, for
    the caller to write at ``positions``."""
    t = x.shape[1]
    q, k, v = _project_qkv(params, cfg, x, x)
    q = rope(q, positions[None], cfg.rope_theta)
    k = rope(k, positions[None], cfg.rope_theta)
    if "k_scale" in past:
        (k, k_scale), (v, v_scale) = quantize_kv(k), quantize_kv(v)
        rows = {"k": k[0], "v": v[0], "k_scale": k_scale[0], "v_scale": v_scale[0]}
        keys = jnp.concatenate([dequantize_kv(past["k"], past["k_scale"]),
                                dequantize_kv(k[0], k_scale[0])])
        values = jnp.concatenate([dequantize_kv(past["v"], past["v_scale"]),
                                  dequantize_kv(v[0], v_scale[0])])
    else:
        rows = {"k": k[0].astype(past["k"].dtype), "v": v[0].astype(past["v"].dtype)}
        keys = jnp.concatenate([past["k"], rows["k"]])
        values = jnp.concatenate([past["v"], rows["v"]])
    s = past["k"].shape[0]
    # cached rows before the chunk, then the chunk's own tokens up to i
    visible = jnp.concatenate([
        jnp.broadcast_to(jnp.arange(s) < positions[0], (t, s)),
        jnp.tril(jnp.ones((t, t), bool)),
    ], axis=1)  # (T, S + T)
    scores = gqa_scores(q, keys[None], cfg.n_kv_heads).astype(jnp.float32)
    scores = jnp.where(visible[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(COMPUTE_DTYPE)
    out = gqa_combine(probs, values[None])
    return out.reshape(1, t, -1) @ params["wo"], rows


# --------------------------------------------------------------------------
# SwiGLU MLP
# --------------------------------------------------------------------------


def mlp_init(key, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind == "gelu":
        return {"wi": _dense_init(ks[0], (d, ff)), "wo": _dense_init(ks[2], (ff, d))}
    return {
        "wi": _dense_init(ks[0], (d, ff)),
        "wg": _dense_init(ks[1], (d, ff)),
        "wo": _dense_init(ks[2], (ff, d)),
    }


def mlp_apply(params: dict, x: Array) -> Array:
    if "wg" not in params:  # GELU (whisper-style 2-matrix MLP)
        return jax.nn.gelu(x @ params["wi"]) @ params["wo"]
    return (jax.nn.silu(x @ params["wg"]) * (x @ params["wi"])) @ params["wo"]


# --------------------------------------------------------------------------
# Mixture-of-Experts MLP (top-k token-choice with capacity, sort-based
# dispatch — the memory-lean TPU formulation)
# --------------------------------------------------------------------------


def moe_init(key, cfg: ModelConfig) -> dict:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 4)
    return {
        "router": _dense_init(ks[0], (d, e)).astype(jnp.float32),
        "wi": _dense_init(ks[1], (e, d, ff), scale_axis=1),
        "wg": _dense_init(ks[2], (e, d, ff), scale_axis=1),
        "wo": _dense_init(ks[3], (e, ff, d), scale_axis=1),
    }


def _moe_route(params: dict, cfg: ModelConfig, xt: Array):
    """Shared router: returns (top_p, top_e, aux_loss). xt: (T, d)."""
    k, e = cfg.experts_per_token, cfg.n_experts
    logits = xt.astype(jnp.float32) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, k)  # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # load-balancing auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], e, dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)
    return top_p, top_e, aux


def _moe_dispatch(cfg: ModelConfig, xt: Array, top_p, top_e, capacity: int):
    """Sort-based dispatch; returns (buf (E,C,d), se, sp, st, slot, keep)."""
    t, d = xt.shape
    k, e = cfg.experts_per_token, cfg.n_experts
    flat_e = top_e.reshape(-1)  # (T*k,)
    flat_p = top_p.reshape(-1)
    token_idx = jnp.repeat(jnp.arange(t), k)
    order = jnp.argsort(flat_e)  # group by expert
    se, sp, st = flat_e[order], flat_p[order], token_idx[order]
    starts = jnp.searchsorted(se, jnp.arange(e))  # first slot of each expert
    pos = jnp.arange(t * k) - starts[se]  # position within expert
    keep = pos < capacity
    slot = jnp.where(keep, pos, 0)
    buf = jnp.zeros((e, capacity, d), xt.dtype)
    buf = buf.at[se, slot].add(xt[st] * keep[:, None].astype(xt.dtype))
    return buf, se, sp, st, slot, keep


def _moe_ffn(params: dict, buf: Array) -> Array:
    h = jnp.einsum("ecd,edf->ecf", buf, params["wg"])
    g = jnp.einsum("ecd,edf->ecf", buf, params["wi"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * g, params["wo"])


def _moe_combine(cfg, yb, se, sp, st, slot, keep, t: int, d: int) -> Array:
    out_tok = yb[se, slot] * (sp * keep)[:, None].astype(yb.dtype)
    return jnp.zeros((t, d), yb.dtype).at[st].add(out_tok)


def moe_apply(params: dict, cfg: ModelConfig, x: Array) -> tuple[Array, Array]:
    """Returns (output, aux_loss). x: (B, S, d).

    Two implementations:

    * ``gspmd`` (default) — single-program sort/scatter dispatch; GSPMD
      shards it, but scatters into an expert-sharded buffer replicate (the
      dominant collective on the 1T-param MoE cells — §Perf-B).
    * ``shard_map`` — explicit expert parallelism: local dispatch per data
      shard, ``lax.all_to_all`` over the model axis to the expert owners,
      local expert FFN, reverse all-to-all, local combine. The production
      MoE data path.
    """
    if cfg.moe_impl == "shard_map":
        out, aux = _moe_shard_map(params, cfg, x)
        if out is not None:
            return out, aux
    b, s, d = x.shape
    t = b * s
    k, e = cfg.experts_per_token, cfg.n_experts
    xt = x.reshape(t, d)
    top_p, top_e, aux = _moe_route(params, cfg, xt)
    capacity = max(int(cfg.capacity_factor * t * k / e), 1)
    buf, se, sp, st, slot, keep = _moe_dispatch(cfg, xt, top_p, top_e, capacity)
    yb = _moe_ffn(params, buf)
    out = _moe_combine(cfg, yb, se, sp, st, slot, keep, t, d)
    return out.reshape(b, s, d), aux


def _moe_shard_map(params: dict, cfg: ModelConfig, x: Array):
    """Expert-parallel MoE via shard_map + all_to_all. Returns (None, 0) when
    no suitable mesh is ambient (single-device smoke paths)."""
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "model" not in mesh.axis_names:
        return None, jnp.zeros((), jnp.float32)
    ep = mesh.shape["model"]
    if cfg.n_experts % ep != 0:
        return None, jnp.zeros((), jnp.float32)
    batch_axes = tuple(a for a in mesh.axis_names if a != "model")
    e, k = cfg.n_experts, cfg.experts_per_token

    def local_moe(lp, xl):
        # xl: (B_local, S, d) — this data shard's tokens, replicated over model
        bl, s, d = xl.shape
        t = bl * s
        xt = xl.reshape(t, d)
        top_p, top_e, aux = _moe_route(lp, cfg, xt)
        aux = lax.pmean(aux, batch_axes)
        capacity = max(int(cfg.capacity_factor * t * k / e), 1)
        # pad capacity so E*C splits evenly across the expert axis
        capacity = -(-capacity // ep) * ep
        buf, se, sp, st, slot, keep = _moe_dispatch(cfg, xt, top_p, top_e, capacity)
        # to expert owners: (E, C, d) -> (E/ep, C*ep, d)
        buf = lax.all_to_all(buf, "model", split_axis=0, concat_axis=1, tiled=True)
        yb = _moe_ffn(lp, buf)  # local experts only: lp weights are (E/ep, ...)
        # back to the token owners: (E/ep, C*ep, d) -> (E, C, d)
        yb = lax.all_to_all(yb, "model", split_axis=1, concat_axis=0, tiled=True)
        out = _moe_combine(cfg, yb, se, sp, st, slot, keep, t, d)
        return out.reshape(bl, s, d), aux

    param_specs = {
        "router": P(None, None),
        "wi": P("model", None, None),
        "wg": P("model", None, None),
        "wo": P("model", None, None),
    }
    out, aux = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(param_specs, P(batch_axes, None, None)),
        out_specs=(P(batch_axes, None, None), P()),
        check_vma=False,
    )(params, x)
    return out, aux


# --------------------------------------------------------------------------
# Mamba (selective SSM) block — Jamba's mixer
# --------------------------------------------------------------------------


def mamba_init(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    n = cfg.ssm_state_dim
    ks = jax.random.split(key, 5)
    return {
        "in_proj": _dense_init(ks[0], (d, 2 * d_in)),
        "conv_w": _dense_init(ks[1], (cfg.ssm_conv_dim, d_in)),
        "x_proj": _dense_init(ks[2], (d_in, 2 * n + 1)),  # -> B, C, dt
        "dt_bias": jnp.zeros((d_in,), jnp.float32),
        "a_log": jnp.log(
            jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32), (d_in, 1))
        ),  # (d_in, N)
        "d_skip": jnp.ones((d_in,), jnp.float32),
        "out_proj": _dense_init(ks[3], (d_in, d)),
    }


def _mamba_scan_combine(left, right):
    a1, b1 = left
    a2, b2 = right
    return a2 * a1, a2 * b1 + b2


def mamba_apply(params: dict, cfg: ModelConfig, x: Array) -> Array:
    """Training/prefill form: associative scan over the sequence.

    With ``cfg.ssm_chunk > 0``, the recurrence runs SSD-style: a sequential
    ``lax.scan`` over sequence chunks carrying the (B, d_in, N) state, with
    the parallel associative scan only *inside* each chunk. Peak activation
    memory drops from O(S·d_in·N) to O(chunk·d_in·N) per layer — the memory
    lever for the Jamba train cells.
    """
    b, s, _ = x.shape
    d_in = cfg.ssm_expand * cfg.d_model
    n = cfg.ssm_state_dim

    xz = x @ params["in_proj"]
    xi, z = jnp.split(xz, 2, axis=-1)  # (B, S, d_in) each

    # depthwise causal conv over time
    w = params["conv_w"]  # (K, d_in)
    pad = jnp.pad(xi, ((0, 0), (cfg.ssm_conv_dim - 1, 0), (0, 0)))
    xi = sum(
        pad[:, i : i + s, :] * w[i][None, None, :] for i in range(cfg.ssm_conv_dim)
    )
    xi = jax.nn.silu(xi)

    bc_dt = xi @ params["x_proj"]  # (B, S, 2N+1)
    bmat, cmat, dt = (
        bc_dt[..., :n],
        bc_dt[..., n : 2 * n],
        bc_dt[..., 2 * n :],
    )
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,S,d_in)
    dt = jnp.broadcast_to(dt, (b, s, d_in))

    a = -jnp.exp(params["a_log"])  # (d_in, N)

    def ssm_prefix(xi_c, dt_c, b_c, h0):
        """Scan one chunk: returns (h_t for each t, final h)."""
        a_bar = jnp.exp(dt_c[..., None] * a[None, None])  # (B, C, d_in, N)
        bx = (dt_c * xi_c.astype(jnp.float32))[..., None] * b_c[:, :, None, :].astype(
            jnp.float32
        )
        a_acc, h = lax.associative_scan(_mamba_scan_combine, (a_bar, bx), axis=1)
        # fold in the carried-in state: h_t += a_acc_t · h0
        h = h + a_acc * h0[:, None]
        return h, h[:, -1]

    chunk = cfg.ssm_chunk
    if chunk and s > chunk and s % chunk == 0:
        nchunks = s // chunk

        def body(h0, inputs):
            xi_c, dt_c, b_c, c_c = inputs
            h, h_last = ssm_prefix(xi_c, dt_c, b_c, h0)
            y_c = jnp.einsum("bsdn,bsn->bsd", h, c_c.astype(jnp.float32))
            return h_last, y_c

        def to_chunks(t):
            return jnp.moveaxis(
                t.reshape(b, nchunks, chunk, *t.shape[2:]), 1, 0
            )

        h0 = jnp.zeros((b, d_in, n), jnp.float32)
        _, y = lax.scan(
            body, h0, (to_chunks(xi), to_chunks(dt), to_chunks(bmat), to_chunks(cmat))
        )
        y = jnp.moveaxis(y, 0, 1).reshape(b, s, d_in)
    else:
        h, _ = ssm_prefix(xi, dt, bmat, jnp.zeros((b, d_in, n), jnp.float32))
        y = jnp.einsum("bsdn,bsn->bsd", h, cmat.astype(jnp.float32))

    y = y + xi.astype(jnp.float32) * params["d_skip"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return y @ params["out_proj"]


def mamba_step(
    params: dict, cfg: ModelConfig, x: Array, state: dict
) -> tuple[Array, dict]:
    """Single-token decode. x: (B, 1, d); state: {"h": (B,d_in,N), "conv": (B,K,d_in)}."""
    b = x.shape[0]
    d_in = cfg.ssm_expand * cfg.d_model
    n = cfg.ssm_state_dim

    xz = x[:, 0] @ params["in_proj"]
    xi, z = jnp.split(xz, 2, axis=-1)  # (B, d_in)

    conv = jnp.concatenate([state["conv"][:, 1:], xi[:, None]], axis=1)  # (B,K,d_in)
    xi = jax.nn.silu(jnp.einsum("bkd,kd->bd", conv, params["conv_w"]))

    bc_dt = xi @ params["x_proj"]
    bvec, cvec, dt = bc_dt[..., :n], bc_dt[..., n : 2 * n], bc_dt[..., 2 * n :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B, d_in)

    a = -jnp.exp(params["a_log"])
    a_bar = jnp.exp(dt[..., None] * a[None])  # (B, d_in, N)
    bx = (dt * xi.astype(jnp.float32))[..., None] * bvec[:, None, :].astype(jnp.float32)
    h = a_bar * state["h"] + bx
    y = jnp.einsum("bdn,bn->bd", h, cvec.astype(jnp.float32))
    y = y + xi.astype(jnp.float32) * params["d_skip"]
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return (y @ params["out_proj"])[:, None], {"h": h, "conv": conv}


# --------------------------------------------------------------------------
# RWKV-6 (Finch) time-mix + channel-mix — data-dependent decay, attention-free
# --------------------------------------------------------------------------


def rwkv_init(key, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    return {
        "wr": _dense_init(ks[0], (d, d)),
        "wk": _dense_init(ks[1], (d, d)),
        "wv": _dense_init(ks[2], (d, d)),
        "wg": _dense_init(ks[3], (d, d)),
        "wo": _dense_init(ks[4], (d, d)),
        "w_decay": _dense_init(ks[5], (d, d)),  # data-dependent decay proj
        "decay_bias": jnp.full((d,), -6.0, jnp.float32),
        "mix": jnp.full((5, d), 0.5, jnp.float32),  # token-shift mixes r,k,v,g,w
        "bonus": jnp.zeros((d,), jnp.float32),  # per-channel "u" bonus
    }


def _rwkv_heads(x: Array, head_dim: int) -> Array:
    b, s, d = x.shape
    return x.reshape(b, s, d // head_dim, head_dim)


def rwkv_apply(params: dict, cfg: ModelConfig, x: Array) -> Array:
    """Training/prefill: scan over time with matrix-valued state (B,H,K,V)."""
    b, s, d = x.shape
    hd = cfg.rwkv_head_dim
    nh = d // hd

    shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]  # token shift
    mix = params["mix"].astype(x.dtype)
    xr, xk, xv, xg, xw = (x * mix[i] + shifted * (1 - mix[i]) for i in range(5))

    r = _rwkv_heads(xr @ params["wr"], hd)  # (B,S,H,K)
    k = _rwkv_heads(xk @ params["wk"], hd)
    v = _rwkv_heads(xv @ params["wv"], hd)
    g = jax.nn.silu(xg @ params["wg"])  # (B,S,D)
    w = jnp.exp(
        -jnp.exp((xw @ params["w_decay"]).astype(jnp.float32) + params["decay_bias"])
    )  # (B,S,D) data-dependent decay in (0,1)
    w = _rwkv_heads(w, hd)  # (B,S,H,K)
    u = _rwkv_heads(jnp.broadcast_to(params["bonus"], (b, 1, d)), hd)[:, 0]  # (B,H,K)

    def step(state, inputs):
        rt, kt, vt, wt = inputs  # (B,H,K) except vt: (B,H,V)
        kv = jnp.einsum("bhk,bhv->bhkv", kt.astype(jnp.float32), vt.astype(jnp.float32))
        out = jnp.einsum("bhk,bhkv->bhv", rt.astype(jnp.float32), state + u[..., None] * kv)
        state = wt[..., None] * state + kv
        return state, out

    state0 = jnp.zeros((b, nh, hd, hd), jnp.float32)
    xs = (
        jnp.moveaxis(r, 1, 0),
        jnp.moveaxis(k, 1, 0),
        jnp.moveaxis(v, 1, 0),
        jnp.moveaxis(w, 1, 0),
    )
    _, outs = lax.scan(step, state0, xs)  # (S, B, H, V)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, d).astype(x.dtype)
    return (out * g) @ params["wo"]


def rwkv_step(
    params: dict, cfg: ModelConfig, x: Array, state: dict
) -> tuple[Array, dict]:
    """Single-token decode. state: {"s": (B,H,K,V) fp32, "shift": (B,d)}."""
    b, _, d = x.shape
    hd = cfg.rwkv_head_dim
    xt = x[:, 0]
    mix = params["mix"].astype(x.dtype)
    prev = state["shift"]
    xr, xk, xv, xg, xw = (xt * mix[i] + prev * (1 - mix[i]) for i in range(5))

    r = (xr @ params["wr"]).reshape(b, -1, hd)
    k = (xk @ params["wk"]).reshape(b, -1, hd)
    v = (xv @ params["wv"]).reshape(b, -1, hd)
    g = jax.nn.silu(xg @ params["wg"])
    w = jnp.exp(
        -jnp.exp((xw @ params["w_decay"]).astype(jnp.float32) + params["decay_bias"])
    ).reshape(b, -1, hd)
    u = jnp.broadcast_to(params["bonus"], (b, d)).reshape(b, -1, hd)

    kv = jnp.einsum("bhk,bhv->bhkv", k.astype(jnp.float32), v.astype(jnp.float32))
    out = jnp.einsum("bhk,bhkv->bhv", r.astype(jnp.float32), state["s"] + u[..., None] * kv)
    new_s = w[..., None] * state["s"] + kv
    out = out.reshape(b, d).astype(x.dtype)
    y = (out * g) @ params["wo"]
    return y[:, None], {"s": new_s, "shift": xt}


def rwkv_channel_mix_init(key, cfg: ModelConfig) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 2)
    return {
        "wk": _dense_init(ks[0], (d, ff)),
        "wv": _dense_init(ks[1], (ff, d)),
        "mix": jnp.full((1, d), 0.5, jnp.float32),
    }


def rwkv_channel_mix(params: dict, x: Array, shifted: Array) -> Array:
    mix = params["mix"][0].astype(x.dtype)
    xk = x * mix + shifted * (1 - mix)
    h = jnp.square(jax.nn.relu(xk @ params["wk"]))
    return h @ params["wv"]
