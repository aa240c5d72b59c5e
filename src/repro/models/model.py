"""Unified model API over the architecture pool.

Every assigned architecture — dense GQA transformers, MoE transformers, the
Jamba attention/Mamba hybrid, RWKV-6, Whisper (enc-dec), and phi-3-vision —
is instantiated through one :class:`Model` facade:

* ``init(key)`` / ``abstract_params()`` — concrete or shape-only parameters.
* ``forward(params, batch)`` / ``loss(params, batch)`` — training path.
* ``init_cache(batch, len)`` / ``abstract_cache()`` / ``decode_step(...)``
  — serving path (single-token decode against a persistent cache).

Layer trunks are built with ``lax.scan`` over stacked per-layer parameters so
the lowered HLO stays small even for the 72-layer Jamba trunk; heterogeneous
trunks (Jamba's 1-attention-per-8 interleave with MoE every other layer) scan
over *groups* and unroll inside the group.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ..kernels import ops as kernel_ops
from . import layers as L
from .config import ModelConfig

Array = jax.Array


def _norm_init(key, d: int, kind: str = "rms"):
    if kind == "ln":
        return {"w": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}
    return {"w": jnp.ones((d,), jnp.float32)}


def _norm(params, x, eps):
    if "b" in params:
        return L.layer_norm(x, params["w"], params["b"], eps)
    return L.rms_norm(x, params["w"], eps)


def _sinusoidal(positions: Array, d: int) -> Array:
    freqs = jnp.exp(-jnp.log(10_000.0) * jnp.arange(0, d, 2, jnp.float32) / d)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(L.COMPUTE_DTYPE)


def _stack_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(fn)


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def _scan(self, body, init, xs):
        """lax.scan with optional full unroll (dry-run exact HLO costs)."""
        return lax.scan(body, init, xs, unroll=True if self.cfg.scan_unroll else 1)

    # ================================================================ params

    def init(self, key) -> dict:
        cfg = self.cfg
        kemb, khead, ktrunk, kfinal = jax.random.split(key, 4)
        params: dict = {
            "embed": L._dense_init(kemb, (cfg.vocab_size, cfg.d_model)),
            "final_norm": _norm_init(kfinal, cfg.d_model, self._norm_kind()),
        }
        if not cfg.tie_embeddings:
            params["head"] = L._dense_init(khead, (cfg.d_model, cfg.vocab_size))
        if cfg.family in ("dense", "vlm", "moe"):
            params["layers"] = self._uniform_trunk_init(ktrunk)
        elif cfg.family == "hybrid":
            params["groups"] = self._hybrid_trunk_init(ktrunk)
        elif cfg.family == "ssm":
            params["layers"] = self._rwkv_trunk_init(ktrunk)
        elif cfg.family == "encdec":
            kenc, kdec = jax.random.split(ktrunk)
            params["enc_layers"] = self._encoder_trunk_init(kenc)
            params["enc_final_norm"] = _norm_init(kenc, cfg.d_model, "ln")
            params["dec_layers"] = self._decoder_trunk_init(kdec)
        else:
            raise ValueError(cfg.family)
        return params

    def abstract_params(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    def _norm_kind(self) -> str:
        return "ln" if self.cfg.family in ("ssm", "encdec") else "rms"

    def _uniform_trunk_init(self, key) -> dict:
        cfg = self.cfg
        n = cfg.n_layers
        k1, k2, k3, k4 = jax.random.split(key, 4)
        trunk = {
            "attn_norm": _stack_init(lambda k: _norm_init(k, cfg.d_model), k1, n),
            "attn": _stack_init(lambda k: L.attention_init(k, cfg), k2, n),
            "mlp_norm": _stack_init(lambda k: _norm_init(k, cfg.d_model), k3, n),
        }
        if cfg.family == "moe":
            trunk["moe"] = _stack_init(lambda k: L.moe_init(k, cfg), k4, n)
        else:
            trunk["mlp"] = _stack_init(lambda k: L.mlp_init(k, cfg), k4, n)
        return trunk

    def _hybrid_trunk_init(self, key) -> dict:
        cfg = self.cfg
        g = cfg.n_layers // cfg.attn_period
        m = cfg.attn_period - 1  # mamba layers per group
        n_moe = (m + 1) // 2  # mamba positions 0,2,4,... carry MoE
        n_dense_m = m - n_moe
        ks = jax.random.split(key, 8)
        d = cfg.d_model
        return {
            "attn_norm": _stack_init(lambda k: _norm_init(k, d), ks[0], g),
            "attn": _stack_init(lambda k: L.attention_init(k, cfg), ks[1], g),
            "attn_mlp_norm": _stack_init(lambda k: _norm_init(k, d), ks[2], g),
            "attn_mlp": _stack_init(lambda k: L.mlp_init(k, cfg), ks[3], g),
            "mamba_norm": _stack_init(
                lambda k: _stack_init(lambda k2: _norm_init(k2, d), k, m), ks[4], g
            ),
            "mamba": _stack_init(
                lambda k: _stack_init(lambda k2: L.mamba_init(k2, cfg), k, m), ks[5], g
            ),
            "mamba_mlp_norm": _stack_init(
                lambda k: _stack_init(lambda k2: _norm_init(k2, d), k, m), ks[4], g
            ),
            "mamba_moe": _stack_init(
                lambda k: _stack_init(lambda k2: L.moe_init(k2, cfg), k, n_moe),
                ks[6],
                g,
            ),
            "mamba_mlp": _stack_init(
                lambda k: _stack_init(lambda k2: L.mlp_init(k2, cfg), k, n_dense_m),
                ks[7],
                g,
            ),
        }

    def _rwkv_trunk_init(self, key) -> dict:
        cfg = self.cfg
        n = cfg.n_layers
        ks = jax.random.split(key, 4)
        d = cfg.d_model
        return {
            "tm_norm": _stack_init(lambda k: _norm_init(k, d, "ln"), ks[0], n),
            "tm": _stack_init(lambda k: L.rwkv_init(k, cfg), ks[1], n),
            "cm_norm": _stack_init(lambda k: _norm_init(k, d, "ln"), ks[2], n),
            "cm": _stack_init(lambda k: L.rwkv_channel_mix_init(k, cfg), ks[3], n),
        }

    def _encoder_trunk_init(self, key) -> dict:
        cfg = self.cfg
        n = cfg.n_encoder_layers
        ks = jax.random.split(key, 4)
        d = cfg.d_model
        return {
            "ln1": _stack_init(lambda k: _norm_init(k, d, "ln"), ks[0], n),
            "attn": _stack_init(lambda k: L.attention_init(k, cfg), ks[1], n),
            "ln2": _stack_init(lambda k: _norm_init(k, d, "ln"), ks[2], n),
            "mlp": _stack_init(lambda k: L.mlp_init(k, cfg), ks[3], n),
        }

    def _decoder_trunk_init(self, key) -> dict:
        cfg = self.cfg
        n = cfg.n_layers
        ks = jax.random.split(key, 6)
        d = cfg.d_model
        return {
            "ln1": _stack_init(lambda k: _norm_init(k, d, "ln"), ks[0], n),
            "self_attn": _stack_init(lambda k: L.attention_init(k, cfg), ks[1], n),
            "ln2": _stack_init(lambda k: _norm_init(k, d, "ln"), ks[2], n),
            "cross_attn": _stack_init(lambda k: L.attention_init(k, cfg), ks[3], n),
            "ln3": _stack_init(lambda k: _norm_init(k, d, "ln"), ks[4], n),
            "mlp": _stack_init(lambda k: L.mlp_init(k, cfg), ks[5], n),
        }

    # ================================================================= train

    def forward(self, params: dict, batch: dict) -> tuple[Array, Array]:
        """Returns (logits, moe_aux_loss)."""
        cfg = self.cfg
        if cfg.family == "encdec":
            return self._forward_encdec(params, batch)
        tokens = batch["tokens"]
        x = params["embed"][tokens]  # (B, S, d)
        prefix = 0
        if cfg.family == "vlm":
            fe = batch["frontend_embeds"].astype(x.dtype)  # (B, P, d)
            x = jnp.concatenate([fe, x], axis=1)
            prefix = fe.shape[1]
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

        if cfg.family in ("dense", "vlm", "moe"):
            x, aux = self._uniform_trunk(params["layers"], x, positions)
        elif cfg.family == "hybrid":
            x, aux = self._hybrid_trunk(params["groups"], x, positions)
        elif cfg.family == "ssm":
            x, aux = self._rwkv_trunk(params["layers"], x)
        else:
            raise ValueError(cfg.family)

        x = _norm(params["final_norm"], x, cfg.norm_eps)
        if prefix:
            x = x[:, prefix:]
        logits = x @ self._head(params)
        return logits, aux

    def _head(self, params):
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["head"]

    def _uniform_trunk(self, trunk, x, positions):
        cfg = self.cfg

        def body(carry, lp):
            x, aux = carry
            h, _ = L.attention_apply(
                lp["attn"], cfg, _norm(lp["attn_norm"], x, cfg.norm_eps), positions
            )
            x = x + h
            y = _norm(lp["mlp_norm"], x, cfg.norm_eps)
            if "moe" in lp:
                y, a = L.moe_apply(lp["moe"], cfg, y)
                aux = aux + a
            else:
                y = L.mlp_apply(lp["mlp"], y)
            return (x + y, aux), None

        (x, aux), _ = self._scan(_remat(body, cfg), (x, jnp.zeros((), jnp.float32)), trunk)
        return x, aux

    def _hybrid_trunk(self, trunk, x, positions):
        cfg = self.cfg
        m = cfg.attn_period - 1

        def body(carry, gp):
            x, aux = carry
            # attention layer (dense MLP)
            h, _ = L.attention_apply(
                gp["attn"], cfg, _norm(gp["attn_norm"], x, cfg.norm_eps), positions
            )
            x = x + h
            x = x + L.mlp_apply(
                gp["attn_mlp"], _norm(gp["attn_mlp_norm"], x, cfg.norm_eps)
            )
            # mamba layers; even in-group index carries MoE
            i_moe = i_mlp = 0
            for i in range(m):
                lpn = jax.tree.map(lambda a: a[i], gp["mamba_norm"])
                lp = jax.tree.map(lambda a: a[i], gp["mamba"])
                x = x + L.mamba_apply(lp, cfg, _norm(lpn, x, cfg.norm_eps))
                mn = jax.tree.map(lambda a: a[i], gp["mamba_mlp_norm"])
                y = _norm(mn, x, cfg.norm_eps)
                if i % 2 == 0:
                    mp = jax.tree.map(lambda a, i_moe=i_moe: a[i_moe], gp["mamba_moe"])
                    y, a = L.moe_apply(mp, cfg, y)
                    aux = aux + a
                    i_moe += 1
                else:
                    mp = jax.tree.map(lambda a, i_mlp=i_mlp: a[i_mlp], gp["mamba_mlp"])
                    y = L.mlp_apply(mp, y)
                    i_mlp += 1
                x = x + y
            return (x, aux), None

        (x, aux), _ = self._scan(_remat(body, cfg), (x, jnp.zeros((), jnp.float32)), trunk)
        return x, aux

    def _rwkv_trunk(self, trunk, x):
        cfg = self.cfg

        def body(carry, lp):
            x, aux = carry
            x = x + L.rwkv_apply(lp["tm"], cfg, _norm(lp["tm_norm"], x, cfg.norm_eps))
            h = _norm(lp["cm_norm"], x, cfg.norm_eps)
            shifted = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
            x = x + L.rwkv_channel_mix(lp["cm"], h, shifted)
            return (x, aux), None

        (x, aux), _ = self._scan(_remat(body, cfg), (x, jnp.zeros((), jnp.float32)), trunk)
        return x, aux

    def _forward_encdec(self, params, batch):
        cfg = self.cfg
        frames = batch["frontend_embeds"].astype(L.COMPUTE_DTYPE)  # (B, T, d)
        tokens = batch["tokens"]
        b, t = frames.shape[:2]
        frames = frames + _sinusoidal(jnp.arange(t), cfg.d_model)[None]
        enc_pos = jnp.broadcast_to(jnp.arange(t), (b, t))

        def enc_body(x, lp):
            h, _ = L.attention_apply(
                lp["attn"], cfg, _norm(lp["ln1"], x, cfg.norm_eps), enc_pos,
                causal=False, use_rope=False,
            )
            x = x + h
            x = x + L.mlp_apply(lp["mlp"], _norm(lp["ln2"], x, cfg.norm_eps))
            return x, None

        enc, _ = self._scan(_remat(enc_body, cfg), frames, params["enc_layers"])
        enc = _norm(params["enc_final_norm"], enc, cfg.norm_eps)

        x = params["embed"][tokens]
        s = x.shape[1]
        x = x + _sinusoidal(jnp.arange(s), cfg.d_model)[None]
        dec_pos = jnp.broadcast_to(jnp.arange(s), (b, s))

        def dec_body(x, lp):
            h, _ = L.attention_apply(
                lp["self_attn"], cfg, _norm(lp["ln1"], x, cfg.norm_eps), dec_pos,
                causal=True, use_rope=False,
            )
            x = x + h
            h, _ = L.attention_apply(
                lp["cross_attn"], cfg, _norm(lp["ln2"], x, cfg.norm_eps), dec_pos,
                causal=False, use_rope=False, kv=enc,
            )
            x = x + h
            x = x + L.mlp_apply(lp["mlp"], _norm(lp["ln3"], x, cfg.norm_eps))
            return x, None

        x, _ = self._scan(_remat(dec_body, cfg), x, params["dec_layers"])
        x = _norm(params["final_norm"], x, cfg.norm_eps)
        logits = x @ self._head(params)
        return logits, jnp.zeros((), jnp.float32)

    def loss(self, params: dict, batch: dict) -> tuple[Array, dict]:
        logits, aux = self.forward(params, batch)
        labels = batch["labels"]
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        # vocab-parallel gold-logit extraction: a masked sum keeps the vocab
        # dim sharded under GSPMD (take_along_axis would force an all-gather
        # of the full logits — ~40 GB/device on the 200k-vocab archs)
        vocab_iota = jnp.arange(logits.shape[-1], dtype=labels.dtype)
        mask = (vocab_iota[None, None, :] == labels[..., None]).astype(jnp.float32)
        gold = jnp.sum(logits * mask, axis=-1)
        ce = jnp.mean(logz - gold)
        zloss = 1e-4 * jnp.mean(jnp.square(logz))
        total = ce + zloss + 0.01 * aux
        return total, {"ce": ce, "zloss": zloss, "moe_aux": aux}

    # ================================================================= serve

    def init_cache(self, batch_size: int, max_len: int, concrete: bool = True):
        cfg = self.cfg
        mk = jnp.zeros if concrete else jax.ShapeDtypeStruct
        hd, hkv = cfg.head_dim_, cfg.n_kv_heads
        d_in = cfg.ssm_expand * cfg.d_model

        def arr(shape, dtype=L.COMPUTE_DTYPE):
            return jnp.zeros(shape, dtype) if concrete else jax.ShapeDtypeStruct(shape, dtype)

        if cfg.family in ("dense", "vlm", "moe"):
            n = cfg.n_layers
            cache = {
                "k": arr((n, batch_size, max_len, hkv, hd),
                         jnp.int8 if cfg.cache_quant == "int8" else L.COMPUTE_DTYPE),
                "v": arr((n, batch_size, max_len, hkv, hd),
                         jnp.int8 if cfg.cache_quant == "int8" else L.COMPUTE_DTYPE),
            }
            if cfg.cache_quant == "int8":
                cache["k_scale"] = arr((n, batch_size, max_len, hkv, 1))
                cache["v_scale"] = arr((n, batch_size, max_len, hkv, 1))
            return cache
        if cfg.family == "hybrid":
            g = cfg.n_layers // cfg.attn_period
            m = cfg.attn_period - 1
            return {
                "k": arr((g, batch_size, max_len, hkv, hd)),
                "v": arr((g, batch_size, max_len, hkv, hd)),
                "h": arr((g, m, batch_size, d_in, cfg.ssm_state_dim), jnp.float32),
                "conv": arr((g, m, batch_size, cfg.ssm_conv_dim, d_in)),
            }
        if cfg.family == "ssm":
            n = cfg.n_layers
            nh = cfg.d_model // cfg.rwkv_head_dim
            return {
                "s": arr((n, batch_size, nh, cfg.rwkv_head_dim, cfg.rwkv_head_dim), jnp.float32),
                "shift_tm": arr((n, batch_size, cfg.d_model)),
                "shift_cm": arr((n, batch_size, cfg.d_model)),
            }
        if cfg.family == "encdec":
            n = cfg.n_layers
            return {
                "k": arr((n, batch_size, max_len, hkv, hd)),
                "v": arr((n, batch_size, max_len, hkv, hd)),
                "xk": arr((n, batch_size, cfg.encoder_seq_len, hkv, hd)),
                "xv": arr((n, batch_size, cfg.encoder_seq_len, hkv, hd)),
            }
        raise ValueError(cfg.family)

    def decode_step(
        self, params: dict, cache: dict, tokens: Array, pos: Array,
        update_mask: Array | None = None,
    ) -> tuple[Array, dict]:
        """One new token per sequence. tokens: (B, 1); pos: scalar int32, or
        an (B,) int32 vector for continuous batching (per-slot positions).

        ``update_mask`` (optional, (B,) bool) freezes the cache rows of
        unselected batch entries: masked-out slots still compute (their
        logits are garbage to be discarded) but their cache state comes out
        bit-identical to what went in. This is what lets one launch advance
        only the slots it means to — a prefill chunk touching one admitted
        slot, or a decode step skipping dead slots."""
        cfg = self.cfg
        old_cache = cache
        x = params["embed"][tokens]  # (B, 1, d)
        b = x.shape[0]
        if getattr(pos, "ndim", 0) == 1:
            positions = pos[:, None]  # (B, 1): each slot at its own position
        else:
            positions = jnp.full((b, 1), pos)

        if cfg.family in ("dense", "vlm", "moe"):
            x, cache = self._uniform_decode(params["layers"], cache, x, positions, pos)
        elif cfg.family == "hybrid":
            cache = self._fresh_state(cache, positions[:, 0])
            x, cache = self._hybrid_decode(params["groups"], cache, x, positions, pos)
        elif cfg.family == "ssm":
            cache = self._fresh_state(cache, positions[:, 0])
            x, cache = self._rwkv_decode(params["layers"], cache, x)
        elif cfg.family == "encdec":
            x, cache = self._encdec_decode(params["dec_layers"], cache, x, positions, pos)
        else:
            raise ValueError(cfg.family)

        if update_mask is not None:
            cache = self._masked_cache(old_cache, cache, update_mask)
        x = _norm(params["final_norm"], x, cfg.norm_eps)
        logits = x @ self._head(params)
        return logits, cache

    @staticmethod
    def _fresh_state(cache: dict, positions: Array) -> dict:
        """Zero the recurrent state (hybrid ``h``/``conv``, ssm ``s``/
        ``shift_*``) of each sequence at position 0, which has no past: a
        slot handed to a new request must not start from the last one's
        state. Attention rows need no reset, as decode masks the rows past a
        sequence's position. The batch axis is as in :meth:`_masked_cache`."""

        def fresh(key: str, leaf: Array) -> Array:
            if key in ("k", "v"):
                return leaf
            shape = [1] * leaf.ndim
            shape[2 if key in ("h", "conv") else 1] = positions.shape[0]
            return jnp.where(positions.reshape(shape) > 0, leaf,
                             jnp.zeros((), leaf.dtype))

        return {k: fresh(k, v) for k, v in cache.items()}

    @staticmethod
    def _masked_cache(old: dict, new: dict, update_mask: Array) -> dict:
        """Per-leaf batch-row select: rows where ``update_mask`` is False
        keep their old cache state bit-exactly. The batch axis is 1 on
        every cache layout (layer/group axis leads) except the hybrid
        trunk's per-group-stacked ``h``/``conv`` leaves, where it is 2."""
        b = update_mask.shape[0]

        def merge(key: str, o: Array, n: Array) -> Array:
            if n is o:  # passthrough leaves (encdec xk/xv): nothing to mask
                return n
            ax = 2 if key in ("h", "conv") else 1
            shape = [1] * n.ndim
            shape[ax] = b
            return jnp.where(update_mask.reshape(shape), n, o)

        return {k: merge(k, old[k], n) for k, n in new.items()}

    @staticmethod
    def _write_rows(buf: Array, rows: Array, slot: Array, pos0: Array,
                    n: Array) -> Array:
        """Write ``rows[:, :n]`` (L, T, ...) into rows ``pos0 .. pos0 + n - 1``
        of ``buf[:, slot]`` (L, B, S, ...), in place: one dynamic-update-slice
        of a window of min(T, S) rows, merged with what the window holds, so
        every other row keeps its value, also where the window is clamped to
        the cache's end."""
        t, s = rows.shape[1], buf.shape[2]
        w = min(t, s)
        start = jnp.clip(pos0, 0, s - w)
        at = (0, slot, start) + (0,) * (buf.ndim - 3)
        old = lax.dynamic_slice(buf, at, (buf.shape[0], 1, w) + buf.shape[3:])
        i = start + jnp.arange(w) - pos0  # the token each window row holds
        keep = ((i < 0) | (i >= n)).reshape((1, 1, w) + (1,) * (buf.ndim - 3))
        # window row j holds token start - pos0 + j: a slice of the rows
        # behind t rows of padding (a gather would make the compiler lay the
        # whole cache out anew around the write)
        padded = jnp.concatenate([jnp.zeros_like(rows), rows], axis=1)
        new = lax.dynamic_slice_in_dim(padded, t + start - pos0, w, axis=1)
        return lax.dynamic_update_slice(
            buf, jnp.where(keep, old, new[:, None].astype(buf.dtype)), at)

    def decode_and_sample(
        self, params: dict, cache: dict, prev_tokens: Array,
        token_overrides: Array, override_mask: Array, pos: Array,
        update_mask: Array | None = None, *, sample_backend: str = "xla",
    ) -> tuple[Array, dict]:
        """Fused decode step + greedy sampling: the launch returns ``(B, 1)``
        int32 token ids instead of ``(B, vocab)`` logits, so the host's
        per-step sync point shrinks from the full logits tensor to a few
        bytes — and, because the sampled ids never leave the device, the
        next launch's input tokens are device-resident state rather than a
        descriptor field. The host injects tokens only through
        ``token_overrides``/``override_mask`` (admissions, freed slots),
        which elide in steady-state decode.

        ``prev_tokens``: (B, 1) device-resident ids from the previous step;
        ``token_overrides``: (B,) int32 host injections where
        ``override_mask`` (B, bool) is set."""
        tokens = jnp.where(override_mask[:, None],
                           token_overrides[:, None].astype(jnp.int32),
                           prev_tokens)
        logits, cache = self.decode_step(params, cache, tokens, pos,
                                         update_mask)
        ids = kernel_ops.sample_op(logits[:, 0], backend=sample_backend)
        return ids[:, None].astype(jnp.int32), cache

    @property
    def prefill_path(self) -> str:
        """How :meth:`prefill_chunk` advances a slot: ``"chunk"``, one causal
        forward of the chunk's tokens, for the dense trunk (attention and MLP
        layers over a k/v cache, plain or int8); ``"scan"``, one masked
        :meth:`decode_step` per token, for the rest. MoE capacity routing
        drops different tokens at different token counts, recurrent state
        (hybrid, ssm) advances a token at a time, and the engine does not
        prefill encdec or vlm front ends."""
        return "chunk" if self.cfg.family == "dense" else "scan"

    def prefill_chunk(
        self, params: dict, cache: dict, chunk_tokens: Array, pos0: Array,
        n_valid: Array, slot_mask: Array,
    ) -> tuple[Array, dict]:
        """Batched prefill: advance the admitted slot through up to
        ``len(chunk_tokens)`` prompt tokens in **one launch**, so a p-token
        prompt costs ``ceil(p/chunk)`` launches instead of p full-batch
        launches. :attr:`prefill_path` picks how; both paths leave every
        other slot's state, and the slot's rows from ``pos0 + n_valid`` on,
        as they were.

        ``chunk_tokens``: (T,) int32, valid through ``n_valid``;
        ``pos0``: (B,) int32 per-slot start positions (token i sits at
        ``pos0 + i``); ``slot_mask``: (B,) bool, True at the admitted slot
        (the engine sets one). Returns ``(probe, cache)`` where probe is the
        (B, 1) int32 argmax of the last valid token's logits in the admitted
        slot's row (a few-byte sync handle for the staging ring; zeros
        elsewhere)."""
        if self.prefill_path == "chunk":
            return self._prefill_forward(params, cache, chunk_tokens, pos0,
                                         n_valid, slot_mask)
        return self.prefill_scan(params, cache, chunk_tokens, pos0, n_valid,
                                 slot_mask)

    def _prefill_forward(self, params, cache, chunk_tokens, pos0, n_valid,
                         slot_mask):
        """The dense chunk path: the chunk's T tokens of the one admitted
        slot go through the trunk together, reading the weights once and
        attending over that slot's rows alone (``L.attention_chunk``). The
        layers' new K/V rows then go into the cache in one in-place write a
        leaf, at the slot's rows ``pos0 .. pos0 + n_valid - 1`` only
        (:meth:`_write_rows`). The head runs on the last valid token alone."""
        cfg = self.cfg
        slot = jnp.argmax(slot_mask)
        start = pos0[slot]
        positions = start + jnp.arange(chunk_tokens.shape[0], dtype=jnp.int32)
        x = params["embed"][chunk_tokens.astype(jnp.int32)][None]  # (1, T, d)

        def body(x, xs):
            lp, layer_cache = xs
            h, rows = L.attention_chunk(
                lp["attn"], cfg, _norm(lp["attn_norm"], x, cfg.norm_eps),
                positions, {name: buf[slot] for name, buf in layer_cache.items()},
            )
            x = x + h
            x = x + L.mlp_apply(lp["mlp"], _norm(lp["mlp_norm"], x, cfg.norm_eps))
            return x, rows

        x, rows = self._scan(body, x, (params["layers"], cache))
        n_write = jnp.where(slot_mask[slot], n_valid, 0)  # no slot: no rows
        cache = {name: self._write_rows(cache[name], rows[name], slot, start, n_write)
                 for name in cache}
        last = lax.dynamic_index_in_dim(x[0], jnp.maximum(n_valid - 1, 0),
                                        keepdims=False)  # (d,)
        logits = _norm(params["final_norm"], last, cfg.norm_eps) @ self._head(params)
        tok = jnp.argmax(logits).astype(jnp.int32)
        probe = jnp.where(slot_mask & (n_valid > 0), tok, 0)
        return probe[:, None], cache

    def prefill_scan(
        self, params: dict, cache: dict, chunk_tokens: Array, pos0: Array,
        n_valid: Array, slot_mask: Array,
    ) -> tuple[Array, dict]:
        """:meth:`prefill_chunk` as a ``lax.scan`` of masked decode steps:
        step i advances the slots in ``slot_mask`` by token i when
        ``i < n_valid`` (padded steps are fully masked). Every step computes
        every slot, reads all the weights and selects over the whole cache;
        the path of the families :attr:`prefill_path` names ``"scan"``."""
        b = slot_mask.shape[0]

        def body(carry, xs):
            cache, probe = carry
            i, tok = xs
            step_mask = slot_mask & (i < n_valid)
            toks = jnp.full((b, 1), tok, jnp.int32)
            logits, cache = self.decode_step(params, cache, toks, pos0 + i,
                                             step_mask)
            ids = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            probe = jnp.where(step_mask[:, None], ids[:, None], probe)
            return (cache, probe), None

        t = chunk_tokens.shape[0]
        (cache, probe), _ = lax.scan(
            body, (cache, jnp.zeros((b, 1), jnp.int32)),
            (jnp.arange(t, dtype=jnp.int32), chunk_tokens.astype(jnp.int32)),
        )
        return probe, cache

    def _uniform_decode(self, trunk, cache, x, positions, pos):
        cfg = self.cfg
        quant = "k_scale" in cache

        def body(x, inputs):
            if quant:
                lp, lk, lv, lks, lvs = inputs
                layer_cache = {"k": lk, "v": lv, "k_scale": lks, "v_scale": lvs}
            else:
                lp, lk, lv = inputs
                layer_cache = {"k": lk, "v": lv}
            h, nc = L.attention_apply(
                lp["attn"], cfg, _norm(lp["attn_norm"], x, cfg.norm_eps), positions,
                cache=layer_cache, cache_pos=pos,
            )
            x = x + h
            y = _norm(lp["mlp_norm"], x, cfg.norm_eps)
            if "moe" in lp:
                y, _ = L.moe_apply(lp["moe"], cfg, y)
            else:
                y = L.mlp_apply(lp["mlp"], y)
            if quant:
                return x + y, (nc["k"], nc["v"], nc["k_scale"], nc["v_scale"])
            return x + y, (nc["k"], nc["v"])

        if quant:
            x, (ck, cv, cks, cvs) = self._scan(
                body, x,
                (trunk, cache["k"], cache["v"], cache["k_scale"], cache["v_scale"]),
            )
            return x, {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
        x, (ck, cv) = self._scan(body, x, (trunk, cache["k"], cache["v"]))
        return x, {"k": ck, "v": cv}

    def _hybrid_decode(self, trunk, cache, x, positions, pos):
        cfg = self.cfg
        m = cfg.attn_period - 1

        def body(x, inputs):
            gp, lk, lv, gh, gconv = inputs
            h, nc = L.attention_apply(
                gp["attn"], cfg, _norm(gp["attn_norm"], x, cfg.norm_eps), positions,
                cache={"k": lk, "v": lv}, cache_pos=pos,
            )
            x = x + h
            x = x + L.mlp_apply(
                gp["attn_mlp"], _norm(gp["attn_mlp_norm"], x, cfg.norm_eps)
            )
            new_h, new_conv = [], []
            i_moe = i_mlp = 0
            for i in range(m):
                lpn = jax.tree.map(lambda a: a[i], gp["mamba_norm"])
                lp = jax.tree.map(lambda a: a[i], gp["mamba"])
                y, st = L.mamba_step(
                    lp, cfg, _norm(lpn, x, cfg.norm_eps),
                    {"h": gh[i], "conv": gconv[i]},
                )
                x = x + y
                new_h.append(st["h"])
                new_conv.append(st["conv"])
                mn = jax.tree.map(lambda a: a[i], gp["mamba_mlp_norm"])
                y = _norm(mn, x, cfg.norm_eps)
                if i % 2 == 0:
                    mp = jax.tree.map(lambda a, j=i_moe: a[j], gp["mamba_moe"])
                    y, _ = L.moe_apply(mp, cfg, y)
                    i_moe += 1
                else:
                    mp = jax.tree.map(lambda a, j=i_mlp: a[j], gp["mamba_mlp"])
                    y = L.mlp_apply(mp, y)
                    i_mlp += 1
                x = x + y
            return x, (nc["k"], nc["v"], jnp.stack(new_h), jnp.stack(new_conv))

        x, (ck, cv, ch, cconv) = self._scan(
            body, x, (trunk, cache["k"], cache["v"], cache["h"], cache["conv"])
        )
        return x, {"k": ck, "v": cv, "h": ch, "conv": cconv}

    def _rwkv_decode(self, trunk, cache, x):
        cfg = self.cfg

        def body(x, inputs):
            lp, s, sh_tm, sh_cm = inputs
            h = _norm(lp["tm_norm"], x, cfg.norm_eps)
            y, st = L.rwkv_step(lp["tm"], cfg, h, {"s": s, "shift": sh_tm})
            x = x + y
            h = _norm(lp["cm_norm"], x, cfg.norm_eps)
            x = x + L.rwkv_channel_mix(lp["cm"], h[:, 0], sh_cm)[:, None]
            return x, (st["s"], st["shift"], h[:, 0])

        x, (s, sh_tm, sh_cm) = self._scan(
            body, x, (trunk, cache["s"], cache["shift_tm"], cache["shift_cm"])
        )
        return x, {"s": s, "shift_tm": sh_tm, "shift_cm": sh_cm}

    def _encdec_decode(self, trunk, cache, x, positions, pos):
        cfg = self.cfg
        x = x + _sinusoidal(positions, cfg.d_model)

        def body(x, inputs):
            lp, lk, lv, xk, xv = inputs
            h, nc = L.attention_apply(
                lp["self_attn"], cfg, _norm(lp["ln1"], x, cfg.norm_eps), positions,
                use_rope=False, cache={"k": lk, "v": lv}, cache_pos=pos,
            )
            x = x + h
            # cross attention against precomputed encoder K/V
            h = _norm(lp["ln2"], x, cfg.norm_eps)
            q = L._split_heads(h @ lp["cross_attn"]["wq"], cfg.n_heads)
            scores = L.gqa_scores(q, xk, cfg.n_kv_heads).astype(jnp.float32)
            probs = jax.nn.softmax(scores, axis=-1).astype(L.COMPUTE_DTYPE)
            o = L.gqa_combine(probs, xv)
            bsz = x.shape[0]
            x = x + o.reshape(bsz, 1, -1) @ lp["cross_attn"]["wo"]
            x = x + L.mlp_apply(lp["mlp"], _norm(lp["ln3"], x, cfg.norm_eps))
            return x, (nc["k"], nc["v"])

        x, (ck, cv) = self._scan(
            body, x, (trunk, cache["k"], cache["v"], cache["xk"], cache["xv"])
        )
        return x, {"k": ck, "v": cv, "xk": cache["xk"], "xv": cache["xv"]}

    # ================================================================ inputs

    def input_specs(self, batch_size: int, seq_len: int) -> dict:
        """ShapeDtypeStruct stand-ins for one training batch."""
        cfg = self.cfg
        specs = {
            "tokens": jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct((batch_size, seq_len), jnp.int32),
        }
        if cfg.family == "vlm":
            specs["frontend_embeds"] = jax.ShapeDtypeStruct(
                (batch_size, cfg.frontend_tokens, cfg.d_model), L.COMPUTE_DTYPE
            )
        if cfg.family == "encdec":
            specs["frontend_embeds"] = jax.ShapeDtypeStruct(
                (batch_size, cfg.encoder_seq_len, cfg.d_model), L.COMPUTE_DTYPE
            )
        return specs
