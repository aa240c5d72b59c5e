"""The dense decoder family: its plain float32 reference, weight draws,
widths and model FLOPs.

Written from the published description (pre-norm RMSNorm decoder, grouped
query attention with rotary positions in the rotate-half form, optional
q/k/v biases, SwiGLU MLP, tied or untied output head), in ``jax.numpy``
under ``jax.default_matmul_precision("highest")``, one sequence and one
layer at a time so that a 3.8B model fits one chip beside its weights.
It imports nothing of the program under test. Its hyperparameters come
from the configuration file; its weights are those ``chipbench.weights``
drew, upcast to float32 layer by layer.

``precision="fp8"`` is the control: every weight matrix product takes
both operands rounded to float8 e4m3 (per-row scales for activations,
per-output-channel scales for weights), the step below the bfloat16 the
configurations state. Attention and norms stay float32.

Every dense leaf has a shared draw (``chipbench.weights``) and every dense
width is one the harness checks for any family, so ``DRAWS`` and
``WIDTHS`` are empty; the attention rotates (``ROTARY``), so a dense
configuration file states ``rope_theta``; the model FLOPs are
``chipbench.flops``' count.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import decode_flops, prefill_flops  # noqa: F401

DRAWS: dict = {}
WIDTHS: dict = {}
ROTARY = True

FP8_MAX = 448.0  # largest finite float8 e4m3 value
VOCAB_BLOCK = 16384


def _bucket(n: int, least: int = 128) -> int:
    """Padded length: the next power of two, so a few shapes compile."""
    size = least
    while size < n:
        size *= 2
    return size


def _fp8(x, axis: int):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, fp8: bool):
    """x: (..., d_in) @ w: (d_in, d_out), both float32."""
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return x @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, positions, theta: float, rot: int):
    """Rotate-half RoPE on the first ``rot`` dims of each head.
    x: (S, H, D); positions: (S,)."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / rot)
    ang = positions[:, None].astype(jnp.float32) * inv  # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


class DenseReference:
    """Final-normed hidden states and head logits of one sequence."""

    def __init__(self, cfg: dict, params, precision: str = "float32"):
        if precision not in ("float32", "fp8"):
            raise ValueError(f"unknown reference precision {precision!r}")
        self.cfg = cfg
        self.params = params
        self.fp8 = precision == "fp8"
        self.heads = int(cfg["num_attention_heads"])
        self.kv_heads = int(cfg["num_key_value_heads"])
        self.head_dim = int(cfg.get("head_dim")
                            or cfg["hidden_size"] // self.heads)
        self.rot = int(self.head_dim * cfg.get("partial_rotary_factor", 1.0))
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.n_layers = int(cfg["num_hidden_layers"])
        self.vocab = int(cfg["vocab_size"])
        self._layer = jax.jit(self._layer_fn)
        self._final = jax.jit(self._final_fn)
        self._head_block = jax.jit(self._head_block_fn,
                                   static_argnames=("block",))
        self._head_at = jax.jit(self._head_at_fn)

    def _head(self):
        if self.cfg["tie_word_embeddings"]:
            return self.params["embed"]  # (V, d): rows are output channels
        return self.params["head"].T

    # ---------------------------------------------------------------- trunk

    def _layer_fn(self, x, layers, i, positions):
        lp = jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)
            .astype(jnp.float32), layers)
        s = x.shape[0]
        a = lp["attn"]
        h = _rms(x, lp["attn_norm"]["w"], self.eps)
        q, k, v = (_mm(h, a[w], self.fp8) for w in ("wq", "wk", "wv"))
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q.reshape(s, self.heads, self.head_dim), positions,
                  self.theta, self.rot)
        k = _rope(k.reshape(s, self.kv_heads, self.head_dim), positions,
                  self.theta, self.rot)
        v = v.reshape(s, self.kv_heads, self.head_dim)
        group = self.heads // self.kv_heads
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        scores = jnp.einsum("shd,thd->hst", q, k) / np.sqrt(self.head_dim)
        causal = positions[None, :] <= positions[:, None]  # (S, T)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        o = jnp.einsum("hst,thd->shd", probs, v).reshape(s, -1)
        x = x + _mm(o, a["wo"], self.fp8)
        m = lp["mlp"]
        h = _rms(x, lp["mlp_norm"]["w"], self.eps)
        y = jax.nn.silu(_mm(h, m["wg"], self.fp8)) * _mm(h, m["wi"], self.fp8)
        return x + _mm(y, m["wo"], self.fp8)

    def _final_fn(self, x, rows, w):
        return _rms(x[rows], w, self.eps)

    def hidden(self, tokens, rows):
        """Final-normed hidden states at ``rows`` of the sequence
        ``tokens``: (len(rows), d) float32. Causal, so padding the
        sequence at its end leaves every earlier row as it was."""
        tokens = np.asarray(tokens, np.int32)
        rows = np.asarray(rows, np.int32)
        s = _bucket(len(tokens))
        padded = np.zeros(s, np.int32)
        padded[:len(tokens)] = tokens
        sel = np.zeros(_bucket(len(rows), 8), np.int32)
        sel[:len(rows)] = rows
        with jax.default_matmul_precision("highest"):
            x = self.params["embed"][jnp.asarray(padded)].astype(jnp.float32)
            positions = jnp.arange(s, dtype=jnp.int32)
            for i in range(self.n_layers):
                x = self._layer(x, self.params["layers"], i, positions)
            xn = self._final(x, jnp.asarray(sel),
                             self.params["final_norm"]["w"])
        return xn[:len(rows)]

    # ----------------------------------------------------------------- head

    def _head_block_fn(self, xn, head, start, *, block):
        w = jax.lax.dynamic_slice_in_dim(head, start, block, axis=0)
        w = w.astype(jnp.float32)
        logits = _mm(xn, w.T, self.fp8)  # (n, block)
        idx = start + jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.max(logits, axis=-1), idx

    def best(self, xn):
        """Largest logit of each row and its token (the lowest index among
        equal maxima), over the whole vocabulary in blocks."""
        head = self._head()
        block = min(VOCAB_BLOCK, self.vocab)
        best = ids = None
        with jax.default_matmul_precision("highest"):
            for start in range(0, self.vocab, block):
                # the last block is clamped to end at the vocabulary's end;
                # the overlap repeats entries, which cannot change a maximum
                start = min(start, self.vocab - block)
                m, i = self._head_block(xn, head, start, block=block)
                if best is None:
                    best, ids = m, i
                else:
                    better = m > best
                    best = jnp.where(better, m, best)
                    ids = jnp.where(better, i, ids)
        return np.asarray(best), np.asarray(ids)

    def _head_at_fn(self, xn, head, ids):
        w = head[ids].astype(jnp.float32)  # (n, d)
        return jnp.sum(xn * w, axis=-1)

    def logit_at(self, xn, ids):
        """Float32 logit of token ``ids[r]`` at row r."""
        with jax.default_matmul_precision("highest"):
            return np.asarray(self._head_at(
                xn, self._head(), jnp.asarray(np.asarray(ids, np.int32))))
