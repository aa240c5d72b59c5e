"""Operations and bytes the served work needs, from a configuration's
shapes alone, whatever code computes it. The layer formulas are the dense
family's; ``chipbench/reference/dense.py`` states them as its model FLOPs.

A multiply-add counts as two operations. Model FLOPs count the weight
matrix products and the attention products (scores and the weighted sum)
over the keys a token really attends to; norms, rotary positions and
softmax are left out. The embedding is a gather and costs no FLOPs.
"""

from __future__ import annotations


def _dims(cfg: dict) -> tuple[int, int, int, int, int, int]:
    d = int(cfg["hidden_size"])
    hq = int(cfg["num_attention_heads"])
    hkv = int(cfg["num_key_value_heads"])
    hd = int(cfg.get("head_dim") or d // hq)
    return d, hq, hkv, hd, int(cfg["intermediate_size"]), int(cfg["num_hidden_layers"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one decoder layer's matrix products: q, k, v, o and the
    three SwiGLU matrices."""
    d, hq, hkv, hd, ff, _ = _dims(cfg)
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d + 3 * d * ff


def head_params(cfg: dict) -> int:
    return int(cfg["hidden_size"]) * int(cfg["vocab_size"])


def attention_flops(cfg: dict, keys: int) -> int:
    """Scores and weighted sum of one token over ``keys`` keys, all layers."""
    _, hq, _, hd, _, n = _dims(cfg)
    return 4 * n * hq * hd * keys


def decode_flops(cfg: dict, contexts) -> int:
    """One decode step of tokens that attend to ``contexts`` keys each
    (their position plus one), with the output head for every token."""
    contexts = list(contexts)
    _, _, _, _, _, n = _dims(cfg)
    per_token = 2 * (n * layer_matmul_params(cfg) + head_params(cfg))
    return len(contexts) * per_token + sum(attention_flops(cfg, c) for c in contexts)


def prefill_flops(cfg: dict, prompt_len: int) -> int:
    """Prefill of a prompt: the trunk over every prompt token but the last,
    which the first decode step takes, causal attention over the earlier
    tokens, and no output head (no prompt position's logits are needed)."""
    _, _, _, _, _, n = _dims(cfg)
    t = max(prompt_len - 1, 0)
    trunk = 2 * n * layer_matmul_params(cfg) * t
    # token j attends to j + 1 keys: sum over j < t is t (t + 1) / 2
    return trunk + attention_flops(cfg, t * (t + 1) // 2)


def weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Bytes of the served weights (matrices, head or tied embedding)."""
    _, _, _, _, _, n = _dims(cfg)
    return bytes_per_param * (n * layer_matmul_params(cfg) + head_params(cfg))


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    """Key and value bytes one token keeps in the cache, all layers."""
    _, _, hkv, hd, _, n = _dims(cfg)
    return 2 * n * hkv * hd * bytes_per_value


def greedy_sample_cost(rows: int, vocab: int) -> tuple[int, int]:
    """(operations, bytes) of greedy sampling over (rows, vocab) float32
    logits: each logit read once and compared once; one id written a row."""
    return rows * vocab, rows * vocab * 4 + rows * 4


def least_time_s(ops: float, nbytes: float, peak_ops: float, peak_bw: float) -> float:
    """Roofline floor: the larger of the compute and the memory time."""
    return max(ops / peak_ops, nbytes / peak_bw)
