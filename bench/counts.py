"""Operations and bytes of the served model's work, from shapes and live
lengths alone: the same numbers whatever implements the step.

``arch`` is the ``arch`` block of a configuration file (dense decoder:
GQA attention, SwiGLU feed-forward, separate output head). FLOPs count a
multiply-add as two. Padding is never counted: a prefill counts each
request's valid tokens, a decode step counts the active slots at their
live context lengths.
"""

from __future__ import annotations

from typing import Iterable

BF16 = 2  # bytes per served weight and cache element


def _dims(arch: dict):
    d, L = arch["d_model"], arch["num_layers"]
    nq, nkv = arch["num_heads"], arch["num_kv_heads"]
    hd = arch.get("head_dim") or d // nq
    return d, L, nq, nkv, hd, arch["d_ff"], arch["vocab_size"]


def layer_matmul_params(arch: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, _, nq, nkv, hd, ff, _ = _dims(arch)
    return d * nq * hd + 2 * d * nkv * hd + nq * hd * d + 3 * d * ff


def weight_bytes(arch: dict) -> int:
    """Bytes a decode step must read at least: every layer's weights and
    norms, the final norm and the output head. The embedding table is not
    read whole (only the gathered rows, counted per token)."""
    d, L, _, _, _, _, V = _dims(arch)
    return BF16 * (L * (layer_matmul_params(arch) + 2 * d) + d + d * V)


def prefill_flops(arch: dict, valid_lens: Iterable[int]) -> float:
    """One prefill dispatch: each request's valid tokens through every
    layer, causal attention over its own prefix, and the output head on its
    last token."""
    d, L, nq, _, hd, _, V = _dims(arch)
    per_tok = 2 * L * layer_matmul_params(arch)
    total = 0.0
    for n in valid_lens:
        n = int(n)
        total += per_tok * n
        total += L * 4 * nq * hd * n * (n + 1) / 2
        total += 2 * d * V
    return total


def decode_flops(arch: dict, contexts: Iterable[int]) -> float:
    """One decode step: one token per active slot, attending over its
    ``context`` positions (the new one included), and the output head."""
    d, L, nq, _, hd, _, V = _dims(arch)
    per_tok = 2 * L * layer_matmul_params(arch) + 2 * d * V
    return sum(per_tok + L * 4 * nq * hd * int(c) for c in contexts)


def decode_bytes(arch: dict, contexts: Iterable[int]) -> float:
    """Least bytes of one decode step: the weights once, the K/V rows of
    each active slot's cached positions, the new K/V row it writes, and its
    embedding row."""
    d, L, _, nkv, hd, _, _ = _dims(arch)
    row = L * 2 * nkv * hd * BF16         # K and V of one position
    total = float(weight_bytes(arch))
    for c in contexts:
        total += row * int(c) + d * BF16  # (c - 1) read + 1 written
    return total


def least_seconds(flops: float, nbytes: float, chips: int, peak: dict):
    """Least time the chips could take, and which bound sets it."""
    t_c = flops / (chips * peak["bf16_flops_per_s"])
    t_m = nbytes / (chips * peak["hbm_bytes_per_s"])
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
