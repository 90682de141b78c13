"""Plain ``jax.numpy`` forward of a dense decoder (GQA attention with
rotary positions, SwiGLU feed-forward, RMSNorm, separate output head),
written from the published description, in float32 at
``default_matmul_precision("highest")``.

Weights are drawn from the seed in the same order and with the same scales
as the served model draws its own (normal, 1/sqrt(fan_in), stored in
bfloat16), so the two compute on identical numbers without sharing any
array. The layers run one at a time on the first device; the stacked
weights are spread over all devices when they do not fit one.

``mode`` selects the precision of every weight matmul: ``"f32"`` is the
reference; ``"int8"`` (symmetric, per row of activations and per column of
weights, integer accumulation) and ``"fp8"`` (e4m3 with the same scaling)
are the lower precisions used as the comparison's control.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MODES = ("f32", "int8", "fp8")


def dims(arch: dict):
    d, nq = arch["d_model"], arch["num_heads"]
    hd = arch.get("head_dim") or d // nq
    return (d, arch["num_layers"], nq, arch["num_kv_heads"], hd,
            arch["d_ff"], arch["vocab_size"])


def leaf_specs(arch: dict) -> List[Tuple[str, tuple, str, int]]:
    """(name, shape, init, fan_in) in the order the served model flattens
    its parameter tree (dict keys sorted at every level)."""
    d, L, nq, nkv, hd, ff, V = dims(arch)
    return [
        ("embedding", (V, d), "normal", 1),
        ("final_norm", (d,), "ones", 0),
        ("head", (d, V), "normal", d),
        ("wk", (L, d, nkv * hd), "normal", d),
        ("wo", (L, nq * hd, d), "normal", nq * hd),
        ("wq", (L, d, nq * hd), "normal", d),
        ("wv", (L, d, nkv * hd), "normal", d),
        ("ffn_wg", (L, d, ff), "normal", d),
        ("ffn_wi", (L, d, ff), "normal", d),
        ("ffn_wo", (L, ff, d), "normal", ff),
        ("ln1", (L, d), "ones", 0),
        ("ln2", (L, d), "ones", 0),
    ]


def _spread(shape, n: int) -> P:
    for ax, size in enumerate(shape):
        if size % n == 0 and size >= n:
            return P(*([None] * ax + ["x"]))
    return P()


def make_weights(arch: dict, seed: int, devices=None) -> Dict[str, jax.Array]:
    """bfloat16 weights from ``seed``, each spread over ``devices`` along
    its first axis that divides evenly."""
    if arch.get("family", "dense") != "dense" or arch.get("qkv_bias") \
            or arch.get("window") or arch.get("tie_embeddings"):
        raise ValueError("the reference covers dense decoders without bias, "
                         "window or tied head")
    devices = list(devices or jax.devices())
    mesh = Mesh(np.array(devices), ("x",))
    specs = leaf_specs(arch)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(specs))
    out = {}
    for key, (name, shape, init, fan) in zip(keys, specs):
        sh = NamedSharding(mesh, _spread(shape, len(devices)))
        if init == "ones":
            fn = partial(jnp.ones, shape, jnp.bfloat16)
            out[name] = jax.jit(fn, out_shardings=sh)()
        else:
            scale = 1.0 / math.sqrt(max(1, fan))

            def fn(k, shape=shape, scale=scale):
                return (jax.random.normal(k, shape, jnp.float32)
                        * scale).astype(jnp.bfloat16)
            out[name] = jax.jit(fn, out_shardings=sh)(key)
    return out


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------

def _quant(x, axis: int, mode: str):
    """Round ``x`` to the grid of ``mode`` with one scale per slice along
    ``axis`` (the contraction axis); returns (values, scale)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if mode == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s
    s = jnp.maximum(amax, 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn), s


def mm(a, w, mode: str):
    """``a [..., k] @ w [k, n]`` in the precision of ``mode``."""
    if mode == "f32":
        return a @ w
    qa, sa = _quant(a, -1, mode)
    qw, sw = _quant(w, 0, mode)
    if mode == "int8":
        acc = jnp.matmul(qa, qw, preferred_element_type=jnp.int32)
        acc = acc.astype(jnp.float32)
    else:
        acc = jnp.matmul(qa.astype(jnp.float32), qw.astype(jnp.float32))
    return acc * sa * sw


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x [S, H, D]: rotate the two halves of each head by position."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnames=("arch_t", "mode"))
def layer(x, w, arch_t, mode):
    """One decoder layer over ``x [K, S, d]`` (causal within each row)."""
    arch = dict(arch_t)
    d, _, nq, nkv, hd, _, _ = dims(arch)
    eps, theta = arch["norm_eps"], arch["rope_theta"]
    K, S, _ = x.shape
    f32 = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(S)
    h = rmsnorm(x, f32(w["ln1"]), eps)
    q = mm(h, f32(w["wq"]), mode).reshape(K, S, nq, hd)
    k = mm(h, f32(w["wk"]), mode).reshape(K, S, nkv, hd)
    v = mm(h, f32(w["wv"]), mode).reshape(K, S, nkv, hd)
    g = nq // nkv
    causal = pos[None, :] <= pos[:, None]

    def one(args):
        q1, k1, v1 = args
        q1 = rope(q1, pos, theta) / math.sqrt(hd)
        k1 = rope(k1, pos, theta)
        kq = jnp.repeat(k1, g, axis=1)          # q head h reads kv head h//g
        vq = jnp.repeat(v1, g, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q1, kq)
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vq)

    o = jax.lax.map(one, (q, k, v)).reshape(K, S, nq * hd)
    x = x + mm(o, f32(w["wo"]), mode)
    h = rmsnorm(x, f32(w["ln2"]), eps)
    y = jax.nn.silu(mm(h, f32(w["ffn_wg"]), mode)) * mm(h, f32(w["ffn_wi"]),
                                                         mode)
    return x + mm(y, f32(w["ffn_wo"]), mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _head(xr, norm, head, eps, mode):
    return mm(rmsnorm(xr, norm.astype(jnp.float32), eps),
              head.astype(jnp.float32), mode)


@jax.jit
def _take_layer(w, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                        w)


_LAYER_LEAVES = ("wk", "wo", "wq", "wv", "ffn_wg", "ffn_wi", "ffn_wo",
                 "ln1", "ln2")


def _pow2(n: int, lo: int) -> int:
    return max(lo, 1 << (int(n) - 1).bit_length())


def logits_at(arch: dict, weights, seqs: Sequence[np.ndarray],
              rows: Sequence[np.ndarray], mode: str = "f32"):
    """Logits ``[sum(len(r)), V]`` (float32, on the first device) of every
    sequence in ``seqs`` at its positions ``rows``: position ``p`` holds the
    logits that predict token ``p + 1``. Sequences are padded at the end
    (causal attention keeps the real positions exact) and batches to powers
    of two, so that a few compiled programs serve every sample."""
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}: {mode!r}")
    dev = jax.devices()[0]
    arch_t = tuple(sorted((k, v) for k, v in arch.items()
                          if isinstance(v, (int, float, str, bool))))
    S = _pow2(max(len(s) for s in seqs), 128)
    toks = np.zeros((_pow2(len(seqs), 1), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    ki = np.concatenate([np.full(len(r), i) for i, r in enumerate(rows)])
    pi = np.concatenate([np.asarray(r) for r in rows])
    n = len(ki)
    pad = _pow2(n, 256) - n
    ki, pi = np.pad(ki, (0, pad)), np.pad(pi, (0, pad))
    emb = jax.device_put(weights["embedding"], dev)
    with jax.default_matmul_precision("highest"):
        x = jnp.take(emb, jax.device_put(toks, dev), axis=0).astype(
            jnp.float32)
        del emb
        stacked = {k: weights[k] for k in _LAYER_LEAVES}
        for i in range(arch["num_layers"]):
            w = jax.device_put(_take_layer(stacked, np.int32(i)), dev)
            x = layer(x, w, arch_t, mode)
        xr = x[jax.device_put(ki, dev), jax.device_put(pi, dev)]
        out = _head(xr, jax.device_put(weights["final_norm"], dev),
                    jax.device_put(weights["head"], dev),
                    arch["norm_eps"], mode)
    return out[:n]


@jax.jit
def gap_of(logits, toks):
    """How far each chosen token's logit lies below the row's best."""
    return jnp.max(logits, -1) - jnp.take_along_axis(logits, toks[:, None],
                                                    -1)[:, 0]
