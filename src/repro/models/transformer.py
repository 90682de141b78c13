"""Decoder-only transformer covering the dense / moe / vlm families.

Layers are stacked and scanned (compile time O(1) in depth). Per-layer
metadata (sliding-window size) rides along as scan inputs so hybrid
global/window stacks share one scan. The VLM frontend is a stub: precomputed
patch embeddings arrive in the batch and are concatenated ahead of the text
embeddings (DESIGN.md §4)."""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import moe as moe_lib
from repro.models.api import Model
from repro.models.common import (
    Spec, attn_qkv, attn_specs, attention_decode_auto,
    attention_prefill, attention_train, axes_tree, cache_update, chunked_loss,
    embed_specs, embed_tokens, glu_apply, glu_specs, init_tree,
    last_valid_slice, lm_head, rmsnorm, rope, stacked, DEFAULT_DTYPE,
)


def _layer_specs(cfg: ModelConfig, nq: int, nkv: int, hd: int) -> Dict[str, Any]:
    specs: Dict[str, Any] = {
        "ln1": Spec((cfg.d_model,), ("embed",), "ones"),
        "attn": attn_specs(cfg.d_model, nq, nkv, hd, cfg.qkv_bias),
        "ln2": Spec((cfg.d_model,), ("embed",), "ones"),
    }
    if cfg.family == "moe":
        specs["moe"] = moe_lib.moe_specs(cfg.d_model, cfg.d_ff, cfg.num_experts)
    else:
        specs["ffn"] = glu_specs(cfg.d_model, cfg.d_ff)
    return specs


def _layer_windows(cfg: ModelConfig) -> list:
    """Per-layer window sizes (0 = full attention), as static Python ints."""
    w = [cfg.window] * cfg.num_layers
    for i in cfg.global_layers:
        w[i] = 0
    return w


def build(cfg: ModelConfig, mesh, rules, *, remat: str = "full",
          q_block: int = 512, k_block: int = 1024) -> Model:
    tp = mesh.shape.get("model", 1)
    pd = cfg.padded(tp)
    nq, nkv, hd, V = pd.num_q_heads, pd.num_kv_heads, pd.head_dim, pd.vocab_size
    d, L = cfg.d_model, cfg.num_layers
    eps = cfg.norm_eps
    from repro.distributed.sharding import norm_axes
    batch_axes = tuple(a for a in norm_axes(rules.get("batch"))
                       if a in mesh.shape)
    moe_dims = None
    if cfg.family == "moe":
        moe_dims = moe_lib.MoEDims(cfg.num_experts, cfg.num_experts_per_tok,
                                   cfg.moe_capacity_factor, d, cfg.d_ff)

    specs = {
        "embed": embed_specs(V, d),
        "layers": stacked(_layer_specs(cfg, nq, nkv, hd), L),
    }
    static_windows = _layer_windows(cfg)
    windows = jnp.asarray(static_windows, jnp.int32)

    def _ffn(lp, h):
        if cfg.family == "moe":
            return moe_lib.moe_apply(
                lp["moe"], h, moe_dims, mesh=mesh, batch_axes=batch_axes,
                fsdp_axis=_axis(rules, "fsdp"), ffn2d_axis=_axis(rules, "expert_ffn"))
        return glu_apply(lp["ffn"], h), jnp.float32(0.0)

    # ---------------- train ----------------
    def layer_train(x, lp, window):
        h = rmsnorm(x, lp["ln1"], eps)
        q, k, v = attn_qkv(lp["attn"], h, nq, nkv, hd)
        S = x.shape[1]
        pos = jnp.arange(S)[None, :]
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
        o = attention_train(q, k, v, causal=True, window=window)
        x = x + shard(o.reshape(*x.shape[:2], nq * hd) @ lp["attn"]["wo"],
                      "batch", "seq", "embed")
        h = rmsnorm(x, lp["ln2"], eps)
        y, aux = _ffn(lp, h)
        x = x + shard(y, "batch", "seq", "embed")
        return x, aux

    if remat == "full":
        layer_train = jax.checkpoint(layer_train,
                                     policy=jax.checkpoint_policies.nothing_saveable)
    elif remat == "dots":
        layer_train = jax.checkpoint(
            layer_train, policy=jax.checkpoint_policies.checkpoint_dots)

    def _backbone_train(params, x):
        def body(carry, xs):
            x, aux = carry
            lp, window = xs
            x, a = layer_train(x, lp, window)
            return (x, aux + a), None
        (x, aux), _ = lax.scan(body, (x, jnp.float32(0.0)),
                               (params["layers"], windows))
        return x, aux

    def _embed_input(params, batch):
        x = embed_tokens(params["embed"], batch["tokens"])
        if cfg.frontend == "vision" and "prefix_embeddings" in batch:
            pre = batch["prefix_embeddings"].astype(x.dtype)
            x = jnp.concatenate([shard(pre, "batch", None, "embed"), x], axis=1)
        return x

    def loss_fn(params, batch):
        x = _embed_input(params, batch)
        x, aux = _backbone_train(params, x)
        n_text = batch["tokens"].shape[1]
        x = x[:, -n_text:]   # loss over text positions only (vlm prefix excluded)
        ce = chunked_loss(params["embed"], x, batch["labels"], eps)
        return ce + 0.01 * aux

    # ---------------- prefill ----------------
    cp = rules.get("seq") == "model"   # context-parallel prefill (§Perf)

    def _cp_attention(q, k, v, window):
        """Sequence-sharded attention: each model-rank holds an S/tp slice;
        K/V are all-gathered once per layer (bytes << the TP activation
        all-reduces this replaces — EXPERIMENTS.md §Perf granite)."""
        from jax.sharding import PartitionSpec as P
        bspec = batch_axes if batch_axes else None
        spec = P(bspec, "model", None, None)

        def body(ql, kl, vl):
            kf = lax.all_gather(kl, "model", axis=1, tiled=True)
            vf = lax.all_gather(vl, "model", axis=1, tiled=True)
            off = lax.axis_index("model") * ql.shape[1]
            return attention_prefill(ql, kf, vf, causal=True, window=window,
                                     q_block=min(q_block, ql.shape[1]),
                                     k_block=k_block, q_offset=off)

        return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)

    def prefill(params, batch, max_len: Optional[int] = None):
        x = _embed_input(params, batch)
        B, S, _ = x.shape
        Smax = max_len or S
        # per-sample valid prompt length (right-padded batch, serving length
        # ladder). Causal masking already keeps real rows clean; kv_valid
        # additionally zeroes the junk rows' attention mass.
        vl = batch.get("lengths")

        def body(x, xs):
            lp, window = xs
            attn_p = lp["attn"]
            if cp:
                # weights stored TP-sharded; gathered per layer (cheaper on
                # the wire than per-token activation all-reduces at 32k seq)
                attn_p = jax.tree.map(lambda w: shard(w, *((None,) * w.ndim)),
                                      attn_p)
                lp = dict(lp, attn=attn_p,
                          ffn=jax.tree.map(
                              lambda w: shard(w, *((None,) * w.ndim)),
                              lp["ffn"]) if "ffn" in lp else lp.get("ffn"))
            h = rmsnorm(x, lp["ln1"], eps)
            q, k, v = attn_qkv(attn_p, h, nq, nkv, hd)
            pos = jnp.arange(S)[None, :]
            q = rope(q, pos, cfg.rope_theta)
            k = rope(k, pos, cfg.rope_theta)
            with jax.named_scope("attention"):
                if cp:
                    o = _cp_attention(q, k, v, window)
                else:
                    o = attention_prefill(q, k, v, causal=True, window=window,
                                          q_block=q_block, k_block=k_block,
                                          kv_valid=vl)
            x = x + shard(o.reshape(B, S, nq * hd) @ attn_p["wo"],
                          "batch", "seq", "embed")
            h2 = rmsnorm(x, lp["ln2"], eps)
            with jax.named_scope("mlp"):
                y, _ = _ffn(lp, h2)
            x = x + shard(y, "batch", "seq", "embed")
            if Smax > S:
                pad = [(0, 0), (0, Smax - S), (0, 0), (0, 0)]
                k, v = jnp.pad(k, pad), jnp.pad(v, pad)
            return x, (k, v)

        x, (ks, vs) = lax.scan(body, x, (params["layers"], windows))
        x_last = x[:, -1:, :] if vl is None else last_valid_slice(x, vl)
        logits = lm_head(params["embed"], x_last, eps)[:, 0]
        lengths = (jnp.full((B,), S, jnp.int32) if vl is None
                   else vl.astype(jnp.int32))
        cache = {"k": ks, "v": vs, "lengths": lengths}
        return logits, cache

    # ---------------- decode ----------------
    # kernel-backend dispatch needs a static window; when every layer shares
    # one window size (the common case — smollm/granite/qwen are all-global)
    # the scanned per-layer window is bypassed with the static value
    uniform_window = (static_windows[0]
                      if len(set(static_windows)) == 1 else None)

    def decode_step(params, cache, tokens, lengths):
        """tokens: [B,1]; lengths: [B] current context length per sample.
        Named scopes (``attention``, ``kv_update``, ``mlp``, ``lm_head``)
        tie the compiled program's ops to the phase they came from."""
        x = embed_tokens(params["embed"], tokens)
        B = x.shape[0]

        def body(x, xs):
            lp, window, k_l, v_l = xs
            h = rmsnorm(x, lp["ln1"], eps)
            q, k, v = attn_qkv(lp["attn"], h, nq, nkv, hd)
            q = rope(q, lengths[:, None], cfg.rope_theta)
            k = rope(k, lengths[:, None], cfg.rope_theta)
            with jax.named_scope("kv_update"):
                k_l, v_l = cache_update(k_l, v_l, k, v, lengths)
            with jax.named_scope("attention"):
                o = attention_decode_auto(
                    q, k_l, v_l, lengths + 1,
                    window=window if uniform_window is None
                    else uniform_window)
            x = x + shard(o.reshape(B, 1, nq * hd) @ lp["attn"]["wo"],
                          "batch", None, "embed")
            h2 = rmsnorm(x, lp["ln2"], eps)
            with jax.named_scope("mlp"):
                y, _ = _ffn(lp, h2)
            x = x + shard(y, "batch", None, "embed")
            return x, (k_l, v_l)

        x, (ks, vs) = lax.scan(body, x,
                               (params["layers"], windows, cache["k"], cache["v"]))
        with jax.named_scope("lm_head"):
            logits = lm_head(params["embed"], x, eps)[:, 0]
        new_cache = {"k": ks, "v": vs, "lengths": lengths + 1}
        return logits, new_cache

    def init_cache(batch: int, max_len: int):
        # distinct buffers per leaf: the serving engine donates the cache
        # into its jitted scatter/decode, and XLA rejects aliased donations
        shape = (L, batch, max_len, nkv, hd)
        return {"k": jnp.zeros(shape, DEFAULT_DTYPE),
                "v": jnp.zeros(shape, DEFAULT_DTYPE),
                "lengths": jnp.zeros((batch,), jnp.int32)}

    def cache_axes(batch: int, max_len: int):
        # "seq" resolves to None in standard rules (kv_heads takes model);
        # under context-parallel prefill it resolves to model (and the
        # duplicate mesh-axis use drops kv_heads) — see sharding.spec()
        kv = (None, "batch", "seq", "kv_heads", None)
        return {"k": kv, "v": kv, "lengths": ("batch",)}

    return Model(
        cfg=cfg,
        init=lambda rng: init_tree(rng, specs),
        param_axes=axes_tree(specs),
        loss_fn=loss_fn,
        prefill=prefill,
        decode_step=decode_step,
        init_cache=init_cache,
        cache_axes=cache_axes,
        # moe excluded from prompt padding: junk tokens contend for expert
        # capacity and can displace real tokens' expert assignments
        extras={"padded": pd, "prompt_pad": cfg.family != "moe"},
    )


def _axis(rules, name):
    v = rules.get(name)
    if isinstance(v, tuple):
        v = v[0] if v else None
    return v
