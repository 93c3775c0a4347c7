"""GQA attention: chunked (memory-efficient) train/prefill path + decode path.

Design notes (see DESIGN.md §6):
- The q-chunk loop is a *python* loop, i.e. fully unrolled in HLO. This keeps
  XLA's `cost_analysis()` honest (while-loop bodies are counted once) and the
  layer-level `lax.scan` amortizes the HLO size. Memory stays O(S * chunk).
- ATTN_LOCAL restricts the key range per q-chunk with *static* slice bounds, so
  sliding-window archs (mixtral / gemma2-local / griffin) get true
  O(S * (window + chunk)) compute — this is what makes long_500k viable.
- Logit softcap (gemma2) is the paper's ALU `clip` pattern fused as an epilogue.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import Spec, rms_norm, apply_rope, softcap
from repro.sharding import lshard

NEG_INF = -2.0e38


def attn_specs(cfg: ModelConfig) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.n_heads, cfg.n_kv_heads
    s = {
        "wq": Spec((d, nq, hd), ("d_model", "heads", "head_dim")),
        "wk": Spec((d, nkv, hd), ("d_model", "kv_heads", "head_dim")),
        "wv": Spec((d, nkv, hd), ("d_model", "kv_heads", "head_dim")),
        "wo": Spec((nq, hd, d), ("heads", "head_dim", "d_model")),
    }
    if cfg.qkv_bias:
        s["bq"] = Spec((nq, hd), ("heads", "head_dim"), "zeros")
        s["bk"] = Spec((nkv, hd), ("kv_heads", "head_dim"), "zeros")
        s["bv"] = Spec((nkv, hd), ("kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm:
        s["q_norm"] = Spec((hd,), ("head_dim",), "zeros")
        s["k_norm"] = Spec((hd,), ("head_dim",), "zeros")
    return s


def _project_qkv(p, x, cfg: ModelConfig, sin, cos):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if sin is not None:
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    q = lshard(q, "batch", "seq", "heads", "head_dim")
    k = lshard(k, "batch", "seq", "kv_heads", "head_dim")
    v = lshard(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def _sdpa_block(q, k, v, mask, scale, cap):
    """q (B,c,H,hd) vs k/v (B,L,KV,hd); mask (c,L) bool. GQA via reshape."""
    B, c, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, c, KV, G, hd)
    logits = jnp.einsum("bckgh,blkh->bkgcl", qg.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    logits = softcap(logits, cap)
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgcl,blkh->bckgh", w, v.astype(jnp.float32))
    return out.reshape(B, c, H, hd).astype(q.dtype)


def attention_full(p, x, cfg: ModelConfig, sin, cos, *, local: bool):
    """Train / prefill attention over the full sequence, q-chunked."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, sin, cos)
    # repeat_kv (default): expand GQA kv to full heads so every attention
    # tensor is uniformly heads-sharded. Without it, GSPMD's (kv, group)
    # regroup of a heads-sharded tensor replicates K/V across the mesh
    # ("involuntary full rematerialization") — measured in EXPERIMENTS.md
    # §Perf. The kv cache (decode path) stays GQA-compact either way.
    ka, va = k, v
    if cfg.repeat_kv and cfg.n_kv_heads < cfg.n_heads:
        g = cfg.n_heads // cfg.n_kv_heads
        ka = lshard(jnp.repeat(k, g, axis=2), "batch", "seq", "heads", "head_dim")
        va = lshard(jnp.repeat(v, g, axis=2), "batch", "seq", "heads", "head_dim")
    scale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim ** -0.5
    window = cfg.sliding_window if local else None
    chunk = min(cfg.attn_chunk, S)

    outs = []
    prev = None
    for qs in range(0, S, chunk):
        c = min(chunk, S - qs)            # final chunk may be short
        qpos = qs + jnp.arange(c)
        if window is not None:
            # static key range covering [qs - window + 1, qs + chunk)
            ks = max(0, (qs - window + 1) // chunk * chunk)
        else:
            ks = 0
        ke = qs + c
        kk, vv = ka[:, ks:ke], va[:, ks:ke]
        kpos = ks + jnp.arange(ke - ks)
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        qc = q[:, qs:qs + c]
        if prev is not None:
            # chain chunks: without this, XLA is free to schedule every
            # chunk's (c, S) f32 score tensor concurrently — at 32k that is
            # tens of GiB of simultaneously-live temporaries per chip
            qc, _ = jax.lax.optimization_barrier((qc, prev))
        prev = _sdpa_block(qc, kk, vv, mask, scale, cfg.attn_logit_softcap)
        outs.append(prev)
    out = jnp.concatenate(outs, axis=1)
    out = lshard(out, "batch", "seq", "heads", "head_dim")
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)), (k, v)


def cache_len(cfg: ModelConfig, seq_len: int, *, local: bool) -> int:
    """KV cache length: sliding-window layers only keep `window` entries."""
    if local and cfg.sliding_window is not None:
        return min(seq_len, cfg.sliding_window)
    return seq_len


# --- quantized KV cache (beyond-paper; halves decode cache bytes) ---------
KV_QSCALE = 16.0     # symmetric fixed-scale int8: q = round(x * 127/16)


def kv_cache_dtype(cfg: ModelConfig):
    return jnp.int8 if cfg.kv_cache_dtype == "int8" else jnp.dtype(cfg.dtype)


def quantize_kv(cfg: ModelConfig, x):
    if cfg.kv_cache_dtype != "int8":
        return x
    scaled = jnp.clip(x.astype(jnp.float32) * (127.0 / KV_QSCALE), -127, 127)
    return jnp.round(scaled).astype(jnp.int8)


def dequantize_kv(cfg: ModelConfig, x, dtype):
    if cfg.kv_cache_dtype != "int8":
        return x
    return (x.astype(jnp.float32) * (KV_QSCALE / 127.0)).astype(dtype)


def init_kv_cache(cfg: ModelConfig, batch: int, seq_len: int, *, local: bool,
                  dtype) -> dict:
    L = cache_len(cfg, seq_len, local=local)
    shp = (batch, L, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


def attention_decode(p, x, cache: dict, pos: jax.Array, cfg: ModelConfig,
                     sin, cos, *, local: bool):
    """One-token decode: x (B,1,d); cache {"k","v"} (B,L,KV,hd); pos scalar.

    The cache is treated as *full* (steady-state decode at context length L,
    per the assigned decode_32k / long_500k shapes): new K/V overwrite the slot
    at `pos % L` (ring buffer for local layers).
    """
    q, k, v = _project_qkv(p, x, cfg, sin, cos)
    L = cache["k"].shape[1]
    slot = (pos % L).astype(jnp.int32)
    ck = jax.lax.dynamic_update_slice(cache["k"], quantize_kv(cfg, k),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache["v"], quantize_kv(cfg, v),
                                      (0, slot, 0, 0))
    scale = cfg.query_scale if cfg.query_scale is not None else cfg.head_dim ** -0.5
    # slots beyond the written prefix are masked; at steady state (pos >= L,
    # the assigned decode_32k/long_500k regime) every slot is valid
    valid = jnp.minimum(pos.astype(jnp.int32) + 1, L)
    mask = (jnp.arange(L, dtype=jnp.int32) < valid)[None, :]
    out = _sdpa_block(q, dequantize_kv(cfg, ck, q.dtype),
                      dequantize_kv(cfg, cv, q.dtype), mask, scale,
                      cfg.attn_logit_softcap)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, {"k": ck, "v": cv}
