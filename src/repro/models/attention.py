"""GQA attention with RoPE, qk-norm, sliding-window, KV cache, cross-attn.

Three entry points per block:
  * ``attn_train``   — full-sequence causal (optionally windowed) attention.
  * ``attn_prefill`` — same as train but also returns the populated KV cache.
  * ``attn_decode``  — one query token against a cache, in-place cache update.

Caches are dicts {"k": [B, S, Hkv, Dh], "v": ..., plus ring metadata for
sliding windows}.  All math is einsum-based so the GSPMD partitioner shards
heads over the model axis; the Pallas flash kernel (kernels/flash_attention)
is swapped in by the launch layer on TPU via ``use_flash``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .layers import norm_spec, rms_norm
from .spec import ParamSpec

NEG_INF = -2.0 ** 30  # large-negative that survives bf16

# Launch-layer hint (set by repro.launch.steps when the arch's head counts
# divide the mesh's model axis): NamedSharding P(None, None, "model", None)
# applied to q/k/v in the training paths.  With sequence-parallel residuals
# this is the Megatron SP->TP transition — attention runs head-local over
# the full sequence instead of re-gathering seq-sharded K/V inside every
# q-chunk iteration (measured: 216 gathers/step at dsv2 train).
HEAD_SPEC = None

# Fallback for archs whose head count does NOT divide the model axis
# (qwen3/minicpm3: 40 heads on 16): K/V cannot be head-sharded, and the
# chunked-q loop would re-gather seq-sharded K/V on every iteration.
# Setting this (a replicated NamedSharding) hoists ONE gather per layer in
# front of the loop instead (§Perf Q1).
KV_GATHER_SPEC = None


def _head_shard(*ts):
    if HEAD_SPEC is None:
        return ts if len(ts) > 1 else ts[0]
    out = tuple(jax.lax.with_sharding_constraint(t, HEAD_SPEC) for t in ts)
    return out if len(out) > 1 else out[0]


# ------------------------------------------------------------------- rope
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, H, Dh]; pos: [..., S] absolute positions."""
    freqs = rope_freqs(x.shape[-1], theta)                      # [Dh/2]
    ang = pos[..., None].astype(jnp.float32) * freqs            # [..., S, Dh/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ specs
def attn_specs(cfg: ArchConfig, stacked: Optional[int], cross: bool = False) -> dict:
    pre_s = (stacked,) if stacked else ()
    pre_a = ("layers",) if stacked else ()
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    out = {
        "wq": ParamSpec(pre_s + (d, h, dh), pre_a + ("embed", "heads", None)),
        "wk": ParamSpec(pre_s + (d, hkv, dh), pre_a + ("embed", "kv_heads", None)),
        "wv": ParamSpec(pre_s + (d, hkv, dh), pre_a + ("embed", "kv_heads", None)),
        "wo": ParamSpec(pre_s + (h, dh, d), pre_a + ("heads", None, "embed")),
        "norm": norm_spec(d, pre_a, pre_s),
    }
    if cfg.qk_norm:
        out["q_norm"] = norm_spec(dh, pre_a, pre_s)
        out["k_norm"] = norm_spec(dh, pre_a, pre_s)
    if cross:
        out["xattn_gate"] = ParamSpec(pre_s + (1,), pre_a + (None,), init="zeros")
    return out


# ------------------------------------------------------------------ masks
def causal_mask(s_q: int, s_kv: int, q_offset: int = 0,
                window: Optional[int] = None) -> jnp.ndarray:
    """[s_q, s_kv] additive mask; window = sliding-window size (None = full)."""
    qpos = jnp.arange(s_q)[:, None] + q_offset
    kpos = jnp.arange(s_kv)[None, :]
    ok = kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, NEG_INF)


ATTN_Q_CHUNK = 512   # q-block size for the chunked softmax(QK^T)V path


def _sdpa_block(q, k, v, bias):
    """q: [B,Sq,H,Dh]; k/v: [B,Skv,Hkv,Dh] (GQA-expanded inside)."""
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    group = h // hkv
    q = q.reshape(b, sq, hkv, group, dh)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(dh)
    logits = logits + bias  # bias broadcasts over [B?,H?,g?,q,k]
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, dh).astype(v.dtype)


# Kernel backend switch (set by the launch layer on real TPUs): routes the
# full-sequence paths through kernels/flash_attention (pl.pallas_call).
# Off by default here — interpret mode on CPU is a Python loop.
USE_FLASH_KERNEL = False


def _sdpa(q, k, v, *, causal: bool, window=None, q_offset: int = 0,
          bias=None, chunk: int = ATTN_Q_CHUNK):
    """Memory-bounded attention: q is processed in remat'd chunks so neither
    the [Sq, Skv] mask nor the [B, H, Sq, Skv] logits ever materialize in
    full.  ``bias`` short-circuits chunking (decode-style precomputed masks).
    """
    b, sq, h, dh = q.shape
    skv = k.shape[1]
    if USE_FLASH_KERNEL and bias is None and sq > 1:
        from repro.kernels.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)
    if bias is not None:
        return _sdpa_block(q, k, v, bias)
    if sq <= chunk:
        m = (causal_mask(sq, skv, q_offset=q_offset, window=window)
             if (causal or window) else jnp.zeros((), q.dtype))
        return _sdpa_block(q, k, v, m)

    if HEAD_SPEC is None and KV_GATHER_SPEC is not None:
        # gather K/V once per layer, not once per q-chunk iteration
        k = jax.lax.with_sharding_constraint(k, KV_GATHER_SPEC)
        v = jax.lax.with_sharding_constraint(v, KV_GATHER_SPEC)
    pad = (-sq) % chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = q.shape[1] // chunk
    qb = jnp.moveaxis(q.reshape(b, nq, chunk, h, dh), 1, 0)
    offs = q_offset + jnp.arange(nq) * chunk

    @jax.checkpoint
    def block(args):
        qc, off = args
        if causal or window:
            # mask rows shifted by the block's dynamic offset
            qpos = jnp.arange(chunk)[:, None] + off
            kpos = jnp.arange(skv)[None, :]
            ok = kpos <= qpos if causal else jnp.ones((chunk, skv), bool)
            if window is not None:
                ok &= kpos > qpos - window
            m = jnp.where(ok, 0.0, NEG_INF)
        else:
            m = jnp.zeros((), jnp.float32)
        return _sdpa_block(qc, k, v, m)

    out = jax.lax.map(block, (qb, offs))
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * chunk, h, dh)
    return out[:, :sq]


def _qkv(p: dict, x: jnp.ndarray, cfg: ArchConfig, kv_x: Optional[jnp.ndarray] = None):
    src = x if kv_x is None else kv_x
    q = jnp.einsum("...d,dhk->...hk", x, p["wq"])
    k = jnp.einsum("...d,dhk->...hk", src, p["wk"])
    v = jnp.einsum("...d,dhk->...hk", src, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _proj_out(p: dict, attn: jnp.ndarray, x: jnp.ndarray, cross: bool) -> jnp.ndarray:
    out = jnp.einsum("...hk,hkd->...d", attn, p["wo"])
    if cross:
        out = out * jnp.tanh(p["xattn_gate"]).astype(out.dtype)
    return x + out


# ------------------------------------------------------------- full-seq ops
def attn_train(p: dict, x: jnp.ndarray, cfg: ArchConfig, *, causal: bool = True,
               pos_offset: int = 0) -> jnp.ndarray:
    """Self-attention over a full sequence. x: [B, S, D]."""
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q, k, v = _qkv(p, h, cfg)
    s = x.shape[-2]
    pos = jnp.arange(s) + pos_offset
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    q, k, v = _head_shard(q, k, v)
    out = _sdpa(q, k, v, causal=causal,
                window=cfg.sliding_window if causal else None)
    return _proj_out(p, out, x, cross=False)


def xattn_train(p: dict, x: jnp.ndarray, memory: jnp.ndarray, cfg: ArchConfig
                ) -> jnp.ndarray:
    """Cross-attention to ``memory`` [B, S_mem, D] (no RoPE on memory)."""
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q, k, v = _qkv(p, h, cfg, kv_x=memory)
    out = _sdpa(q, k, v, causal=False)
    return _proj_out(p, out, x, cross="xattn_gate" in p)


# ------------------------------------------------------------------- cache
def init_cache_spec(cfg: ArchConfig, batch: int, max_len: int,
                    stacked: Optional[int], dtype=jnp.bfloat16) -> dict:
    """KV cache spec. Sliding-window archs cache only the window (ring)."""
    hkv, dh = cfg.n_kv_heads, cfg.resolved_head_dim
    length = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    pre_s = (stacked,) if stacked else ()
    pre_a = ("layers",) if stacked else ()
    return {
        "k": ParamSpec(pre_s + (batch, length, hkv, dh),
                       pre_a + ("act_batch", "kv_seq", "kv_heads", None), dtype, "zeros"),
        "v": ParamSpec(pre_s + (batch, length, hkv, dh),
                       pre_a + ("act_batch", "kv_seq", "kv_heads", None), dtype, "zeros"),
    }


def attn_prefill(p: dict, x: jnp.ndarray, cfg: ArchConfig, cache: dict
                 ) -> tuple[jnp.ndarray, dict]:
    """Full-sequence attention that also fills the cache (keys post-RoPE)."""
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q, k, v = _qkv(p, h, cfg)
    s = x.shape[-2]
    pos = jnp.arange(s)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)
    out = _proj_out(p, _sdpa(q, k, v, causal=True,
                             window=cfg.sliding_window), x, cross=False)
    clen = cache["k"].shape[-3]
    keep = min(s, clen)
    # ring placement: position p lives at slot p % clen (no-op when clen >= s)
    slots = (jnp.arange(s - keep, s) % clen)
    new_cache = {
        "k": cache["k"].at[..., slots, :, :].set(
            k[..., -keep:, :, :].astype(cache["k"].dtype)),
        "v": cache["v"].at[..., slots, :, :].set(
            v[..., -keep:, :, :].astype(cache["v"].dtype)),
    }
    return out, new_cache


def attn_decode(p: dict, x: jnp.ndarray, cfg: ArchConfig, cache: dict,
                pos: jnp.ndarray) -> tuple[jnp.ndarray, dict]:
    """One-token decode. x: [B, 1, D]; pos: scalar current position.

    Sliding-window caches are rings indexed by pos % window; full caches
    write at pos.  Key invariant: cached keys already carry RoPE.
    """
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q, k, v = _qkv(p, h, cfg)
    q = apply_rope(q, pos[None], cfg.rope_theta)
    k = apply_rope(k, pos[None], cfg.rope_theta)
    clen = cache["k"].shape[-3]
    slot = (pos % clen) if cfg.sliding_window else pos
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), slot, axis=-3)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), slot, axis=-3)
    kpos_abs = jnp.arange(clen)
    if cfg.sliding_window:
        # ring: entry i holds the latest position congruent to i mod clen
        kpos_abs = jnp.where(kpos_abs <= slot, pos - slot + kpos_abs,
                             pos - slot - clen + kpos_abs)
    valid = (kpos_abs >= 0) & (kpos_abs <= pos)
    if cfg.sliding_window:
        valid &= kpos_abs > pos - cfg.sliding_window
    bias = jnp.where(valid, 0.0, NEG_INF)[None, :]  # [1(sq), clen]
    out = _proj_out(p, _sdpa(q, ck, cv, causal=False, bias=bias), x,
                    cross=False)
    return out, {"k": ck, "v": cv}


def xattn_decode(p: dict, x: jnp.ndarray, memory: jnp.ndarray, cfg: ArchConfig
                 ) -> jnp.ndarray:
    """Cross-attention for decode — memory is static, no cache mutation."""
    return xattn_train(p, x, memory, cfg)
