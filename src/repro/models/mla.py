"""Multi-head Latent Attention (DeepSeek-V2): the KV cache stores only the
compressed latent ``c_kv`` [B,S,kv_lora] plus the shared decoupled RoPE key
[B,S,rope_dim] — a ~10-50× cache reduction vs full K/V.  Decode uses the
*absorbed* formulation (W_uk folded into the query, W_uv applied after the
latent-space attention), so decode FLOPs/bytes scale with kv_lora, not H×dh.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import KeyGen, make_param
from .layers import apply_rope, attention_chunked, lsc, rms_norm, rms_norm_init, rope_angles


def mla_init(keys: KeyGen, d_model: int, n_heads: int, q_lora: int, kv_lora: int,
             nope_dim: int = 128, rope_dim: int = 64, v_dim: int = 128):
    p = {
        "wdq": make_param(keys(), (d_model, q_lora), ("embed", None), scale=d_model ** -0.5),
        "q_norm": rms_norm_init(keys(), q_lora),
        "wuq": make_param(keys(), (q_lora, n_heads, nope_dim + rope_dim),
                          (None, "heads", "head"), scale=q_lora ** -0.5),
        "wdkv": make_param(keys(), (d_model, kv_lora), ("embed", None), scale=d_model ** -0.5),
        "kv_norm": rms_norm_init(keys(), kv_lora),
        "wuk": make_param(keys(), (kv_lora, n_heads, nope_dim),
                          (None, "heads", "head"), scale=kv_lora ** -0.5),
        "wuv": make_param(keys(), (kv_lora, n_heads, v_dim),
                          (None, "heads", "head"), scale=kv_lora ** -0.5),
        "wkr": make_param(keys(), (d_model, rope_dim), ("embed", None), scale=d_model ** -0.5),
        "wo": make_param(keys(), (n_heads, v_dim, d_model), ("heads", "head", "embed"),
                         scale=(n_heads * v_dim) ** -0.5),
    }
    return p


def _queries(params, x, cos, sin, nope_dim, eps):
    cq = rms_norm(params["q_norm"], x @ params["wdq"], eps)
    q = jnp.einsum("bsq,qhk->bshk", cq, params["wuq"])
    qn, qr = q[..., :nope_dim], q[..., nope_dim:]
    qr = apply_rope(qr, cos, sin)
    return qn, qr


def mla_forward(params, x, positions, nope_dim=128, rope_dim=64,
                rope_theta=10000.0, q_chunk=2048, kv_chunk=2048, return_cache=False,
                unroll=False, *, eps):
    """Training/prefill path: expand the latent into full K/V per head."""
    B, S, D = x.shape
    cos, sin = rope_angles(positions, rope_dim, rope_theta)
    qn, qr = _queries(params, x, cos, sin, nope_dim, eps)
    ckv = rms_norm(params["kv_norm"], x @ params["wdkv"], eps)      # [B,S,kvl]
    kr = apply_rope((x @ params["wkr"])[:, :, None, :], cos, sin)   # [B,S,1,rope]
    kn = jnp.einsum("bsc,chk->bshk", ckv, params["wuk"])
    v = jnp.einsum("bsc,chk->bshk", ckv, params["wuv"])
    H = kn.shape[2]
    q = jnp.concatenate([qn, qr], -1)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, (B, S, H, kr.shape[-1]))], -1)
    q = lsc(q, "batch", "seq", "heads", None)
    k = lsc(k, "batch", "seq", "heads", None)
    attn = attention_chunked(q, k, v, causal=True, q_chunk=q_chunk,
                             kv_chunk=kv_chunk, unroll=unroll)
    out = jnp.einsum("bshk,hkd->bsd", attn, params["wo"])
    if return_cache:
        return out, (ckv, kr[:, :, 0, :])
    return out


def mla_decode(params, x, cache_ckv, cache_kr, pos, nope_dim=128, rope_dim=64,
               rope_theta=10000.0, *, eps):
    """Absorbed decode: score/context in the kv_lora latent space.
    x [B,1,D]; cache_ckv [B,T,kvl]; cache_kr [B,T,rope]."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, jnp.int32)
    cos, sin = rope_angles(positions, rope_dim, rope_theta)
    qn, qr = _queries(params, x, cos, sin, nope_dim, eps)           # [B,1,H,*]
    ckv_t = rms_norm(params["kv_norm"], x @ params["wdkv"], eps)    # [B,1,kvl]
    kr_t = apply_rope((x @ params["wkr"])[:, :, None, :], cos, sin)[:, :, 0, :]
    cache_ckv = jax.lax.dynamic_update_slice_in_dim(
        cache_ckv, ckv_t.astype(cache_ckv.dtype), pos, 1)
    cache_kr = jax.lax.dynamic_update_slice_in_dim(
        cache_kr, kr_t.astype(cache_kr.dtype), pos, 1)
    # absorb W_uk into the query: q_lat [B,H,kvl]
    q_lat = jnp.einsum("bhk,chk->bhc", qn[:, 0], params["wuk"])
    s = jnp.einsum("bhc,btc->bht", q_lat, cache_ckv).astype(jnp.float32)
    s = s + jnp.einsum("bhk,btk->bht", qr[:, 0], cache_kr).astype(jnp.float32)
    s = s / math.sqrt(nope_dim + rope_dim)
    valid = jnp.arange(cache_ckv.shape[1])[None, :] < (pos + 1)
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(cache_ckv.dtype)
    ctx = jnp.einsum("bht,btc->bhc", p, cache_ckv)                  # latent context
    out_v = jnp.einsum("bhc,chk->bhk", ctx, params["wuv"])          # expand to v_dim
    out = jnp.einsum("bhk,hkd->bd", out_v, params["wo"])[:, None, :]
    return out, cache_ckv, cache_kr
