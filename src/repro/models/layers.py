"""Core transformer layers, pure JAX: RMSNorm, RoPE / M-RoPE, GQA attention
(with a chunked online-softmax "flash" path that never materializes the S×S
matrix — the XLA production path; the Pallas TPU kernel in
``repro.kernels.flash_attention`` is the hardware hot-spot version), and the
SwiGLU MLP.  All parameters are ``Param``-boxed with logical sharding axes.
"""
from __future__ import annotations

import math
from contextvars import ContextVar

import jax
import jax.numpy as jnp

from .common import KeyGen, make_param

# -- logical activation sharding ----------------------------------------------------
# The distributed layer installs a resolver(logical_axes_tuple) -> PartitionSpec;
# model code annotates activations with logical axes and stays mesh-agnostic.
_ACT_RESOLVER: ContextVar = ContextVar("act_resolver", default=None)


def set_activation_resolver(resolver):
    return _ACT_RESOLVER.set(resolver)


def reset_activation_resolver(token):
    _ACT_RESOLVER.reset(token)


def lsc(x, *axes):
    """logical sharding constraint (no-op outside a mesh context)."""
    resolver = _ACT_RESOLVER.get()
    if resolver is None:
        return x
    sharding = resolver(axes, x.shape)
    if sharding is None:
        return x
    return jax.lax.with_sharding_constraint(x, sharding)


# -- norms ---------------------------------------------------------------------------
def rms_norm_init(key, d, name="norm"):
    return {"w": make_param(key, (d,), ("embed",), init="ones")}


def rms_norm(params, x, eps):
    """``eps`` is the model's own (``ModelConfig.rms_eps``): no default, so
    that every call states it."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * params["w"].astype(jnp.float32)).astype(x.dtype)


# -- RoPE ----------------------------------------------------------------------------
def rope_angles(positions, head_dim: int, theta: float = 10000.0):
    """positions [...]: int -> cos/sin [..., head_dim/2] in fp32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., half]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [B,S,H,D]; cos/sin [B,S,D/2] or [S,D/2] (broadcast over heads)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def mrope_angles(positions3, head_dim: int, sections, theta: float = 10000.0):
    """Qwen2-VL M-RoPE: positions3 [B,S,3] (t,h,w); ``sections`` split the
    rotary half-dim across the three position streams."""
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    coss, sins = [], []
    start = 0
    for i, sec in enumerate(sections):
        pos = positions3[..., i].astype(jnp.float32)  # [B,S]
        ang = pos[..., None] * freqs[start:start + sec]
        coss.append(jnp.cos(ang))
        sins.append(jnp.sin(ang))
        start += sec
    return jnp.concatenate(coss, -1), jnp.concatenate(sins, -1)  # [B,S,half]


# -- attention ------------------------------------------------------------------------
def _row_mask(mask, kv_pos, kv_start):
    """[Sq,Skv] mask -> [B|1,Sq,Skv]: with ``kv_start`` [B], row b also
    masks the keys before its first valid slot (its left padding)."""
    if kv_start is None:
        return mask[None]
    return mask[None] & (kv_pos[None, None, :] >= kv_start[:, None, None])


def attention_naive(q, k, v, causal=True, kv_len=None, pos_offset=0,
                    kv_start=None):
    """Reference O(S²)-memory attention (oracle for tests; never the prod path).
    q [B,Sq,Hq,D], k/v [B,Skv,Hkv,D] with Hq = G*Hkv; ``kv_start`` [B] masks
    each row's keys before its first valid slot (left padding)."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, G, D)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(D)
    q_pos = pos_offset + jnp.arange(Sq)
    kv_pos = jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= q_pos[:, None] >= kv_pos[None, :]
    if kv_len is not None:
        mask &= kv_pos[None, :] < kv_len
    mask = _row_mask(mask, kv_pos, kv_start)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgst,bthd->bshgd", p, v)
    return out.reshape(B, Sq, Hq, D)


def attention_chunked(q, k, v, causal=True, kv_len=None, pos_offset=0,
                      q_chunk=2048, kv_chunk=2048, unroll=False, kv_start=None):
    """Online-softmax flash attention in pure JAX: double scan over q/kv chunks.
    Peak intermediate is [B,Hkv,G,qc,kc] — no S×S materialization.
    ``kv_start`` [B] masks each row's left padding, as in ``attention_naive``.

    ``unroll=True`` emits the chunk loops as straight-line HLO (and *skips*
    fully-masked causal blocks — the triangular schedule, ~2× fewer FLOPs).
    Used by the dry-run analysis probes (XLA cost analysis does not scale
    ``while`` bodies by trip count) and available as a production option."""
    if unroll:
        return _attention_unrolled(q, k, v, causal, kv_len, pos_offset,
                                   q_chunk, kv_chunk, kv_start)
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]  # value head dim may differ (MLA)
    G = Hq // Hkv
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Skv)
    nq, nk = -(-Sq // qc), -(-Skv // kc)
    # pad to multiples
    q = jnp.pad(q, ((0, 0), (0, nq * qc - Sq), (0, 0), (0, 0))) if nq * qc != Sq else q
    k = jnp.pad(k, ((0, 0), (0, nk * kc - Skv), (0, 0), (0, 0))) if nk * kc != Skv else k
    v = jnp.pad(v, ((0, 0), (0, nk * kc - Skv), (0, 0), (0, 0))) if nk * kc != Skv else v
    qg = q.reshape(B, nq, qc, Hkv, G, D).transpose(1, 0, 3, 4, 2, 5)  # [nq,B,Hkv,G,qc,D]
    kg = k.reshape(B, nk, kc, Hkv, D).transpose(1, 0, 3, 2, 4)        # [nk,B,Hkv,kc,D]
    vg = v.reshape(B, nk, kc, Hkv, Dv).transpose(1, 0, 3, 2, 4)
    scale = 1.0 / math.sqrt(D)
    neg = jnp.float32(-1e30)

    def q_block(carry, inp):
        iq, qb = inp  # qb [B,Hkv,G,qc,D]
        q_pos = pos_offset + iq * qc + jnp.arange(qc)

        def kv_block(acc, kin):
            ik, kb, vb = kin
            m_prev, l_prev, o_prev = acc
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb).astype(jnp.float32) * scale
            kv_pos = ik * kc + jnp.arange(kc)
            msk = jnp.ones((qc, kc), bool)
            if causal:
                msk &= q_pos[:, None] >= kv_pos[None, :]
            if kv_len is not None:
                msk &= (kv_pos < kv_len)[None, :]
            else:
                msk &= (kv_pos < Skv)[None, :]
            msk = _row_mask(msk, kv_pos, kv_start)
            s = jnp.where(msk[:, None, None], s, neg)
            m_new = jnp.maximum(m_prev, s.max(-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_prev * alpha + p.sum(-1)
            o_new = o_prev * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb).astype(jnp.float32)
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, Hkv, G, qc), neg, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        o0 = jnp.zeros((B, Hkv, G, qc, Dv), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            kv_block, (m0, l0, o0), (jnp.arange(nk), kg, vg))
        out = o / jnp.maximum(l[..., None], 1e-30)
        return carry, out.astype(v.dtype)

    _, outs = jax.lax.scan(q_block, None, (jnp.arange(nq), qg))
    # outs [nq,B,Hkv,G,qc,Dv] -> [B,S,Hq,Dv]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * qc, Hq, Dv)
    return out[:, :Sq]


def _attention_unrolled(q, k, v, causal, kv_len, pos_offset, q_chunk, kv_chunk,
                        kv_start):
    """Straight-line flash attention with causal block skipping."""
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Skv)
    nq, nk = -(-Sq // qc), -(-Skv // kc)
    q = jnp.pad(q, ((0, 0), (0, nq * qc - Sq), (0, 0), (0, 0))) if nq * qc != Sq else q
    k = jnp.pad(k, ((0, 0), (0, nk * kc - Skv), (0, 0), (0, 0))) if nk * kc != Skv else k
    v = jnp.pad(v, ((0, 0), (0, nk * kc - Skv), (0, 0), (0, 0))) if nk * kc != Skv else v
    scale = 1.0 / math.sqrt(D)
    neg = jnp.float32(-1e30)
    outs = []
    for iq in range(nq):
        qb = q[:, iq * qc:(iq + 1) * qc].reshape(B, qc, Hkv, G, D)
        qb = qb.transpose(0, 2, 3, 1, 4)  # [B,Hkv,G,qc,D]
        q_pos = pos_offset + iq * qc + jnp.arange(qc)
        q_end = pos_offset + (iq + 1) * qc - 1
        m = jnp.full((B, Hkv, G, qc), neg, jnp.float32)
        l = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        o = jnp.zeros((B, Hkv, G, qc, Dv), jnp.float32)
        for ik in range(nk):
            if causal and ik * kc > q_end:
                continue  # fully-masked block: triangular skip
            kb = k[:, ik * kc:(ik + 1) * kc].transpose(0, 2, 1, 3)  # [B,Hkv,kc,D]
            vb = v[:, ik * kc:(ik + 1) * kc].transpose(0, 2, 1, 3)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb).astype(jnp.float32) * scale
            kv_pos = ik * kc + jnp.arange(kc)
            msk = jnp.ones((qc, kc), bool)
            if causal:
                msk &= q_pos[:, None] >= kv_pos[None, :]
            msk &= (kv_pos < (Skv if kv_len is None else kv_len))[None, :]
            msk = _row_mask(msk, kv_pos, kv_start)
            s = jnp.where(msk[:, None, None], s, neg)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * alpha + p.sum(-1)
            o = o * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb).astype(jnp.float32)
            m = m_new
        out = (o / jnp.maximum(l[..., None], 1e-30)).astype(v.dtype)
        outs.append(out.transpose(0, 3, 1, 2, 4).reshape(B, qc, Hq, Dv))
    return jnp.concatenate(outs, axis=1)[:, :Sq]


def attention_decode(q, k_cache, v_cache, pos, start=None):
    """Single-token decode vs a (padded) cache.  q [B,1,Hq,D],
    caches [B,T,Hkv,D], ``pos`` = number of filled cache slots (int or [B]);
    ``start`` [B], each row's first valid slot (after its left padding)."""
    B, _, Hq, D = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bthd->bhgt", qg, k_cache).astype(jnp.float32)
    s = s / math.sqrt(D)
    kv_pos = jnp.arange(T)
    valid = kv_pos[None, :] < (pos if jnp.ndim(pos) else pos + jnp.zeros((B,), jnp.int32))[:, None]
    if start is not None:
        valid &= kv_pos[None, :] >= start[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhgt,bthd->bhgd", p, v_cache)
    return out.reshape(B, 1, Hq, D)


# -- GQA attention block ----------------------------------------------------------------
def gqa_init(keys: KeyGen, d_model: int, n_heads: int, n_kv: int, head_dim: int):
    return {
        "wq": make_param(keys(), (d_model, n_heads, head_dim), ("embed", "heads", "head"),
                         scale=d_model ** -0.5),
        "wk": make_param(keys(), (d_model, n_kv, head_dim), ("embed", "kv_heads", "head"),
                         scale=d_model ** -0.5),
        "wv": make_param(keys(), (d_model, n_kv, head_dim), ("embed", "kv_heads", "head"),
                         scale=d_model ** -0.5),
        "wo": make_param(keys(), (n_heads, head_dim, d_model), ("heads", "head", "embed"),
                         scale=(n_heads * head_dim) ** -0.5),
    }


def gqa_qkv(params, x):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    return q, k, v


def gqa_out(params, attn):
    return jnp.einsum("bshk,hkd->bsd", attn, params["wo"])


def gqa_forward(params, x, cos, sin, causal=True, q_chunk=2048, kv_chunk=2048,
                return_kv=False, unroll=False, kv_start=None):
    q, k, v = gqa_qkv(params, x)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    q = lsc(q, "batch", "seq", "heads", None)
    k = lsc(k, "batch", "seq", "kv_heads", None)
    attn = attention_chunked(q, k, v, causal=causal, q_chunk=q_chunk,
                             kv_chunk=kv_chunk, unroll=unroll, kv_start=kv_start)
    out = gqa_out(params, attn)
    if return_kv:
        return out, (k, v)
    return out


def gqa_decode(params, x, cache_k, cache_v, pos, cos, sin, start=None):
    """x [B,1,D]; writes K/V at ``pos`` and attends over each row's valid
    slots ``[start, pos]``."""
    q, k, v = gqa_qkv(params, x)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k.astype(cache_k.dtype), pos, 1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v.astype(cache_v.dtype), pos, 1)
    out = attention_decode(q, cache_k, cache_v, pos + 1, start)
    return gqa_out(params, out), cache_k, cache_v


# -- SwiGLU MLP -----------------------------------------------------------------------
def mlp_init(keys: KeyGen, d_model: int, d_ff: int):
    return {
        "wg": make_param(keys(), (d_model, d_ff), ("embed", "ffn"), scale=d_model ** -0.5),
        "wu": make_param(keys(), (d_model, d_ff), ("embed", "ffn"), scale=d_model ** -0.5),
        "wd": make_param(keys(), (d_ff, d_model), ("ffn", "embed"), scale=d_ff ** -0.5),
    }


def mlp_forward(params, x):
    g = jnp.einsum("bsd,df->bsf", x, params["wg"])
    u = jnp.einsum("bsd,df->bsf", x, params["wu"])
    h = jax.nn.silu(g) * u
    h = lsc(h, "batch", "seq", "ffn")
    return jnp.einsum("bsf,fd->bsd", h, params["wd"])
