"""xLSTM blocks: mLSTM (matrix memory, chunked-parallel) and sLSTM (scalar
memory with recurrent gate mixing).

mLSTM recurrence per head (state C [Dk,Dv], normalizer n [Dk]):
    C_t = f_t·C_{t-1} + i_t·(k_t ⊗ v_t)
    n_t = f_t·n_{t-1} + i_t·k_t
    y_t = (q_t·C_t) / max(|q_t·n_t|, 1)
Training uses a chunk-parallel form (same algebra as the SSD chunking in
``ssm.py``), verified against the step recurrence by property tests.

Deviation from the paper (recorded in DESIGN.md): the input gate uses
``sigmoid`` rather than ``exp`` so the chunked form is stable in fp32 without
the max-stabilizer bookkeeping; forget gates are sigmoid as in the paper.
sLSTM keeps the paper's per-head block-diagonal recurrent gate mixing but is
evaluated as a plain time scan (it is inherently sequential — the paper
accelerates it with a fused GPU kernel; on TPU we keep the scan in HLO).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import KeyGen, make_param
from .layers import lsc, rms_norm, rms_norm_init


# ---------------------------------------------------------------- mLSTM ----
def mlstm_init(keys: KeyGen, d_model: int, n_heads: int, expand: int = 2):
    di = expand * d_model
    Dh = di // n_heads
    # q/k/v are per-head block-diagonal projections (as in the xLSTM paper's
    # mLSTM cell) — di²/H params each instead of di²
    return {
        "w_up": make_param(keys(), (d_model, 2 * di), ("embed", "ffn"), scale=d_model ** -0.5),
        "wq": make_param(keys(), (n_heads, Dh, Dh), ("heads", None, None), scale=Dh ** -0.5),
        "wk": make_param(keys(), (n_heads, Dh, Dh), ("heads", None, None), scale=Dh ** -0.5),
        "wv": make_param(keys(), (n_heads, Dh, Dh), ("heads", None, None), scale=Dh ** -0.5),
        "wi": make_param(keys(), (di, n_heads), ("ffn", None), scale=di ** -0.5),
        "wf": make_param(keys(), (di, n_heads), ("ffn", None), scale=di ** -0.5),
        "f_bias": make_param(keys(), (n_heads,), (None,), init="ones"),
        "out_norm": rms_norm_init(keys(), di),
        "w_down": make_param(keys(), (di, d_model), ("ffn", "embed"), scale=di ** -0.5),
    }


def _mlstm_chunked(q, k, v, log_f, i_gate, chunk: int):
    """q/k/v [B,S,H,D]; log_f/i_gate [B,S,H].  Returns y, (C_T, n_T)."""
    Bsz, S, H, D = q.shape
    Q = min(chunk, S)
    S0 = S
    if S % Q:
        # neutral padding: f=1 (log_f=0), i=0 ⇒ padded steps are no-ops
        pad = Q - S % Q
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        log_f = jnp.pad(log_f, ((0, 0), (0, pad), (0, 0)))
        i_gate = jnp.pad(i_gate, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // Q
    f32 = jnp.float32
    scale = D ** -0.5

    qc = q.reshape(Bsz, nc, Q, H, D).astype(f32) * scale
    kc = k.reshape(Bsz, nc, Q, H, D).astype(f32)
    vc = v.reshape(Bsz, nc, Q, H, D).astype(f32)
    lf = log_f.reshape(Bsz, nc, Q, H).astype(f32)
    ig = i_gate.reshape(Bsz, nc, Q, H).astype(f32)
    L = jnp.cumsum(lf, axis=2)
    Llast = L[:, :, -1]

    G = jnp.einsum("bcihd,bcjhd->bcijh", qc, kc)
    decay = jnp.exp(L[:, :, :, None, :] - L[:, :, None, :, :])
    ii = jnp.arange(Q)
    mask = (ii[:, None] >= ii[None, :]).astype(f32)
    att = G * decay * mask[None, None, :, :, None] * ig[:, :, None, :, :]
    y_diag = jnp.einsum("bcijh,bcjhd->bcihd", att, vc)
    # denominator: q_i·n_i — the intra part is just the row-sum of att
    den_diag = att.sum(axis=3)                                    # [b,c,i,h]

    w = jnp.exp(Llast[:, :, None, :] - L) * ig                    # [b,c,q,h]
    csC = jnp.einsum("bcjh,bcjhk,bcjhv->bchkv", w, kc, vc)
    csn = jnp.einsum("bcjh,bcjhk->bchk", w, kc)

    def step(carry, inp):
        C, n = carry
        csC_c, csn_c, dec_c = inp
        prev = (C, n)
        C = dec_c[:, :, None, None] * C + csC_c
        n = dec_c[:, :, None] * n + csn_c
        return (C, n), prev

    C0 = jnp.zeros((Bsz, H, D, D), f32)
    n0 = jnp.zeros((Bsz, H, D), f32)
    (CT, nT), (Cprev, nprev) = jax.lax.scan(
        step, (C0, n0),
        (csC.transpose(1, 0, 2, 3, 4), csn.transpose(1, 0, 2, 3),
         jnp.exp(Llast).transpose(1, 0, 2)))
    Cprev = Cprev.transpose(1, 0, 2, 3, 4)                         # [b,c,h,k,v]
    nprev = nprev.transpose(1, 0, 2, 3)

    eL = jnp.exp(L)
    y_inter = jnp.einsum("bcihk,bchkv,bcih->bcihv", qc, Cprev, eL)
    den_inter = jnp.einsum("bcihk,bchk,bcih->bcih", qc, nprev, eL)
    den = den_diag + den_inter
    y = (y_diag + y_inter) / jnp.maximum(jnp.abs(den), 1.0)[..., None]
    return y.reshape(Bsz, S, H, D)[:, :S0], (CT, nT)


def mlstm_cell_step(q, k, v, log_f, i_gate, C, n):
    """Single step: q/k/v [B,H,D], gates [B,H]."""
    f32 = jnp.float32
    scale = q.shape[-1] ** -0.5
    q, k, v = q.astype(f32) * scale, k.astype(f32), v.astype(f32)
    f = jnp.exp(log_f.astype(f32))
    i = i_gate.astype(f32)
    C = f[:, :, None, None] * C + i[:, :, None, None] * jnp.einsum("bhk,bhv->bhkv", k, v)
    n = f[:, :, None] * n + i[:, :, None] * k
    num = jnp.einsum("bhk,bhkv->bhv", q, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", q, n)), 1.0)
    return num / den[..., None], C, n


def _mlstm_qkvg(params, xm, n_heads):
    di = xm.shape[-1]
    D = di // n_heads
    xh = xm.reshape(*xm.shape[:-1], n_heads, D)
    q = jnp.einsum("...hd,hde->...he", xh, params["wq"])
    k = jnp.einsum("...hd,hde->...he", xh, params["wk"])
    v = jnp.einsum("...hd,hde->...he", xh, params["wv"])
    log_f = jax.nn.log_sigmoid((xm @ params["wf"]).astype(jnp.float32)
                               + params["f_bias"].astype(jnp.float32))
    i_gate = jax.nn.sigmoid((xm @ params["wi"]).astype(jnp.float32))
    return q, k, v, log_f, i_gate


def mlstm_forward(params, x, n_heads: int, chunk: int = 128, return_state: bool = False,
                  *, eps: float):
    up = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    up = lsc(up, "batch", "seq", "ffn")
    xm, z = jnp.split(up, 2, axis=-1)
    q, k, v, log_f, i_gate = _mlstm_qkvg(params, xm, n_heads)
    y, state = _mlstm_chunked(q, k, v, log_f, i_gate, chunk)
    y = y.reshape(*xm.shape).astype(x.dtype)
    y = rms_norm(params["out_norm"], y, eps) * jax.nn.silu(z)
    out = jnp.einsum("bsf,fd->bsd", y, params["w_down"])
    if return_state:
        return out, state
    return out


def mlstm_decode(params, x, state, n_heads: int, *, eps: float):
    C, n = state
    up = jnp.einsum("bsd,df->bsf", x, params["w_up"])
    xm, z = jnp.split(up, 2, axis=-1)
    q, k, v, log_f, i_gate = _mlstm_qkvg(params, xm[:, 0], n_heads)
    y, C, n = mlstm_cell_step(q, k, v, log_f, i_gate, C, n)
    y = y.reshape(xm[:, 0].shape).astype(x.dtype)
    y = rms_norm(params["out_norm"], y, eps) * jax.nn.silu(z[:, 0])
    out = jnp.einsum("bf,fd->bd", y, params["w_down"])[:, None, :]
    return out, (C, n)


# ---------------------------------------------------------------- sLSTM ----
def slstm_init(keys: KeyGen, d_model: int, n_heads: int):
    dh = d_model // n_heads
    return {
        "wx": make_param(keys(), (d_model, 4 * d_model), ("embed", "ffn"),
                         scale=d_model ** -0.5),
        "r": make_param(keys(), (n_heads, dh, 4 * dh), ("heads", None, None),
                        scale=dh ** -0.5),
        "bias": make_param(keys(), (4 * d_model,), ("ffn",), init="zeros"),
        "out_norm": rms_norm_init(keys(), d_model),
        "wo": make_param(keys(), (d_model, d_model), ("embed", "embed2"),
                         scale=d_model ** -0.5),
    }


def slstm_cell_step(gx, r, h, c, n, n_heads):
    """gx [B,4d] (input-projected gates); h/c/n [B,H,dh]."""
    f32 = jnp.float32
    B, H = h.shape[0], n_heads
    dh = h.shape[-1]
    rec = jnp.einsum("bhd,hde->bhe", h, r).reshape(B, 4 * H * dh)
    g = (gx.astype(f32) + rec.astype(f32)).reshape(B, H, dh, 4)
    i = jax.nn.sigmoid(g[..., 0])
    f = jax.nn.sigmoid(g[..., 1] + 1.0)
    z = jnp.tanh(g[..., 2])
    o = jax.nn.sigmoid(g[..., 3])
    c = f * c + i * z
    n = f * n + i
    h = o * c / jnp.maximum(n, 1.0)
    return h, c, n


def slstm_forward(params, x, n_heads: int, return_state: bool = False,
                  *, eps: float):
    B, S, d = x.shape
    dh = d // n_heads
    gx = jnp.einsum("bsd,de->bse", x, params["wx"]) + params["bias"]
    # regroup so gates interleave per head-dim: [B,S,H,dh,4]
    gx = gx.reshape(B, S, 4, n_heads, dh).transpose(0, 1, 3, 4, 2).reshape(B, S, 4 * d)

    def step(carry, gx_t):
        h, c, n = carry
        h, c, n = slstm_cell_step(gx_t, params["r"], h, c, n, n_heads)
        return (h, c, n), h

    h0 = jnp.zeros((B, n_heads, dh), jnp.float32)
    carry, hs = jax.lax.scan(step, (h0, h0, h0), gx.transpose(1, 0, 2))
    y = hs.transpose(1, 0, 2, 3).reshape(B, S, d).astype(x.dtype)
    y = rms_norm(params["out_norm"], y, eps)
    out = jnp.einsum("bsd,de->bse", y, params["wo"])
    if return_state:
        return out, carry
    return out


def slstm_decode(params, x, state, n_heads: int, *, eps: float):
    B, _, d = x.shape
    dh = d // n_heads
    h, c, n = state
    gx = (x[:, 0] @ params["wx"]) + params["bias"]
    gx = gx.reshape(B, 4, n_heads, dh).transpose(0, 2, 3, 1).reshape(B, 4 * d)
    h, c, n = slstm_cell_step(gx, params["r"], h, c, n, n_heads)
    y = h.reshape(B, d).astype(x.dtype)
    y = rms_norm(params["out_norm"], y, eps)
    out = (y @ params["wo"])[:, None, :]
    return out, (h, c, n)
