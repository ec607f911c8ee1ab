"""Plain reference of a dense decoder LM with grouped-query attention
(the Llama architecture, as Yi-9B publishes it), and the benchmark's own
seeded weights for it.

Written from the published description, in ``jax.numpy`` and float32 at
``highest`` matmul precision, with no kernel, cache, chunking or batching:
pre-norm RMSNorm blocks, rotary embedding on the two halves of each head,
causal softmax attention in which query head ``h`` reads key/value head
``h // (heads / kv_heads)``, a SwiGLU MLP, a final RMSNorm and an untied
output head.  It imports nothing of the program under test.

Weights live in one dict: ``embed [V, D]``, ``lm_head [D, V]``,
``final_norm.w [D]`` and, stacked over layers, ``ln1.w``, ``ln2.w [L, D]``,
``attn.wq [L, D, H, hd]``, ``attn.wk``/``attn.wv [L, D, KV, hd]``,
``attn.wo [L, H, hd, D]``, ``mlp.wg``/``mlp.wu [L, D, F]``, ``mlp.wd [L, F, D]``.

``quant="fp8"`` is the control: every matmul operand rounded to
``float8_e4m3fn`` with a scale per output channel (weights) and per token
(activations), the step below the served bfloat16.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


def shapes(sizes: dict) -> Dict[str, tuple]:
    """Leaf path -> shape for the sizes of a configuration file."""
    L, D = sizes["num_hidden_layers"], sizes["hidden_size"]
    H, KV = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    hd = sizes.get("head_dim", D // H)
    F, V = sizes["intermediate_size"], sizes["vocab_size"]
    return {
        "embed": (V, D), "lm_head": (D, V), "final_norm.w": (D,),
        "layers.ln1.w": (L, D), "layers.ln2.w": (L, D),
        "layers.attn.wq": (L, D, H, hd), "layers.attn.wk": (L, D, KV, hd),
        "layers.attn.wv": (L, D, KV, hd), "layers.attn.wo": (L, H, hd, D),
        "layers.mlp.wg": (L, D, F), "layers.mlp.wu": (L, D, F),
        "layers.mlp.wd": (L, F, D),
    }


def _std(path: str, sizes: dict) -> float:
    """Scale of each matrix: 0.02 for the embedding, 1/sqrt(fan-in) else."""
    D, F = sizes["hidden_size"], sizes["intermediate_size"]
    H = sizes["num_attention_heads"]
    hd = sizes.get("head_dim", D // H)
    fan_in = {"embed": None, "layers.attn.wo": H * hd, "layers.mlp.wd": F}
    n = fan_in.get(path, D)
    return 0.02 if n is None else n ** -0.5


def init_params(sizes: dict, seed: int, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Every weight from the seed, on the device, in one jitted call.

    Matrices are normal with variance 1/fan-in; norm gains are 1 plus a
    normal of scale 0.1, so that a norm that ignored its gain would show."""
    shp = shapes(sizes)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF)

    def make():
        out = {}
        for i, (path, s) in enumerate(sorted(shp.items())):
            k = jax.random.fold_in(key, i)
            if path.endswith(".w"):
                v = 1.0 + 0.1 * jax.random.normal(k, s, jnp.float32)
            else:
                v = jax.random.normal(k, s, jnp.float32) * _std(path, sizes)
            out[path] = v.astype(dtype)
        return out

    return jax.jit(make)()


# -- the forward pass -----------------------------------------------------------
def _q8(x, axis):
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, quant, w_in_axes):
    """``x [..., in] @ w`` with ``w``'s input dims ``w_in_axes`` leading."""
    if quant == "fp8":
        x = _q8(x, -1)
        w = _q8(w, w_in_axes)
    return jnp.tensordot(x, w, axes=((x.ndim - 1,), (0,)))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate the two halves of each head by ``pos * theta**(-2i/hd)``."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = pos[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lw, theta, eps, quant):
    """One block over one sequence ``x [S, D]``, all in float32."""
    S = x.shape[0]
    lw = {k: v.astype(jnp.float32) for k, v in lw.items()}
    H, hd = lw["wq"].shape[1], lw["wq"].shape[2]
    KV = lw["wk"].shape[1]
    pos = jnp.arange(S, dtype=jnp.float32)
    h = _rms(x, lw["ln1"], eps)
    wq = lw["wq"].reshape(h.shape[-1], H * hd)
    wk = lw["wk"].reshape(h.shape[-1], KV * hd)
    wv = lw["wv"].reshape(h.shape[-1], KV * hd)
    q = _rope(_mm(h, wq, quant, 0).reshape(S, H, hd), pos, theta)
    k = _rope(_mm(h, wk, quant, 0).reshape(S, KV, hd), pos, theta)
    v = _mm(h, wv, quant, 0).reshape(S, KV, hd)
    kv_of = jnp.arange(H) // (H // KV)
    k, v = k[:, kv_of], v[:, kv_of]                       # [S, H, hd]
    if quant == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    if quant == "fp8":
        p = _q8(p, -1)
    a = jnp.einsum("hqk,khd->qhd", p, v).reshape(S, H * hd)
    x = x + _mm(a, lw["wo"].reshape(H * hd, -1), quant, 0)
    h = _rms(x, lw["ln2"], eps)
    g = _mm(h, lw["wg"], quant, 0)
    u = _mm(h, lw["wu"], quant, 0)
    return x + _mm(jax.nn.silu(g) * u, lw["wd"], quant, 0)


def _head(x, wf, lm_head, eps, quant):
    return _mm(_rms(x, wf.astype(jnp.float32), eps),
               lm_head.astype(jnp.float32), quant, 0)


_layer_jit = jax.jit(_layer, static_argnums=(2, 3, 4))
_head_jit = jax.jit(_head, static_argnums=(3, 4))


def logits(params: Dict[str, jax.Array], tokens, rows, *, theta: float,
           eps: float, quant: Optional[str] = None) -> np.ndarray:
    """Float32 logits of one sequence ``tokens`` at positions ``rows``,
    one layer at a time so that only one layer's float32 weights exist."""
    n_layers = params["layers.ln1.w"].shape[0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
        for i in range(n_layers):
            lw = {"ln1": params["layers.ln1.w"][i],
                  "ln2": params["layers.ln2.w"][i],
                  "wq": params["layers.attn.wq"][i],
                  "wk": params["layers.attn.wk"][i],
                  "wv": params["layers.attn.wv"][i],
                  "wo": params["layers.attn.wo"][i],
                  "wg": params["layers.mlp.wg"][i],
                  "wu": params["layers.mlp.wu"][i],
                  "wd": params["layers.mlp.wd"][i]}
            x = _layer_jit(x, lw, theta, eps, quant)
        out = _head_jit(x[jnp.asarray(rows)], params["final_norm.w"],
                        params["lm_head"], eps, quant)
    return np.asarray(out)
