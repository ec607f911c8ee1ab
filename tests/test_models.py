"""Model-zoo tests: per-arch smoke (reduced configs, one forward/train step,
shape + finiteness asserts) and the key numerical invariants:

* chunked Mamba2 SSD == step recurrence
* chunked mLSTM == step recurrence
* prefill + decode == full forward (per family, incl. MLA absorbed decode)
* chunked flash attention == naive O(S²) oracle (incl. unrolled variant)
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import ARCHS, get_config
from repro.models import Model, unbox
from repro.models.layers import attention_chunked, attention_naive
from repro.models.ssm import mamba2_decode, mamba2_forward, mamba2_init
from repro.models.xlstm import (mlstm_forward, mlstm_decode, mlstm_init,
                                slstm_forward, slstm_decode, slstm_init)
from repro.models.common import KeyGen

RNG = jax.random.PRNGKey(0)


def _batch_for(cfg, B=2, S=32, rng=RNG):
    if cfg.family == "audio":
        toks = jax.random.randint(rng, (B, cfg.codebooks, S), 0, cfg.vocab)
    else:
        toks = jax.random.randint(rng, (B, S), 0, cfg.vocab)
    batch = {"tokens": toks, "targets": toks}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            rng, (B, cfg.n_patches, cfg.d_model), jnp.bfloat16)
        batch["patch_positions"] = jnp.tile(jnp.arange(cfg.n_patches)[None], (B, 1))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_train_step(arch):
    """Required per-arch smoke: reduced config, one forward + train step."""
    from repro.training.optimizer import AdamW
    from repro.training.train_step import make_train_step

    cfg = get_config(arch, smoke=True)
    model = Model(cfg)
    params = unbox(model.init(RNG))
    batch = _batch_for(cfg)
    logits, aux = model.forward(params, batch)
    if cfg.family == "audio":
        assert logits.shape == (2, 32, cfg.codebooks, cfg.vocab)
    else:
        assert logits.shape == (2, 32, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    opt = AdamW()
    step = make_train_step(model, opt)
    params2, opt_state, metrics = step(params, opt.init(params), batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    # parameters actually moved
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree_util.tree_leaves(params), jax.tree_util.tree_leaves(params2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_prefill_decode_consistency(arch):
    """Decode step at position S must match the full forward's last logits."""
    cfg = get_config(arch, smoke=True)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32, capacity_factor=8.0)
    model = Model(cfg)
    params = unbox(model.init(RNG))
    B, S = 2, 24
    batch = _batch_for(cfg, B, S)
    full_logits, _ = model.forward(params, batch)

    if cfg.family == "audio":
        prefix = {"tokens": batch["tokens"][:, :, :-1]}
        last = {"tokens": batch["tokens"][:, :, -1:]}
        want = full_logits[:, -1]          # [B,K,V]
    else:
        prefix = {k: (v[:, : S - 1] if v.shape[1] == S else v)
                  for k, v in batch.items() if k != "targets"}
        if cfg.family == "vlm":
            # keep patches within the prefix
            prefix["patch_embeds"] = batch["patch_embeds"]
            prefix["patch_positions"] = batch["patch_positions"]
        last = {"tokens": batch["tokens"][:, -1:]}
        want = full_logits[:, -1]
    _, cache = model.prefill(params, prefix, max_len=S + 4)
    got, _ = model.decode(params, cache, last)
    err = float(jnp.abs(got - want).max())
    rtol = 2e-2 if cfg.family == "vlm" else 1e-2
    assert err < rtol * (1 + float(jnp.abs(want).max())), (arch, err)


def test_mamba2_chunked_equals_recurrent():
    keys = KeyGen(jax.random.PRNGKey(3))
    d, di, N, hd = 16, 32, 8, 8
    p = unbox(mamba2_init(keys, d, di, N, hd))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, d), jnp.float32) * 0.5
    y_chunked, (state, conv) = mamba2_forward(p, x, chunk=4, return_state=True, eps=1e-5)
    # step the recurrence token by token
    W = p["conv_w"].shape[0]
    st = jnp.zeros((2, di // hd, N, hd), jnp.float32)
    cc = jnp.zeros((2, W - 1, di), jnp.float32)
    outs = []
    for t in range(16):
        o, st, cc = mamba2_decode(p, x[:, t:t + 1], st, cc, eps=1e-5)
        outs.append(o)
    y_step = jnp.concatenate(outs, axis=1)
    assert jnp.allclose(y_chunked, y_step, atol=2e-4), float(
        jnp.abs(y_chunked - y_step).max())
    assert jnp.allclose(state, st, atol=2e-4)


def test_mlstm_chunked_equals_recurrent():
    keys = KeyGen(jax.random.PRNGKey(5))
    d, H = 16, 4
    p = unbox(mlstm_init(keys, d, H, expand=2))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, d), jnp.float32) * 0.5
    y_chunked, (C, n) = mlstm_forward(p, x, H, chunk=4, return_state=True, eps=1e-5)
    di = 2 * d
    Dh = di // H
    Cs = jnp.zeros((2, H, Dh, Dh), jnp.float32)
    ns = jnp.zeros((2, H, Dh), jnp.float32)
    outs = []
    for t in range(12):
        o, (Cs, ns) = mlstm_decode(p, x[:, t:t + 1], (Cs, ns), H, eps=1e-5)
        outs.append(o)
    y_step = jnp.concatenate(outs, axis=1)
    assert jnp.allclose(y_chunked, y_step, atol=2e-4), float(
        jnp.abs(y_chunked - y_step).max())
    assert jnp.allclose(C, Cs, atol=2e-4)


def test_slstm_forward_equals_decode():
    keys = KeyGen(jax.random.PRNGKey(7))
    d, H = 16, 4
    p = unbox(slstm_init(keys, d, H))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 10, d), jnp.float32) * 0.5
    y_full, state = slstm_forward(p, x, H, return_state=True, eps=1e-5)
    st = tuple(jnp.zeros((2, H, d // H), jnp.float32) for _ in range(3))
    outs = []
    for t in range(10):
        o, st = slstm_decode(p, x[:, t:t + 1], st, H, eps=1e-5)
        outs.append(o)
    y_step = jnp.concatenate(outs, axis=1)
    assert jnp.allclose(y_full, y_step, atol=2e-4)


@given(st.sampled_from([16, 24, 64]), st.sampled_from([1, 2, 4]),
       st.sampled_from([8, 16]), st.booleans(), st.booleans())
@settings(max_examples=12, deadline=None)
def test_attention_chunked_matches_naive_property(S, G, qc, causal, unroll):
    B, Hkv, D = 2, 2, 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(S * G + qc), 3)
    q = jax.random.normal(k1, (B, S, Hkv * G, D))
    k = jax.random.normal(k2, (B, S, Hkv, D))
    v = jax.random.normal(k3, (B, S, Hkv, D))
    ref = attention_naive(q, k, v, causal=causal)
    out = attention_chunked(q, k, v, causal=causal, q_chunk=qc, kv_chunk=qc,
                            unroll=unroll)
    assert jnp.allclose(ref, out, atol=5e-5), float(jnp.abs(ref - out).max())


def test_param_counts_are_plausible():
    """Full configs must land near their nameplate sizes.

    The spec pins *dimensions* (llama-arch SwiGLU blocks); two archs deviate
    from their nameplates by construction and get a wider band: granite-20b's
    original gpt_bigcode uses a 2-matrix MLP (ours is SwiGLU → ~28B at the
    pinned d_ff) and xlstm-1.3b's cells carry the paper's conv/skip trimmings
    we simplify (ours ~1.9B)."""
    expect = {
        "granite-20b": (28.2e9, 0.05), "deepseek-67b": (67e9, 0.1),
        "yi-9b": (9e9, 0.15), "llama3.2-3b": (3.6e9, 0.15),
        "qwen2-vl-72b": (72e9, 0.1), "phi3.5-moe-42b-a6.6b": (42e9, 0.1),
        "deepseek-v2-236b": (236e9, 0.1), "musicgen-large": (3.3e9, 0.1),
        "zamba2-1.2b": (1.2e9, 0.25), "xlstm-1.3b": (1.9e9, 0.25),
    }
    for arch, (target, tol) in expect.items():
        n = get_config(arch).param_count()
        assert abs(n - target) / target < tol, (arch, n, target)


def test_mamba2_bf16_decay_close_to_fp32():
    """The §Perf memory lever must stay numerically sane (decay ∈ [0,1])."""
    keys = KeyGen(jax.random.PRNGKey(11))
    p = unbox(mamba2_init(keys, 32, 64, 16, 16))
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 64, 32), jnp.float32) * 0.5
    y32 = mamba2_forward(p, x, chunk=16, eps=1e-5)
    y16 = mamba2_forward(p, x, chunk=16, decay_dtype=jnp.bfloat16, eps=1e-5)
    rel = float(jnp.abs(y32 - y16).max() / (jnp.abs(y32).max() + 1e-9))
    assert rel < 0.05, rel
