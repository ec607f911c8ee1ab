"""Trigger-batched serving: a request's logits do not depend on its batch,
the model's own RMSNorm epsilon reaches its output, and the engine's
counters book every batch exactly.

The reference is the benchmark's plain float32 Llama
(``chipbench/references/dense_gqa.py``) with its seeded weights, loaded
into the program's parameter tree.  The program runs in bfloat16, so its
logits (of scale ≈ 4 here) may sit up to ``TOL`` off the reference, about
three bfloat16 ulps at that scale; masked rows read ≤ 0.05 off, unmasked
padded rows and a wrong epsilon 2 or more.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Triggerflow
from repro.models import Model
from repro.serving.engine import ServingEngine

TOL = 0.1
MAX_LEN = 32
STEPS = 4
SEED = 7

_REF = Path(__file__).resolve().parents[1] / "chipbench" / "references" / "dense_gqa.py"


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("dense_gqa", _REF)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _sizes(cfg):
    return {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab}


def _program_params(flat):
    """The reference's flat ``a.b.c`` leaves as the program's nested tree."""
    out = {}
    for key, value in flat.items():
        *path, leaf = key.split(".")
        node = out
        for p in path:
            node = node.setdefault(p, {})
        node[leaf] = value
    return out


def _requests(vocab):
    rng = np.random.default_rng(0)
    lens = [5, 16, 11, 8]
    prompts = [rng.integers(0, vocab, n).tolist() for n in lens]
    follow = [rng.integers(0, vocab, STEPS).tolist() for _ in lens]
    return prompts, follow


def _logits(model, params, prompts, follow, masked=True):
    """[B, 1 + STEPS, V]: the prefill's last logits, then each decode
    step's, teacher-forced with ``follow``; prompts left-padded."""
    lens = [len(p) for p in prompts]
    S = max(lens)
    toks = np.zeros((len(prompts), S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, S - len(p):] = p
    batch = {"tokens": jnp.asarray(toks)}
    if masked:
        batch["lengths"] = jnp.asarray(lens, jnp.int32)
    logits, cache = model.prefill(params, batch, max_len=MAX_LEN)
    out = [logits]
    for j in range(STEPS):
        step = jnp.asarray([[f[j]] for f in follow], jnp.int32)
        logits, cache = model.decode(params, cache, {"tokens": step})
        out.append(logits)
    return np.stack([np.asarray(o) for o in out], 1)


def _reference(ref, flat, cfg, prompt, follow, eps):
    seq = list(prompt) + list(follow)
    return ref.logits(flat, seq, np.arange(len(prompt) - 1, len(seq)),
                      theta=cfg.rope_theta, eps=eps)


@pytest.mark.parametrize("masked", [True, False], ids=["masked", "mask_taken_out"])
def test_mixed_length_batch_matches_reference_and_alone(ref, masked):
    """Each request of a left-padded batch gets, in prefill and in every
    decode step, the logits of the float32 reference and of itself served
    alone; with the lengths withheld (the mask taken out) the padded
    requests do not."""
    cfg = get_config("yi-9b", smoke=True)
    flat = ref.init_params(_sizes(cfg), SEED)
    params = _program_params(flat)
    model = Model(cfg)
    prompts, follow = _requests(cfg.vocab)
    got = _logits(model, params, prompts, follow, masked=masked)
    S = max(len(p) for p in prompts)
    for i, p in enumerate(prompts):
        alone = _logits(model, params, [p], [follow[i]])[0]
        want = _reference(ref, flat, cfg, p, follow[i], cfg.rms_eps)
        err_ref = float(np.abs(got[i] - want).max())
        err_alone = float(np.abs(got[i] - alone).max())
        if masked or len(p) == S:
            assert err_ref < TOL, (i, err_ref)
            assert err_alone < TOL, (i, err_alone)
        else:
            assert err_ref > 10 * TOL, (i, err_ref)
            assert err_alone > 10 * TOL, (i, err_alone)


@pytest.mark.parametrize("eps", [1e-6, 1e-2])
def test_rms_eps_reaches_the_output(ref, eps):
    """The program at ``eps`` matches the reference at ``eps`` and not at
    the other value: every norm reads ``ModelConfig.rms_eps``."""
    cfg = dataclasses.replace(get_config("yi-9b", smoke=True), rms_eps=eps)
    other = 1e-2 if eps == 1e-6 else 1e-6
    flat = ref.init_params(_sizes(cfg), SEED)
    model = Model(cfg)
    prompts, follow = _requests(cfg.vocab)
    got = _logits(model, _program_params(flat), prompts, follow)
    for i, p in enumerate(prompts):
        same = _reference(ref, flat, cfg, p, follow[i], eps)
        wrong = _reference(ref, flat, cfg, p, follow[i], other)
        assert float(np.abs(got[i] - same).max()) < TOL
        assert float(np.abs(got[i] - wrong).max()) > 10 * TOL


def test_yi_configs_carry_the_published_epsilon():
    assert get_config("yi-9b").rms_eps == 1e-6
    assert get_config("yi-9b", smoke=True).rms_eps == 1e-6
    assert get_config("llama3.2-3b").rms_eps == 1e-5


def _engine(cfg, max_new_tokens=3):
    tf = Triggerflow(inline_functions=True)
    eng = ServingEngine(cfg, tf, "serve", max_batch=4,
                        max_new_tokens=max_new_tokens, max_len=MAX_LEN)
    return tf, eng


def test_serve_counters_book_each_batch_exactly():
    tf, eng = _engine(get_config("yi-9b", smoke=True))
    batches = [[5, 16, 11, 8], [7, 7, 7, 7], [3, 12]]
    for lens in batches:
        out = eng.generate_batch([
            {"id": f"r{n}.{i}", "prompt": [1] * n, "t": 0.0}
            for i, n in enumerate(lens)])
        assert [len(o["tokens"]) for o in out] == [3] * len(lens)
    snap = tf.metrics_snapshot("serve")
    c, h = snap["counters"], snap["histograms"]
    n_req = sum(len(b) for b in batches)
    assert c["tf_serve_batches_total"] == 3
    assert c["tf_serve_requests_total"] == n_req
    assert c["tf_serve_prompt_tokens_total"] == sum(map(sum, batches))
    assert c["tf_serve_pad_tokens_total"] == sum(
        len(b) * max(b) - sum(b) for b in batches)
    assert c["tf_serve_tokens_total"] == n_req * 3
    assert c["tf_serve_host_syncs_total"] == n_req * 3
    assert h["tf_serve_prefill_seconds"]["count"] == 3
    assert h["tf_serve_decode_step_seconds"]["count"] == 3 * 3
    assert h["tf_serve_request_wait_seconds"]["count"] == n_req
    tf.shutdown()


def test_host_syncs_count_the_reads_made(monkeypatch):
    """The counter follows the decode loop's device-to-host reads: one a
    row a step as the loop reads today, one a step where the step's
    tokens come over in one read, with the same tokens served."""
    prompts = [[1] * 5, [2] * 9, [3] * 9, [4] * 2]

    def serve(tf, eng):
        out = eng.generate_batch([{"id": f"r{i}", "prompt": p}
                                  for i, p in enumerate(prompts)])
        c = tf.metrics_snapshot("serve")["counters"]
        tf.shutdown()
        return ([o["tokens"] for o in out],
                c["tf_serve_host_syncs_total"] / c["tf_serve_tokens_total"])

    cfg = get_config("yi-9b", smoke=True)
    tokens, per_token = serve(*_engine(cfg))
    assert per_token == 1.0
    monkeypatch.setattr(ServingEngine, "_read_tokens",
                        lambda self, tok: self._host(tok[:, 0]).tolist())
    batched, per_token = serve(*_engine(cfg))
    assert batched == tokens
    assert per_token == 1 / len(prompts)


def test_warmup_compiles_every_prompt_length():
    tf, eng = _engine(get_config("yi-9b", smoke=True))
    eng.warmup([8, 4, 16])
    n = eng._prefill._cache_size()
    assert eng._decode._cache_size() == 1
    eng.generate_batch([{"id": f"r{i}", "prompt": [2] * k}
                        for i, k in enumerate([4, 8, 16, 8])])
    eng.generate_batch([{"id": f"s{i}", "prompt": [2] * 8} for i in range(4)])
    assert eng._prefill._cache_size() == n == 3
    assert eng._decode._cache_size() == 1
    tf.shutdown()


def test_unmaskable_family_refuses_a_mixed_length_batch():
    cfg = get_config("xlstm-1.3b", smoke=True)
    assert not cfg.masks_padding
    tf, eng = _engine(cfg, max_new_tokens=2)
    out = eng.generate_batch([{"id": "a", "prompt": [1, 2, 3]},
                              {"id": "b", "prompt": [4, 5, 6]}])
    assert [len(o["tokens"]) for o in out] == [2, 2]
    with pytest.raises(ValueError, match="cannot mask padding"):
        eng.generate_batch([{"id": "a", "prompt": [1, 2, 3]},
                            {"id": "b", "prompt": [4, 5]}])
    tf.shutdown()
