"""Trigger-orchestrated batched serving engine.

Requests arrive as CloudEvents; a *batcher* trigger aggregates up to
``max_batch`` requests (or fires on a flush timeout — same rich-trigger
machinery as the FL aggregator), its action runs prefill + N decode steps on
the mesh, and emits one termination event per request.  Scale-to-zero falls
out of Triggerflow: no requests → no events → the worker is reclaimed.

Prompts of different lengths share a batch left-padded to the longest, with
each row's length passed to the model, which masks the padding: a request
gets the tokens it would get alone.  A family that cannot mask padding
(``ModelConfig.masks_padding``) refuses a mixed-length batch.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Triggerflow, termination_event
from repro.core.actions import register_pyfunc
from repro.core.events import stamp_publish_time
from repro.core.triggers import make_trigger
from repro.models import Model, ModelConfig, unbox
from repro.obs.metrics import ServeMetrics
from repro.obs.trace import span

_ENGINES: Dict[str, "ServingEngine"] = {}


class ServingEngine:
    def __init__(self, cfg: ModelConfig, tf: Triggerflow, workflow: str,
                 max_batch: int = 4, max_new_tokens: int = 16,
                 max_len: int = 256):
        self.cfg = cfg
        self.tf = tf
        self.workflow = workflow
        self.max_batch = max_batch
        self.max_new_tokens = max_new_tokens
        self.max_len = max_len
        self.model = Model(cfg)
        # one compiled program: at full width, op-by-op init would hold each
        # stacked layer weight in f32 beside its bf16 copy on the device
        self.params = jax.jit(lambda key: unbox(self.model.init(key)))(
            jax.random.PRNGKey(0))
        self._prefill = jax.jit(lambda p, b: self.model.prefill(p, b, max_len=max_len))
        self._decode = jax.jit(self.model.decode)
        self.served = 0
        self.batches = 0
        self.host_reads = 0
        self.metrics = ServeMetrics()
        tf.add_metrics_source(workflow, self.metrics.registry.snapshot)
        _ENGINES[workflow] = self

    def deploy(self) -> None:
        self.tf.create_workflow(self.workflow, {"kind": "serving"})
        self.tf.add_trigger(self.workflow, make_trigger(
            "serve|request",
            condition={"name": "counter", "expected": self.max_batch,
                       "reset_on_fire": True},
            action={"name": "pyfunc", "func": "serve.batch", "engine": self.workflow},
            trigger_id=f"{self.workflow}/batcher",
            transient=False,
        ))

    def submit(self, request_id: str, prompt_tokens: List[int]) -> None:
        """Publish a request; it carries its own bus stamp (``t``), which
        its batch's wait is measured from."""
        t = time.time()
        event = termination_event("serve|request", result={
            "id": request_id, "prompt": prompt_tokens, "t": t})
        stamp_publish_time((event,), t)
        self.tf.publish(self.workflow, event)

    def flush(self) -> None:
        """Force the batcher to fire with a partial batch (timeout analogue)."""
        worker = self.tf.worker(self.workflow)
        ctx = worker.context_of(f"{self.workflow}/batcher")
        pending = ctx.get("count", 0)
        if pending:
            ctx["expected"] = pending

    def _prompt_batch(self, prompts: List[List[int]]) -> Dict[str, jax.Array]:
        """The prefill batch: prompts left-padded to the longest, with each
        row's length."""
        lens = [len(p) for p in prompts]
        S = max(lens)
        if min(lens) != S and not self.cfg.masks_padding:
            raise ValueError(
                f"family {self.cfg.family!r} cannot mask padding: prompts of "
                f"lengths {sorted(set(lens))} cannot share a batch")
        toks = np.zeros((len(prompts), S), np.int32)
        for i, p in enumerate(prompts):
            toks[i, S - lens[i]:] = p
        return {"tokens": jnp.asarray(toks),
                "lengths": jnp.asarray(lens, jnp.int32)}

    def warmup(self, prompt_lens: List[int]) -> None:
        """Compile before traffic comes: prefill of a full batch at each of
        ``prompt_lens``, then one decode step with its token reads."""
        for n in sorted(set(prompt_lens)):
            batch = self._prompt_batch([[0] * n] * self.max_batch)
            logits, cache = self._prefill(self.params, batch)
        tok = jnp.argmax(logits, -1)[:, None]
        self._read_tokens(tok)
        jax.block_until_ready(self._decode(self.params, cache, {"tokens": tok}))

    def _host(self, x: jax.Array) -> np.ndarray:
        """A device-to-host read of the decode loop, counted."""
        self.host_reads += 1
        return np.asarray(x)

    def _read_tokens(self, tok: jax.Array) -> List[int]:
        """The step's next token of each row, on the host."""
        return [int(self._host(tok[i, 0])) for i in range(tok.shape[0])]

    def generate_batch(self, requests: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        B = len(requests)
        t_start = time.time()
        batch = self._prompt_batch([r["prompt"] for r in requests])
        t0 = time.perf_counter()
        with span("tf.serve.prefill"):
            logits, cache = self._prefill(self.params, batch)
            logits.block_until_ready()
        t1 = time.perf_counter()
        outs = [[] for _ in range(B)]
        reads0 = self.host_reads
        with span("tf.serve.decode"):
            tok = jnp.argmax(logits, -1)[:, None]
            for _ in range(self.max_new_tokens):
                for out, t in zip(outs, self._read_tokens(tok)):
                    out.append(t)
                logits, cache = self._decode(self.params, cache, {"tokens": tok})
                tok = jnp.argmax(logits, -1)[:, None]
        t2 = time.perf_counter()
        steps = self.max_new_tokens
        self.metrics.batch(
            [len(r["prompt"]) for r in requests], batch["tokens"].shape[1],
            [t_start - r["t"] for r in requests if "t" in r],
            t1 - t0, steps, t2 - t1, tokens=B * steps,
            host_syncs=self.host_reads - reads0)
        self.served += B
        self.batches += 1
        return [{"id": r["id"], "tokens": outs[i]} for i, r in enumerate(requests)]


def _serve_batch(ctx, event, params) -> None:
    eng = _ENGINES[params["engine"]]
    requests = [r for r in (ctx.get("fired_results") or []) if r]
    if not requests:
        return
    for out in eng.generate_batch(requests):
        ctx.produce(termination_event(f"serve|done|{out['id']}", result=out))


register_pyfunc("serve.batch", _serve_batch)
