"""Trigger-orchestrated batched serving engine.

Requests arrive as CloudEvents; a *batcher* trigger aggregates up to
``max_batch`` requests (or fires on a flush timeout — same rich-trigger
machinery as the FL aggregator), its action runs prefill + N decode steps on
the mesh, and emits one termination event per request.  Scale-to-zero falls
out of Triggerflow: no requests → no events → the worker is reclaimed.
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Triggerflow, termination_event
from repro.core.actions import register_pyfunc
from repro.core.triggers import make_trigger
from repro.models import Model, ModelConfig, unbox

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
        self.tf.publish(self.workflow, termination_event(
            "serve|request", result={"id": request_id, "prompt": prompt_tokens}))

    def flush(self) -> None:
        """Force the batcher to fire with a partial batch (timeout analogue)."""
        worker = self.tf.worker(self.workflow)
        ctx = worker.context_of(f"{self.workflow}/batcher")
        pending = ctx.get("count", 0)
        if pending:
            ctx["expected"] = pending

    def generate_batch(self, requests: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
        B = len(requests)
        S = max(len(r["prompt"]) for r in requests)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(requests):
            toks[i, S - len(r["prompt"]):] = r["prompt"]  # left-pad
        logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(toks)})
        outs = [[] for _ in range(B)]
        tok = jnp.argmax(logits, -1)[:, None]
        for _ in range(self.max_new_tokens):
            for i in range(B):
                outs[i].append(int(tok[i, 0]))
            logits, cache = self._decode(self.params, cache, {"tokens": tok})
            tok = jnp.argmax(logits, -1)[:, None]
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
