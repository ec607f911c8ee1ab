"""Trigger-batched serving of a dense GQA model under open-loop requests.

Set-up builds the serving deployment as ``launch/serve.py`` runs it
(``ServingEngine`` on a ``Triggerflow`` facade behind ``KedaAutoscaler``)
with the benchmark's seeded weights, warms each prompt length the traffic
uses with one batch, and then runs ``warmup_s`` seconds of the cell's own
traffic through the whole path.  The window submits each request through
``ServingEngine.submit`` at its due time; arrivals go on past the window
until every request due in it has its result on the bus.

A request is timed from its due time to the moment its ``serve|done|<id>``
event reached the bus (the bus stamps it).  Once the window has closed and
the program is shut down, a sample of the finished requests drawn from the
seed, the longest prompt among them, is run through the plain float32
reference over prompt and served tokens, and the widest gap by which a
served token's reference logit lies below that position's best is
compared with its limit.
"""
from __future__ import annotations

import gc
import sys
import threading
import time
from typing import Dict, List

import numpy as np

from . import openloop, traffic as tr
from .trace import WINDOW_SPAN
from ..references import dense_gqa

WORKFLOW = "serve"
DRAIN_S = 60.0


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for the sizes of a configuration file."""
    from repro.models import ModelConfig

    return ModelConfig(
        arch=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"])


def boxed_weights(model, cfg: dict, seed: int):
    """The benchmark's weights in the program's parameter tree: each leaf
    of the program's own init is replaced by the reference's leaf of the
    same path, which must have the same shape."""
    import jax

    from repro.models.common import Param, is_param

    spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat = dense_gqa.init_params(cfg, seed)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(spec, is_leaf=is_param)
    want = set(flat)
    out = []
    for path, leaf in leaves:
        key = ".".join(p.key for p in path)
        if key not in flat or tuple(flat[key].shape) != tuple(leaf.value.shape):
            raise ValueError(f"program parameter {key} {leaf.value.shape} has "
                             "no leaf of that shape in the reference")
        want.discard(key)
        out.append(Param(flat[key], leaf.axes))
    if want:
        raise ValueError(f"reference leaves the program lacks: {sorted(want)}")
    return jax.tree_util.tree_unflatten(treedef, out)


def engine_class():
    """A ``ServingEngine`` with the benchmark's weights, and a span around
    each batch it serves."""
    import jax

    from repro.serving.engine import ServingEngine

    class _SeededModel:
        """The program's model, except that ``init`` gives the benchmark's
        weights; ``ServingEngine.__init__`` calls it inside its jit."""

        def __init__(self, model, weights):
            self._model, self._weights = model, weights

        def init(self, key):
            return self._weights(self._model)

        def __getattr__(self, name):
            return getattr(self._model, name)

    class BenchEngine(ServingEngine):
        def __init__(self, *args, weights, **kw):
            self._weights = weights
            self.spans: List[tuple] = []
            self.closed = False
            self._spans_lock = threading.Lock()
            super().__init__(*args, **kw)

        @property
        def model(self):
            return self._bench_model

        @model.setter
        def model(self, m):
            self._bench_model = _SeededModel(m, self._weights)

        def generate_batch(self, requests):
            if self.closed:     # the run is over: serve nothing more
                return []
            t0 = time.time()
            with jax.profiler.TraceAnnotation("chipbench.generate_batch"):
                out = super().generate_batch(requests)
            with self._spans_lock:
                self.spans.append((t0, time.time(),
                                   [r["id"] for r in requests],
                                   [len(r["prompt"]) for r in requests]))
            return out

    return BenchEngine


def run(cfg: dict, traffic: dict, seed: int, seconds: float,
        trace_dir, on_window_open, read_device, control: bool = False) -> dict:
    """One run of the cell; ``on_window_open()`` returns the set-up time and
    ``read_device()`` the device line.  ``control`` also reads the control:
    the float8 reference put in the program's place at the same positions."""
    import jax

    from repro.core import KedaAutoscaler, Triggerflow

    from . import trace as trace_mod

    srv = cfg["serving"]
    sched = tr.schedule(traffic, seed, seconds)
    due = sched.due
    lengths = sched.attrs["prompt_len"]
    prompts = tr.prompt_tokens(seed, lengths, cfg["vocab_size"])
    ids = [f"r{i}" for i in range(due.size)]

    mcfg = model_config(cfg)
    tf = Triggerflow(inline_functions=True)
    eng = engine_class()(
        mcfg, tf, WORKFLOW, max_batch=srv["max_batch"],
        max_new_tokens=srv["max_new_tokens"], max_len=srv["max_len"],
        weights=lambda model: boxed_weights(model, cfg, seed))
    eng.deploy()
    # one batch per prompt length the traffic uses compiles prefill, the
    # decode step and the token reads, and nothing else
    for n in sorted(set(lengths.tolist())):
        eng.generate_batch([{"id": f"warm{n}.{i}", "prompt": [1] * n}
                            for i in range(srv["max_batch"])])
    eng.spans.clear()
    scaler = KedaAutoscaler(tf, poll_interval=0.05, grace_period=0.5).start()

    out: Dict = {"cell_kind": "serve"}
    in_window = (due >= 0) & (due < seconds)
    window_ids = {ids[i] for i in np.flatnonzero(in_window)}
    done = watch_results(tf.event_store)

    sent = [0]

    def send(i: int, j: int) -> None:
        for k in range(i, j):
            eng.submit(ids[k], prompts[k])
        sent[0] = j

    def all_served() -> bool:
        return all(r in done for r in window_ids)

    window_span = []    # made as the window opens: only then is the trace on
    capture = trace_mod.Capture(trace_dir)

    def open_window():
        out["setup_s"] = on_window_open()
        out["t_open_wall"] = time.time()
        out["t_open"] = time.perf_counter()
        window_span.append(jax.profiler.TraceAnnotation(WINDOW_SPAN))
        window_span[0].__enter__()

    def close_window():
        window_span[0].__exit__(None, None, None)
        out["t_close"] = time.perf_counter()
        out["t_close_wall"] = time.time()
        out["backlog_at_close"] = sum(r not in done for r in ids[:sent[0]])
        if trace_dir:
            capture.__exit__(None, None, None)

    t_base = time.perf_counter() + float(traffic.get("warmup_s", 0.0))
    wall_base = time.time() + (t_base - time.perf_counter())
    marks = {0.0: open_window, float(seconds): close_window}
    if trace_dir:
        marks[-0.5] = capture.__enter__
    try:
        late = openloop.drive(due, send, t_base, marks,
                              until=float(seconds) + DRAIN_S,
                              done=all_served)
        out["device"] = read_device()
    finally:
        stop_serving(tf, scaler, eng)

    spans = list(eng.spans)
    del eng
    gc.collect()

    window = np.flatnonzero(in_window)
    out["late_s"] = late[window][np.isfinite(late[window])]
    lat = [done[ids[i]][1] - (wall_base + due[i])
           for i in window if ids[i] in done]
    unserved = sum(ids[i] not in done for i in window)
    new = srv["max_new_tokens"]
    malformed = sum(ids[i] in done and len(done[ids[i]][0]) != new
                    for i in window)
    out["attempted"] = int(window.size)
    out["failed"] = int(unserved + malformed)
    window_s = out["t_close"] - out["t_open"]
    out["window_s"] = window_s
    completed = [i for i in range(due.size) if ids[i] in done
                 and out["t_open_wall"] <= done[ids[i]][1] < out["t_close_wall"]]
    out["e2e"] = {
        "request_latency_p95_ms": (float(np.percentile(lat, 95)) * 1e3
                                   if lat else None),
        "tokens_per_s": sum(len(done[ids[i]][0]) for i in completed) / window_s,
    }
    # per-batch spans of the window, for the serving layers' metrics
    pos = {r: i for i, r in enumerate(ids)}
    out["batches"] = [
        {"start": s0, "end": s1, "prompt_lens": lens,
         "waits": [s0 - (wall_base + due[pos[r]]) for r in rids if r in pos]}
        for s0, s1, rids, lens in spans
        if out["t_open_wall"] <= s0 < out["t_close_wall"]]
    out["new_tokens"] = new

    # -- the comparison with the plain reference -----------------------------
    fin = [i for i in window if ids[i] in done
           and len(done[ids[i]][0]) == new]
    sample = sample_requests(fin, lengths, seed, cfg["check"]["sample"])
    gaps, cgaps = reference_gaps(cfg, seed, [prompts[i] for i in sample],
                                 [done[ids[i]][0] for i in sample],
                                 control=control)
    gap = max(gaps) if gaps else float("inf")
    batch_len = {r: max(lens) for _, _, rids, lens in spans for r in rids}
    out["sample_detail"] = [
        {"prompt_len": int(lengths[i]), "batch_len": batch_len.get(ids[i]),
         "gap": g} for i, g in zip(sample, gaps)]
    if control:
        out["control_checks"] = [
            {"name": "max_logit_gap", "value": max(cgaps) if cgaps else None,
             "limit": cfg["check"]["max_logit_gap"]}]
    out["sample"] = len(sample)
    out["checks"] = [
        {"name": "unserved", "value": int(unserved), "limit": 0},
        {"name": "malformed", "value": int(malformed), "limit": 0},
        {"name": "max_logit_gap", "value": gap,
         "limit": cfg["check"]["max_logit_gap"]},
    ]
    print(f"serve: {len(ids)} requests scheduled, {out['attempted']} due in "
          f"the window, {len(done)} served in all, {len(out['batches'])} "
          f"batches began in the window; reference over {len(sample)} "
          f"requests, {sum(len(done[ids[i]][0]) for i in sample)} tokens",
          file=sys.stderr)
    return out


def stop_serving(tf, scaler, eng) -> None:
    """Stop the deployment and free the served weights once no batch can
    run: a batch that fires from here on serves nothing, and the worker's
    thread, with the batch it may be running, is waited for."""
    eng.closed = True
    scaler.stop()
    tf.shutdown()
    deadline = time.monotonic() + DRAIN_S
    while tf.worker_alive(WORKFLOW):
        if time.monotonic() > deadline:
            raise RuntimeError("the serving worker did not stop")
        time.sleep(0.01)
    eng.params = None


def watch_results(store) -> Dict[str, tuple]:
    """``{request id: (tokens, bus time)}``, filled as each result event is
    published: the bus stamps an event's ``time`` as it takes it."""
    done: Dict[str, tuple] = {}
    publish, publish_batch = store.publish, store.publish_batch

    def note(events):
        for e in events:
            if e.subject.startswith("serve|done|"):
                r = e.data["result"]
                done[r["id"]] = (r["tokens"], e.time)

    def one(workflow, event):
        publish(workflow, event)
        note((event,))

    def many(workflow, events):
        events = list(events)
        publish_batch(workflow, events)
        note(events)

    store.publish, store.publish_batch = one, many
    return done


def sample_requests(finished: List[int], lengths: np.ndarray, seed: int,
                    k: int) -> List[int]:
    """``k`` finished requests drawn from the seed, the longest prompt
    first among them."""
    if not finished:
        return []
    longest = max(finished, key=lambda i: (lengths[i], -i))
    rest = [i for i in finished if i != longest]
    rng = tr.rng_for(seed, "check_sample")
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[p] for p in sorted(pick)]


def reference_gaps(cfg: dict, seed: int, prompts: List[list],
                   served: List[list], control: bool = False):
    """Per request, the widest gap, in logits of the float32 reference,
    between a position's best and the served token; with ``control`` also
    the same for the token that the float8 control puts first."""
    params = dense_gqa.init_params(cfg, seed)
    kw = dict(theta=cfg["rope_theta"], eps=cfg["rms_norm_eps"])
    gaps, cgaps = [], []
    for p, s in zip(prompts, served):
        seq = list(p) + list(s[:-1])
        rows = np.arange(len(p) - 1, len(seq))
        ref = dense_gqa.logits(params, seq, rows, **kw)
        best = ref.max(-1)
        at = np.arange(len(s))
        gaps.append(float((best - ref[at, s]).max()))
        if control:
            low = dense_gqa.logits(params, seq, rows, quant="fp8", **kw)
            cgaps.append(float((best - ref[at, low.argmax(-1)]).max()))
    del params
    return gaps, cgaps
