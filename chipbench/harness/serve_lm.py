"""Trigger-batched serving of a dense GQA model under open-loop requests,
at the configuration's own RMSNorm epsilon, timed inside the program.

The deployment, the window and the comparison are those of
``serve.py``, whose helpers this module uses: ``ServingEngine`` on a
``Triggerflow`` facade behind ``KedaAutoscaler``, the benchmark's seeded
weights, each request submitted at its due time, and a seeded sample of
the served requests run through the plain float32 reference.  It differs
in four ways:

- the model is built with the configuration's ``rms_norm_eps``;
- set-up compiles through ``ServingEngine.warmup`` (prefill at each prompt
  length the traffic uses, then one decode step), then runs ``warmup_s``
  seconds of the cell's traffic;
- the program's ``tf_serve_*`` counters are read at the window's open and
  close (``snap0``, ``snap1``) for the per-layer readers;
- the end-to-end metric is named as the benchmark names it:
  ``fire_latency_p95_ms``, the 95th percentile over the requests due in
  the window of the time from a request's due time to the bus stamp of
  its ``serve|done|<id>`` event.

A program whose ``ServingEngine`` has no ``warmup`` predates masked
padding and the model's own epsilon: its run stops at set-up with an
``AttributeError``.

Traced, the run also reads the decode program's executions in the window
off the device trace (``decode_programs``), for the decode step's share of
its HBM roofline.
"""
from __future__ import annotations

import gc
import glob
import os
import sys
import time
from typing import Dict

import numpy as np

from . import openloop, traffic as tr
from .serve import (DRAIN_S, WORKFLOW, boxed_weights, engine_class,
                    reference_gaps, sample_requests, stop_serving,
                    watch_results)
from .trace import WINDOW_SPAN, window_of

DECODE_PROGRAM = "jit_decode"


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file, its
    epsilon included."""
    from repro.models import ModelConfig

    return ModelConfig(
        arch=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
        rms_eps=cfg["rms_norm_eps"])


def module_rows(trace_dir: str) -> dict:
    """The device programs of the one trace under ``trace_dir``, as plain
    rows: ``{"modules": [[program, start_ns, dur_ns], ...], "host":
    [[span, start_ns, dur_ns], ...]}``, from the ``XLA Modules`` line of
    each ``/device:TPU:<n>`` plane (one row an execution) and the
    benchmark's window span."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    modules, host = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if plane.name.startswith("/device:TPU:") and line.name == "XLA Modules":
                modules += [[ev.name.split("(", 1)[0], int(ev.start_ns),
                             int(ev.duration_ns)] for ev in line.events]
            elif plane.name.startswith("/host:"):
                host += [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                         for ev in line.events if ev.name == WINDOW_SPAN]
    return {"modules": modules, "host": host}


def program_runs(rows: dict, program: str) -> dict:
    """Executions of ``program`` that lie wholly inside the window: their
    number and their summed device seconds."""
    lo, hi = window_of(rows)
    runs = [d for name, s, d in rows["modules"]
            if name == program and s >= lo and s + d <= hi]
    return {"runs": len(runs), "device_s": sum(runs) / 1e9}


def run(cfg: dict, traffic: dict, seed: int, seconds: float,
        trace_dir, on_window_open, read_device, control: bool = False) -> dict:
    """One run of the cell; ``on_window_open()`` returns the set-up time and
    ``read_device()`` the device line.  ``control`` also reads the control:
    the float8 reference put in the program's place at the same positions."""
    import jax

    from repro.core import KedaAutoscaler, Triggerflow
    from repro.obs.trace import arm_profiler_spans

    from . import trace as trace_mod

    srv = cfg["serving"]
    sched = tr.schedule(traffic, seed, seconds)
    due = sched.due
    lengths = sched.attrs["prompt_len"]
    prompts = tr.prompt_tokens(seed, lengths, cfg["vocab_size"])
    ids = [f"r{i}" for i in range(due.size)]

    tf = Triggerflow(inline_functions=True)
    eng = engine_class()(
        model_config(cfg), tf, WORKFLOW, max_batch=srv["max_batch"],
        max_new_tokens=srv["max_new_tokens"], max_len=srv["max_len"],
        weights=lambda model: boxed_weights(model, cfg, seed))
    eng.deploy()
    eng.warmup(lengths.tolist())
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

    def start_trace():
        arm_profiler_spans(True)
        capture.__enter__()

    def open_window():
        out["setup_s"] = on_window_open()
        out["snap0"] = tf.metrics_snapshot(WORKFLOW)
        out["t_open_wall"] = time.time()
        out["t_open"] = time.perf_counter()
        window_span.append(jax.profiler.TraceAnnotation(WINDOW_SPAN))
        window_span[0].__enter__()

    def close_window():
        window_span[0].__exit__(None, None, None)
        out["t_close"] = time.perf_counter()
        out["t_close_wall"] = time.time()
        out["snap1"] = tf.metrics_snapshot(WORKFLOW)
        out["backlog_at_close"] = sum(r not in done for r in ids[:sent[0]])
        if trace_dir:
            capture.__exit__(None, None, None)
            arm_profiler_spans(False)

    t_base = time.perf_counter() + float(traffic.get("warmup_s", 0.0))
    wall_base = time.time() + (t_base - time.perf_counter())
    marks = {0.0: open_window, float(seconds): close_window}
    if trace_dir:
        marks[-0.5] = start_trace
    try:
        late = openloop.drive(due, send, t_base, marks,
                              until=float(seconds) + DRAIN_S,
                              done=all_served)
        out["device"] = read_device()
    finally:
        stop_serving(tf, scaler, eng)

    if trace_dir:
        out["decode_programs"] = program_runs(module_rows(trace_dir),
                                              DECODE_PROGRAM)
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
        "fire_latency_p95_ms": (float(np.percentile(lat, 95)) * 1e3
                                if lat else None),
        "tokens_per_s": sum(len(done[ids[i]][0]) for i in completed) / window_s,
    }
    # per-batch spans of the window, for the whole step's readers
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
    print(f"serve_lm: {len(ids)} requests scheduled, {out['attempted']} due "
          f"in the window, {len(done)} served in all, {len(out['batches'])} "
          f"batches began in the window; reference over {len(sample)} "
          f"requests, {sum(len(done[ids[i]][0]) for i in sample)} tokens",
          file=sys.stderr)
    return out
