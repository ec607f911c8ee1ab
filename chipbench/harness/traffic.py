"""The one traffic generator: a traffic file of parameters in, a schedule out.

A traffic file (``traffic/<name>.json``) names its distributions by kind:
``arrivals`` gives the due times, and each entry of ``items`` one attribute
of every item (a join event's subject, a request's prompt length).  Each
kind is a module of its own, ``traffic/kinds/<kind>.py``, found by name:
an arrival kind defines ``due(spec, rate, start, end, rng)``, an item kind
``draw(spec, n, rng)``.  A new mix is a new data file, and a new
distribution a new kind file; neither edits this one.

Every seed gets the same work: the kinds draw the same multiset of gaps
and values for every seed, and the seed only orders them.  Runs with
different seeds then differ by ordering alone, as two users of one
deployment would, and not by how much work they carry.

The schedule spans ``[-warmup_s, seconds + tail_s)``: items due before 0
warm the system up, the window is ``[0, seconds)``, and the tail keeps
arrivals coming past it where a batcher needs later arrivals to close a
batch that holds a window request.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from functools import lru_cache
from pathlib import Path
from typing import Dict

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


def load_traffic(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


@lru_cache(maxsize=None)
def kind(name: str):
    """The module of a distribution kind, ``traffic/kinds/<name>.py``."""
    path = TRAFFIC_DIR / "kinds" / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no traffic kind {name!r} ({path} is missing)")
    spec = importlib.util.spec_from_file_location(f"chipbench_kind_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent stream per purpose; any whole seed up to and past
    2**32 is accepted."""
    return np.random.default_rng([seed & (2**63 - 1), *stream.encode()])


@dataclass
class Schedule:
    due: np.ndarray                 # seconds from the window's start, sorted
    attrs: Dict[str, np.ndarray] = field(default_factory=dict)

    def __len__(self) -> int:
        return int(self.due.size)


def schedule(traffic: dict, seed: int, seconds: float) -> Schedule:
    """The whole run's schedule: warm-up, window and tail."""
    start = -float(traffic.get("warmup_s", 0.0))
    end = float(seconds) + float(traffic.get("tail_s", 0.0))
    arr = traffic["arrivals"]
    due = kind(arr["kind"]).due(arr, float(traffic["rate_per_s"]), start, end,
                                rng_for(seed, "arrivals"))
    attrs = {name: kind(spec["kind"]).draw(spec, due.size, rng_for(seed, name))
             for name, spec in traffic.get("items", {}).items()}
    return Schedule(due=due, attrs=attrs)


def prompt_tokens(seed: int, lengths: np.ndarray, vocab: int) -> list:
    """Each request's prompt, drawn from the seed over the whole vocabulary."""
    rng = rng_for(seed, "prompt_tokens")
    return [rng.integers(0, vocab, int(n)).tolist() for n in lengths]
