"""The one traffic generator. A mix is a data file under ``traffic/``:

    {"loop": "closed", "clients": 32, "processes": 4,
     "request": {"kind": "near_vector", "metadata": ["uuid", "distance"]},
     "filter": null | {"property": "bucket", "operator": "less_than",
                       "values": [1, 10, 50, 99]}}

    {"loop": "open", "rate_per_s": 284.0, "clients": 64, "processes": 4, ...}

``limit`` is not a mix's to set: it is the configuration's ``k``. A closed
loop's client sends its next request on the reply. An open loop's client is
one of ``clients`` independent Poisson sources of ``rate_per_s / clients``
each (together a Poisson stream of ``rate_per_s``): a request is due at its
seeded time whether or not the one before it has come back, and its latency
runs from when it was due.

A client's plan is a long seeded list of (pool query, filter value, gap to
the next request). Query indices are uniform over the pool; filter values
come from a shuffled deck that holds each value equally often, so every seed
carries the same mix of work in another order. ``operator`` is any of
``OPERATORS``, by the wire protocol's name."""

from __future__ import annotations

import json
import os

import numpy as np

PLAN_LEN = 1 << 15   # requests planned per client; a client cycles past it
HERE = os.path.dirname(os.path.abspath(__file__))
LOOPS = ("closed", "open")
REQUEST_KINDS = ("near_vector",)
OPERATORS = {"less_than": np.less, "less_than_equal": np.less_equal,
             "greater_than": np.greater,
             "greater_than_equal": np.greater_equal,
             "equal": np.equal, "not_equal": np.not_equal}


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return check(json.load(f), name)


def check(mix: dict, name: str = "mix") -> dict:
    if mix["loop"] not in LOOPS:
        raise ValueError(f"traffic {name}: loop {mix['loop']!r} is none of "
                         f"{LOOPS}")
    if mix["loop"] == "open" and not mix["rate_per_s"] > 0:
        raise ValueError(f"traffic {name}: an open loop needs rate_per_s")
    if mix["request"]["kind"] not in REQUEST_KINDS:
        raise ValueError(f"traffic {name}: request kind "
                         f"{mix['request']['kind']!r} is none of "
                         f"{REQUEST_KINDS}")
    flt = mix.get("filter")
    if flt is not None and flt["operator"] not in OPERATORS:
        raise ValueError(f"traffic {name}: filter operator "
                         f"{flt['operator']!r} is none of {list(OPERATORS)}")
    return mix


def allowed(flt: dict, column: np.ndarray, value: int) -> np.ndarray:
    """bool [N]: the rows a request with this filter value may return."""
    return OPERATORS[flt["operator"]](column, value)


def client_plan(mix: dict, seed: int, client: int, pool: int):
    """-> (query index [PLAN_LEN] int32, filter value [PLAN_LEN] int64 or
    None, seconds to the next request's due time [PLAN_LEN] or None)."""
    rng = np.random.default_rng([seed, 7919, client])
    q = rng.integers(0, pool, PLAN_LEN, dtype=np.int32)
    flt = mix.get("filter")
    bounds = None
    if flt is not None:
        deck = np.resize(np.asarray(flt["values"], dtype=np.int64), PLAN_LEN)
        bounds = rng.permutation(deck)
    gaps = None
    if mix["loop"] == "open":
        gaps = rng.exponential(mix["clients"] / mix["rate_per_s"], PLAN_LEN)
    return q, bounds, gaps
