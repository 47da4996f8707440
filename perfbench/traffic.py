"""The one general traffic generator: a mix file in, a schedule out.

A traffic mix is a JSON file of parameters under ``perfbench/mixes/``.
This module turns (file, seed, seconds) into the list of arrivals the
runner offers.  The file fixes the work: which items and how many.  The
seed only orders the items, and later draws the token ids -- so every seed
offers the same multiset of work.

Schema (times in seconds, relative to the start of the window)::

    {"unit": "request",
     "arrival": {"mode": "backlog", "ramp_s": 3.0, "base": 16,
                 "per_second": 4.0},
     "items": [[prompt_len, output_len], ...]}

``backlog``: every arrival is due at ``-ramp_s``; there are
``base + ceil(per_second * (seconds + ramp_s))`` of them, rounded up to
whole multisets, enough that the queue is not empty when the window ends.
Items are dealt from successive seeded permutations of ``items``, so each
run of ``len(items)`` consecutive arrivals holds the whole multiset.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Arrival:
    due_s: float        # relative to the window's start; < 0 = before it
    item: int           # index into the mix's items


def load(name: str) -> dict:
    with open(os.path.join(HERE, "mixes", name + ".json")) as f:
        return json.load(f)


def rng_for(seed: int, tag: str) -> np.random.Generator:
    """A generator for one purpose of one seed; `tag` keeps the streams
    (order, tokens, sample) independent of each other."""
    return np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32,
         int.from_bytes(tag.encode()[:8].ljust(8, b"\0"), "little")])


def schedule(traffic: dict, seed: int, seconds: float) -> List[Arrival]:
    arr = traffic["arrival"]
    if arr["mode"] != "backlog":
        raise ValueError(f"unknown arrival mode {arr['mode']!r}")
    ramp = float(arr["ramp_s"])
    n = len(traffic["items"])
    count = int(arr["base"]) + math.ceil(
        float(arr["per_second"]) * (seconds + ramp))
    rng = rng_for(seed, "order")
    order: List[int] = []
    for _ in range(-(-count // n)):         # whole multisets
        order.extend(int(i) for i in rng.permutation(n))
    return [Arrival(-ramp, it) for it in order]


def offered_work(traffic: dict, arrivals: List[Arrival]) -> dict:
    """What a run offers, as counts: the same for every seed."""
    items = traffic["items"]
    pairs = sorted((items[a.item][0], items[a.item][1]) for a in arrivals)
    return {"n": len(arrivals), "pairs": pairs,
            "prompt_tokens": sum(p for p, _ in pairs),
            "output_tokens": sum(o for _, o in pairs)}
