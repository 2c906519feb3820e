"""The one traffic generator: reads a mix's parameter file and turns it,
with a seed, into requests.

Arrivals are an open loop: Poisson, on a schedule, whatever the server
does.  A mix file (``chipbench/traffic/<mix>.json``) holds:

    rate_per_s  mean arrivals per second
    prompt, output   length distributions: {"dist": "exponential",
                "mean", "min", "max"} (the most even distribution that a
                published mean alone allows), {"dist": "lognormal",
                "median", "sigma", "min", "max"} or {"dist": "uniform",
                "min", "max"}
    block       requests per stratified block (see below)
    warmup_s    seconds of the same traffic served before the window
    check_requests   finished requests the correctness check samples

Every seed gets the same work in another order.  Lengths and gaps between
arrivals come in blocks of ``block`` requests: a block holds the
distribution's quantiles at (j + 0.5) / block, j = 0 .. block - 1, and the
seed only permutes them within the block.  So any stretch of whole blocks
carries the same multiset of prompt lengths, output lengths and gaps on
every seed, and runs with different seeds differ by the order of the
work, not its amount.  The warm-up and the window draw from two streams
of one seed, so the window starts on a whole block; a block is sized to last about one window.  Token ids are uniform over the
vocabulary.
"""
from __future__ import annotations

import dataclasses
import json
import math
import statistics
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"

_NORMAL = statistics.NormalDist()
STREAM_RIDS = 1_000_000          # request ids of stream k start at k * this


def load(name: str) -> dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def quantile(dist: dict, q: float) -> int:
    """Length at quantile ``q`` of a length distribution, clipped."""
    if dist["dist"] == "exponential":
        v = -dist["mean"] * math.log1p(-q)
    elif dist["dist"] == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(q))
    elif dist["dist"] == "uniform":
        v = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(max(round(v), dist["min"]), dist["max"]))


@dataclasses.dataclass
class Item:
    """One request as the generator made it; ``due_s`` is seconds after
    the schedule's origin."""

    rid: int
    prompt: np.ndarray
    max_new: int
    due_s: float


class Traffic:
    """Requests of one mix for one seed, made block by block on demand."""

    def __init__(self, spec: dict, seed: int, *, vocab: int, max_len: int,
                 stream: int = 0):
        self.spec, self.vocab, self.max_len = spec, vocab, max_len
        self.block = int(spec.get("block", 64))
        self.rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32,
                                          stream])
        self.first_rid = STREAM_RIDS * stream
        qs = (np.arange(self.block) + 0.5) / self.block
        self._prompt_q = [quantile(spec["prompt"], q) for q in qs]
        self._output_q = [quantile(spec["output"], q) for q in qs]
        worst = max(self._prompt_q) + max(self._output_q)
        if worst > max_len:
            raise ValueError(f"traffic: a prompt of {max(self._prompt_q)} "
                             f"and {max(self._output_q)} new tokens exceed "
                             f"max_len {max_len}")
        rate = float(spec["rate_per_s"])
        self._gap_q = [-math.log1p(-q) / rate for q in qs]
        self.items: list[Item] = []
        self._t = 0.0

    def _extend(self):
        rng, n0 = self.rng, self.first_rid + len(self.items)
        prompts = rng.permutation(self._prompt_q)
        outputs = rng.permutation(self._output_q)
        gaps = rng.permutation(self._gap_q)
        for j in range(self.block):
            self._t += float(gaps[j])
            prompt = rng.integers(0, self.vocab, int(prompts[j]),
                                  dtype=np.int32)
            self.items.append(Item(n0 + j, prompt, int(outputs[j]),
                                   self._t))

    def item(self, i: int) -> Item:
        while len(self.items) <= i:
            self._extend()
        return self.items[i]
