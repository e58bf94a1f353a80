"""The one traffic generator: turns a traffic file's parameters and a seed
into requests.

Prompt lengths, output lengths and inter-arrival gaps are quantiles of the
stated distributions, shuffled once by the file's ``schedule_seed``: every
run of a cell offers the same schedule, and its ``--seed`` draws the token
ids (and, elsewhere, the weights). So runs differ only by noise, which
keeps a cell's spread, and so its bounds, small.

A traffic file (``bench/traffic/<name>.json``) holds:

- ``kind``: ``"open_loop"`` (arrivals on a fixed schedule at ``rate_per_s``,
  Poisson gaps) or ``"saturated"`` (requests are handed in whenever fewer
  than ``backlog`` wait, so the queue never runs dry);
- ``prompt_len`` / ``output_len``: ``{"median", "sigma", "min", "max"}`` of
  a lognormal, clipped; prompt lengths are rounded up to ``grid`` tokens;
- ``requests``: how many distinct requests one run draws;
- ``schedule_seed``: the shuffle of lengths and gaps (default 0);
- ``lead_s``: seconds of traffic before the measured window opens;
- ``backlog_per_slot`` (saturated): requests kept waiting, per ring slot;
- optional ``knee`` (open loop): how ``bench/knee.py`` judged the rate:
  the lone request's rate, the multiples of its median time to first
  token and time per output token that the tails may reach, the queue
  growth allowed, and the fraction of the knee the cell runs at.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np

# token ids are drawn from [FIRST_TOKEN, vocab): the lowest ids are left
# out as a tokenizer keeps them for control tokens
FIRST_TOKEN = 3


@dataclass
class Request:
    index: int
    prompt: np.ndarray      # int32 token ids
    max_new: int
    gap_s: float            # seconds after the previous request (open loop)


def load(path: Path) -> dict:
    spec = json.loads(Path(path).read_text())
    if spec.get("kind") not in ("open_loop", "saturated"):
        raise ValueError(f"{path}: kind must be open_loop or saturated")
    if spec["kind"] == "open_loop" and not spec.get("rate_per_s", 0) > 0:
        raise ValueError(f"{path}: an open loop needs rate_per_s > 0")
    return spec


def _lognormal_quantiles(n: int, dist: dict) -> np.ndarray:
    """``n`` midpoint quantiles of a clipped lognormal, ascending."""
    from statistics import NormalDist
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(x, dist["min"], dist["max"])


def lengths(n: int, dist: dict) -> np.ndarray:
    x = np.ceil(_lognormal_quantiles(n, dist))
    grid = int(dist.get("grid", 1))
    x = np.ceil(x / grid) * grid
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def generate(spec: dict, seed: int, vocab: int) -> List[Request]:
    """The run's requests in submission order. Same ``seed``, same list.

    The schedule (each request's prompt length, output length and gap,
    in order) comes from the traffic file alone, so every seed offers the
    engine the same work at the same instants and runs differ by noise,
    not by which long prompts happened to bunch up; the seed draws the
    token ids."""
    n = int(spec["requests"])
    order = np.random.default_rng(int(spec.get("schedule_seed", 0)))
    plens = order.permutation(lengths(n, spec["prompt_len"]))
    olens = order.permutation(lengths(n, spec["output_len"]))
    if spec["kind"] == "open_loop":
        q = (np.arange(n) + 0.5) / n
        gaps = order.permutation(-np.log1p(-q) / float(spec["rate_per_s"]))
    else:
        gaps = np.zeros(n)
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(FIRST_TOKEN, vocab, int(p),
                                    dtype=np.int32), int(o), float(g))
            for i, (p, o, g) in enumerate(zip(plens, olens, gaps))]


def distinct_prompt_lengths(spec: dict) -> List[int]:
    return sorted({int(x) for x in lengths(int(spec["requests"]),
                                           spec["prompt_len"])})

