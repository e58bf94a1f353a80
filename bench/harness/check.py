"""The comparison that decides ``correct``.

After the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and holding the longest one, is
run through the configuration's plain float32 reference: each prompt with
the tokens that were served for it. For every served token the gap by
which its reference logit lies below the reference's best logit at that
position is read; greedy decoding served the best token, so the gap is 0
up to the program's rounding. The number compared is the widest gap.

The control (``control="int8"`` or ``"fp8"``, the configuration's
``correct.control``) reads, at the same positions, the gap of the token
that the reference computed in that format puts first: what a program
that served in it would have scored.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# sequences are padded to a multiple of this many tokens, so one run
# compiles the reference for a few lengths only
LENGTH_BUCKET = 512


@dataclass
class Served:
    prompt: np.ndarray
    output: List[int]


def sample(served: List[Served], seed: int, min_tokens: int) -> List[Served]:
    """The longest request (prompt plus output), then others in an order
    drawn from ``seed``, until ``min_tokens`` served tokens are held."""
    if not served:
        return []
    total = [len(s.prompt) + len(s.output) for s in served]
    first = int(np.argmax(total))
    rest = [i for i in np.random.default_rng(seed).permutation(len(served))
            if i != first]
    out, n = [served[first]], len(served[first].output)
    for i in rest:
        if n >= min_tokens:
            break
        out.append(served[i])
        n += len(served[i].output)
    return out


@functools.lru_cache(maxsize=None)
def _gap_fn(code, control: Optional[str]):
    def gaps(w, tokens, rows, targets):
        h = code.hidden(w, tokens)[rows]
        lg = code.logits(w, h)
        if control:
            hc = code.hidden(w, tokens, low=control)[rows]
            targets = jnp.argmax(code.logits(w, hc, low=control), -1)
        got = jnp.take_along_axis(lg, targets[:, None], axis=1)[:, 0]
        return jnp.max(lg, -1) - got
    return jax.jit(gaps)


def token_gaps(code, params, item: Served, rows_pad: int,
               control: Optional[str] = None) -> np.ndarray:
    """Gap of each served token of one request (see module docstring)."""
    n, p = len(item.output), len(item.prompt)
    seq = np.concatenate([item.prompt, np.asarray(item.output[:-1],
                                                  np.int32)])
    T = -(-len(seq) // LENGTH_BUCKET) * LENGTH_BUCKET
    tokens = np.zeros(T, np.int32)
    tokens[:len(seq)] = seq
    rows = np.zeros(rows_pad, np.int32)
    rows[:n] = np.arange(p - 1, p - 1 + n)
    targets = np.zeros(rows_pad, np.int32)
    targets[:n] = item.output
    g = _gap_fn(code, control)(params, tokens, rows, targets)
    return np.asarray(g, np.float64)[:n]


def widest_gap(code, params, items: List[Served], rows_pad: int,
               control: Optional[str] = None) -> float:
    worst = 0.0
    for item in items:
        g = token_gaps(code, params, item, rows_pad, control)
        worst = max(worst, float(np.max(g)) if g.size else 0.0)
    return worst
