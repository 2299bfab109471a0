"""Seeded traffic generators, read from a traffic file's parameters.

A serving run replays one schedule: the prompt lengths, output lengths and
inter-arrival gaps are the quantiles of the stated distributions at the
midpoints of ``n`` equal strata, put in an order drawn once from the
traffic file's ``schedule_seed``. The run's seed draws the token ids (and
the weights), so seeds change what is computed, not how much or when.

The schedule opens with ``backlog`` requests queued when the window opens
(the queue an offered rate above what the engine sustains has built up
before it), followed by open-loop Poisson arrivals at ``rate_per_s``
through the window, at the times the gaps give: none are moved to fit the
window, and those that fall after it are not sent.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float          # scheduled arrival, seconds after the window opens
    prompt: np.ndarray    # (P,) int32 token ids
    max_new: int          # output tokens asked for


def _strata(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the stratum midpoints of ``dist``:
    ``{"dist": "log_uniform" | "uniform", "min": a, "max": b}``."""
    u, lo, hi = _strata(n), dist["min"], dist["max"]
    if dist["dist"] == "log_uniform":
        x = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
        return np.clip(np.round(x), lo, hi).astype(np.int64)
    if dist["dist"] == "uniform":
        return (lo + np.floor(u * (hi - lo + 1))).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def gaps(kind: str, rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the stratum midpoints of the arrival
    process: exponential quantiles for ``poisson``."""
    if kind != "poisson":
        raise ValueError(f"unknown arrival process {kind!r}")
    return -np.log1p(-_strata(n)) / rate


def serve_requests(traffic: dict, seed: int, seconds: float,
                   vocab: int) -> list:
    """The requests of one run, sorted by due time: the backlog, due at 0,
    then the arrivals due inside the window."""
    n_back = int(traffic["backlog"])
    n_arr = int(np.ceil(traffic["rate_per_s"] * seconds))
    n = n_back + n_arr
    order = np.random.default_rng(traffic["schedule_seed"])
    prompt = order.permutation(lengths(traffic["prompt_tokens"], n))
    out = order.permutation(lengths(traffic["output_tokens"], n))
    due = np.concatenate([np.zeros(n_back), np.cumsum(order.permutation(
        gaps(traffic["arrivals"], traffic["rate_per_s"], n_arr)))])
    rng = np.random.default_rng(int(seed))
    return [Request(float(t), rng.integers(0, vocab, int(p), np.int32),
                    int(m)) for t, p, m in zip(due, prompt, out)
            if t < seconds]


def train_tokens(traffic: dict, seed: int, step: int, vocab: int
                 ) -> np.ndarray:
    """(batch, seq + 1) token ids of one training step: documents with
    lengths from ``traffic["docs"]`` packed end to end, each opening with
    id 0 and drawing its tokens Zipf-distributed over its own random
    permutation offset of the vocabulary. Rows of every step differ; the
    step's seed is (seed, step)."""
    B, S = traffic["batch"], traffic["seq"]
    rng = np.random.default_rng((int(seed), int(step)))
    total = B * (S + 1)
    n_docs = total // traffic["docs"]["min"] + 1
    doc_len = rng.permutation(lengths(traffic["docs"], n_docs))
    doc_id = np.repeat(np.arange(n_docs), doc_len)[:total]
    offset = rng.integers(0, vocab, n_docs)[doc_id]
    ranks = np.minimum(rng.zipf(traffic["zipf_a"], total), vocab) - 1
    tokens = (offset + ranks) % vocab
    starts = np.concatenate([[0], np.cumsum(doc_len)[:-1]])
    tokens[starts[starts < total]] = 0
    return tokens.reshape(B, S + 1).astype(np.int32)
