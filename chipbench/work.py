"""Work a step needs, counted from shapes and positions the benchmark made.

Nothing here reads a counter of the program: operations come from the
(query, key) pairs each layer attends (causal, the last ``window`` keys
plus ``sinks`` leading keys under SALO, every earlier key where the window
is ``None``) and bytes from the minimal traffic of each operand, so a
kernel that skips wasted work raises its share. Layers are counted by kind,
as the configuration's architecture module lists them
(``attention_layers``, ``matmul_params_per_token``).
"""
from __future__ import annotations

import numpy as np


def attended(t, window, sinks: int) -> np.ndarray:
    """Keys the query at position ``t`` attends: the window up to and
    including ``t``, plus the sinks that lie before the window; every key
    up to ``t`` where ``window`` is None."""
    t = np.asarray(t, np.int64)
    if window is None:
        return t + 1
    return (np.minimum(t, window - 1) + 1
            + np.clip(t - (window - 1), 0, sinks))


def pairs_causal_prefix(n: int, window, sinks: int) -> int:
    """Attended pairs of a whole causal sequence of ``n`` tokens."""
    return int(attended(np.arange(n), window, sinks).sum())


def forward_flops(arch, m: dict, positions, head_rows: int) -> float:
    """Model FLOPs of a forward pass over tokens at ``positions`` (each
    attending its keys in every layer) with the output head applied at
    ``head_rows`` of them, for the dims ``m`` of the architecture module
    ``arch``. Recomputation is not counted."""
    positions = np.asarray(positions, np.int64)
    dense = 2.0 * arch.matmul_params_per_token(m) * positions.size
    attn = sum(4.0 * count * H * hd * float(
        attended(positions, window, sinks).sum())
        for count, H, _, hd, window, sinks in arch.attention_layers(m))
    return dense + attn + 2.0 * m["d"] * m["V"] * head_rows


def ring_pages(t, window, sinks: int, page: int) -> np.ndarray:
    """Pages of the paged ring cache that hold a key the query at ``t``
    attends: ``ceil(sinks/page)`` sink pages, then a ring of
    ``ceil(window/page)`` pages where position ``p >= sinks`` sits at ring
    slot ``(p - sinks) % ring_cap``. Where ``window`` is None, position
    ``p`` sits on page ``p // page``, and every page up to ``t``'s counts."""
    t = np.asarray(t, np.int64)
    if window is None:
        return t // page + 1
    cap = -(-window // page) * page
    sink_pages = -(-np.minimum(sinks, t + 1) // page)
    lo = np.maximum(sinks, t - window + 1)
    n = np.maximum(t - lo + 1, 0)
    s0 = (lo - sinks) % cap
    end = s0 + n - 1
    straight = end // page - s0 // page + 1
    wrapped = (cap - 1) // page - s0 // page + 1 + (end - cap) // page + 1
    ring = np.where(end < cap, straight, wrapped)
    ring = np.where(n >= cap, cap // page, np.where(n > 0, ring, 0))
    return sink_pages + ring
