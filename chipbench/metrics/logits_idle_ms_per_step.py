"""Milliseconds per decode step in which device 0 sits idle while the host
copies the step's logits (the engine's ``logits_to_host`` span) or picks
its tokens (``sample``): the part of the window outside every device
operation that lies inside those host events of the profiler trace,
divided by the number of ``logits_to_host`` events."""

import numpy as np

SPANS = ("logits_to_host", "sample")


def _merged(iv: list) -> list:
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _busy_until(busy: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Busy nanoseconds of the merged, sorted intervals ``busy`` that lie
    before each time in ``x``."""
    if not len(busy):
        return np.zeros_like(x)
    done = np.concatenate([[0.0], np.cumsum(busy[:, 1] - busy[:, 0])])
    i = np.searchsorted(busy[:, 0], x, side="right")
    last = np.maximum(i - 1, 0)
    part = np.where(i > 0, np.minimum(x, busy[last, 1]) - busy[last, 0], 0.0)
    return done[last] + part


def read(ctx):
    t = ctx.trace
    steps = sum(n == SPANS[0] for n, *_ in t.host)
    if not t.n_devices or not steps:
        return None
    lo, hi = t.window
    host = np.asarray(_merged([[max(s, lo), min(e, hi)]
                               for n, s, e, _ in t.host
                               if n in SPANS and min(e, hi) > max(s, lo)]),
                      np.float64).reshape(-1, 2)
    busy = t.busy_intervals(0)
    inside = _busy_until(busy, host[:, 1]) - _busy_until(busy, host[:, 0])
    idle_ns = float(np.sum(host[:, 1] - host[:, 0]) - np.sum(inside))
    return idle_ns * 1e-6 / steps
