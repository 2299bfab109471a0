"""From a profiler trace (``.xplane.pb``) to device time, busy share and gaps.

Reads the trace with ``jax.profiler.ProfileData`` and nothing of the
program. The window is the host annotation named ``window`` that the
benchmark wraps around its measured loop; everything is clipped to it.

- Device operations are the events of the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane, named by their HLO instruction (``fusion.12``,
  ``salo_paged_decode.7``); a loop's op spans the ops of its body, and
  only ops with none inside count towards ``top_ops``. Programs are the
  events of the ``XLA Modules`` line (``jit__chunk_fn(<id>)``).
- Busy time is the union of the operation intervals; idle gaps are its
  complement, each named by the innermost benchmark annotation on the host
  that covers the gap's midpoint.

  python chipbench/reduce.py TRACE.xplane.pb   # prints what it finds
"""
from __future__ import annotations

import collections
import dataclasses
import sys

import numpy as np

WINDOW = "window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Trace:
    window: tuple                  # (start_ns, end_ns)
    n_devices: int
    ops: list                      # (name, start_ns, end_ns, device, leaf)
    modules: list                  # (name, start_ns, end_ns, device)
    host: list                     # (name, start_ns, end_ns, depth)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def op_seconds(self, name: str) -> float:
        """Summed device time of the operations ``name.<n>``, such as a
        Pallas kernel's calls."""
        return sum(e - s for n, s, e, _, _ in self.ops
                   if base(n) == name) * 1e-9

    def op_calls(self, name: str) -> int:
        """How many operations ``name.<n>`` ran."""
        return sum(base(n) == name for n, _, _, _, _ in self.ops)

    def module_runs(self, name: str) -> tuple:
        """(launches, summed device seconds) of programs whose name holds
        ``name``."""
        runs = [(s, e) for n, s, e, _ in self.modules if name in n]
        return len(runs), sum(e - s for s, e in runs) * 1e-9

    def busy_intervals(self, device: int) -> np.ndarray:
        iv = sorted((s, e) for _, s, e, d, _ in self.ops if d == device)
        merged: list = []
        for s, e in iv:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return np.asarray(merged, np.float64).reshape(-1, 2)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        tot = sum(float(np.sum(b[:, 1] - b[:, 0])) for b in
                  (self.busy_intervals(d) for d in range(self.n_devices)))
        return tot * 1e-9 / max(self.n_devices, 1)

    def gaps(self, device: int = 0, k: int = 10) -> list:
        """The ``k`` longest idle intervals of one device inside the
        window, as (host annotation, seconds)."""
        b = self.busy_intervals(device)
        starts = np.concatenate([[self.window[0]], b[:, 1]])
        ends = np.concatenate([b[:, 0], [self.window[1]]])
        order = np.argsort(starts - ends)[:k]
        return [(self.host_at((starts[i] + ends[i]) / 2),
                 (ends[i] - starts[i]) * 1e-9)
                for i in order if ends[i] > starts[i]]

    def host_at(self, t: float) -> str:
        best, depth = "host", -1
        for n, s, e, d in self.host:
            if s <= t < e and d > depth and n != WINDOW:
                best, depth = n, d
        return best

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` op names (``fusion.12``) with most device time, over
        ops with none nested inside them."""
        tot: dict = collections.defaultdict(float)
        for n, s, e, _, leaf in self.ops:
            if leaf:
                tot[n] += (e - s) * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:k]


def _clip(s: float, e: float, w: tuple):
    s, e = max(s, w[0]), min(e, w[1])
    return (s, e) if e > s else None


def op_name(text: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    return text.split(" = ", 1)[0].lstrip("%")


def base(name: str) -> str:
    """'salo_paged_decode.7' -> 'salo_paged_decode'."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _leaves(events: list) -> list:
    """Mark each (name, start, end) of one line as a leaf unless another
    event of the line starts inside it."""
    out = []
    for i, (n, s, e) in enumerate(events):
        leaf = i + 1 == len(events) or events[i + 1][1] >= e
        out.append((n, s, e, leaf))
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                stack: list = []
                for ev in sorted(line.events, key=lambda e: e.start_ns):
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    while stack and stack[-1] <= s:
                        stack.pop()
                    host.append((ev.name, s, e, len(stack)))
                    stack.append(e)
    wins = [(s, e) for n, s, e, _ in host if n == WINDOW]
    if not wins:
        raise ValueError(f"{path}: no host annotation named {WINDOW!r}")
    window = max(wins, key=lambda w: w[1] - w[0])
    ops, modules = [], []
    for dev, plane in enumerate(sorted(devices, key=lambda p: p.name)):
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = []
            for ev in line.events:
                iv = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, window)
                if iv:
                    evs.append((ev.name, iv[0], iv[1]))
            if line.name == MODULES_LINE:
                modules += [(n, s, e, dev) for n, s, e in evs]
                continue
            evs.sort(key=lambda x: (x[1], -x[2]))
            ops += [(op_name(n), s, e, dev, leaf)
                    for n, s, e, leaf in _leaves(evs)]
    host = [h for h in host if _clip(h[1], h[2], window)]
    return Trace(window, len(devices), ops, modules, host)


def dump(path: str) -> None:
    """Print the planes and lines of a trace and the busiest names."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            tot: dict = collections.defaultdict(float)
            for ev in evs:
                tot[ev.name] += ev.duration_ns
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:8]
            print(f"  line {line.name!r}: {len(evs)} events; busiest "
                  f"{[(n, round(t * 1e-6, 3)) for n, t in top]}")
            for ev in evs[:2]:
                print(f"    e.g. {ev.name!r} start {ev.start_ns} dur "
                      f"{ev.duration_ns} stats "
                      f"{dict(ev.stats) if ev.stats else {}}")


if __name__ == "__main__":
    dump(sys.argv[1])
