"""A serving cell: a saturated continuous engine under open-loop traffic.

Set-up makes the weights on the device from the seed, builds the engine as
``launch/serve.py`` builds it, and warms its two programs (one prefill
chunk, one decode step at ``max_batch``) with one request that runs the
whole lifecycle. It then queues the traffic's backlog and steps the engine
until every request that took a row has its first token, so the window
opens on a full engine (``fill``). The window then drives
``ContinuousEngine.submit`` and ``.step`` for ``seconds``: each arrival is
submitted once it is due, and each output token is stamped when the step
that made it returns. The offered rate lies above what the engine
sustains, so the queue never empties and every row stays live; the run
logs the fewest live rows and the queue at the close to show it.

End to end: ``output_tokens_per_s``, the output tokens delivered in the
window over the seconds from its start to the return of the last step
that delivered any (all the work over its own time, not stepped by one
step's tokens against the window's length). No tail is end to end: above
capacity a request's first token waits on the queue the traffic built,
and the gaps between tokens are the engine's step times, whose 95th
percentile sits between steps with and without a prefill chunk (PERF.md).

``correct``: after the window, on a sample of served requests drawn from
the seed (the longest among them), the architecture's float32 reference
(``reference/decoder.py`` for ``dense_decoder``) judges each served token
by how far its logit lies below the reference's best at that position; the
widest such gap must stay within the cell's limit (``checks/<cell>.json``).
"""
from __future__ import annotations

import gc
import time
import types

import numpy as np

from chipbench import gen, harness, model


class _Tracked:
    __slots__ = ("req", "times")

    def __init__(self, req):
        self.req, self.times = req, []


def build(spec: dict, traffic: dict, seed: int, tracing: bool):
    """(engine, params) with the weights made from the seed."""
    import jax

    from repro.launch.serve import continuous_setup
    from repro.models.model import build_model
    from repro.obs import Observability
    from repro.serve.engine import ContinuousEngine

    cfg = harness.arch(spec).model_config(spec)
    prog = build_model(cfg)
    params = model.make_params(spec, seed)
    model.check_tree(params, jax.eval_shape(prog.init,
                                            jax.random.PRNGKey(0)))
    e = traffic["engine"]
    ccfg, _ = continuous_setup(cfg, max_batch=e["max_batch"], page=e["page"],
                               chunk=e["chunk"], kv_dtype=e["kv_dtype"])
    obs = Observability(tracing=tracing, trace_capacity=1 << 22)
    return ContinuousEngine(prog, ccfg, obs=obs), params


def warm_up(eng, params, vocab: int, seed: int) -> None:
    """One request through admission, two prefill chunks, one decode step
    and release: every program the window runs, at its one shape."""
    import jax

    rng = np.random.default_rng(int(seed))
    prompt = rng.integers(0, vocab, eng.ccfg.chunk + 1, np.int32)
    eng.submit(prompt, 2)
    eng.run(params)
    jax.block_until_ready(eng.slabs)


def step(eng, params, live: list, work=None) -> list:
    """One engine step; stamps each token on ``perf_counter`` and, with
    ``work``, records the positions the step computed. Returns the
    requests still live."""
    before = [(t.req.prefilled, len(t.req.out)) for t in live]
    eng.step(params)
    stamp = time.perf_counter()
    still = []
    for t, (pf, n_out) in zip(live, before):
        req = t.req
        if work is not None and req.prefilled > pf:
            work.prefill_chunks.append((pf, req.prefilled))
        if len(req.out) > n_out:
            t.times.append(stamp)
            if work is not None:
                work.head_rows += 1
                if n_out:
                    work.decode_positions.append(req.prompt_len + n_out - 1)
        if req.state not in ("done", "failed"):
            still.append(t)
    return still


def submit(eng, r, tracked: list, live: list) -> None:
    rid = eng.submit(r.prompt, r.max_new)
    req = eng.batcher.queue[-1]
    if req.rid != rid:
        raise RuntimeError("submitted request not found")
    t = _Tracked(req)
    tracked.append(t)
    live.append(t)


def fill(eng, params, requests: list) -> tuple:
    """Set-up: queue the backlog (the requests due at 0) and step until
    every request that took a row in the first step has its first token.
    Returns (tracked, live, the arrivals still to come)."""
    tracked, live = [], []
    backlog = [r for r in requests if r.due_s <= 0]
    for r in backlog:
        submit(eng, r, tracked, live)
    live = step(eng, params, live)
    first = [q for q in eng.batcher.rows if q is not None]
    while any(q.state == "prefill" for q in first):
        live = step(eng, params, live)
    return tracked, live, requests[len(backlog):]


def drive(eng, params, tracked: list, live: list, arrivals: list,
          seconds: float, prof):
    """The measured window. Token stamps become seconds since it opened.
    Returns (the work of the steps run while ``prof`` profiled, the fewest
    live rows after any step, the requests queued at the close)."""
    import jax

    work = types.SimpleNamespace(decode_positions=[], prefill_chunks=[],
                                 head_rows=0, steps=0)
    on = prof.on
    fewest = eng.ccfg.max_batch
    drain = lambda: jax.block_until_ready(eng.slabs)  # noqa: E731
    i, n = 0, len(arrivals)
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        prof.tick(now - t0, drain)
        with harness.annotate(on, "submit"):
            while i < n and t0 + arrivals[i].due_s <= now:
                submit(eng, arrivals[i], tracked, live)
                i += 1
        if eng.batcher.idle:
            with harness.annotate(on, "wait_for_arrival"):
                nxt = t0 + arrivals[i].due_s if i < n else t_end
                time.sleep(max(0.0, min(nxt, t_end) - now))
            continue
        with harness.annotate(on, "engine.step"):
            live = step(eng, params, live, work if prof.active else None)
        work.steps += prof.active
        fewest = min(fewest, sum(q is not None for q in eng.batcher.rows))
    prof.close(drain)
    for t in tracked:
        t.times = [s - t0 for s in t.times]
    work.decode_positions = np.asarray(work.decode_positions)
    return work, fewest, len(eng.batcher.queue)


def end_to_end(tracked: list, seconds: float) -> tuple:
    """(output tokens per second, failed requests)."""
    stamps = [s for t in tracked for s in t.times if 0 < s <= seconds]
    rate = len(stamps) / max(stamps) if stamps else 0.0
    failed = sum(t.req.state == "failed" for t in tracked)
    return rate, failed


def sample_served(tracked: list, seed: int, tokens: int) -> list:
    """Requests to check, as (prompt, served tokens): the longest finished
    one (or, where none finished in the window, the one with most tokens
    served), then others drawn from the seed, finished ones first, until
    ``tokens`` served tokens are in the sample. A served token is final
    whether or not its request finished."""
    reqs = [t.req for t in tracked if len(t.req.out) >= 2]
    if not reqs:
        return []
    key = lambda r: (r.state == "done", r.prompt_len + len(r.out), r.rid)  # noqa: E731,E501
    reqs.sort(key=key)
    picked = [reqs.pop()]
    rng = np.random.default_rng(int(seed) + 1)
    rest = [reqs[j] for j in rng.permutation(len(reqs))]
    rest.sort(key=lambda r: r.state != "done")
    for r in rest:
        if sum(len(p.out) for p in picked) >= tokens:
            break
        picked.append(r)
    return [(np.asarray(r.prompt), np.asarray(r.out, np.int32))
            for r in picked]


def widest_gap(spec: dict, seed: int, sample: list,
               control: bool = False) -> float:
    """Widest gap of the sample's served tokens under the reference (made
    from the benchmark's own weights, which are made again here)."""
    arch = harness.arch(spec)
    params = model.make_params(spec, seed)
    m = arch.dims(spec)
    gaps = [arch.served_gaps(m, params, p, o, control=control)
            for p, o in sample]
    return float(max(float(np.max(g)) for g in gaps))


def open_session(spec: dict, traffic: dict, seed: int, seconds: float,
                 trace: bool):
    """Set-up of a run: (engine, params, tracked, live, arrivals)."""
    eng, params = build(spec, traffic, seed, trace)
    vocab = spec["vocab_size"]
    warm_up(eng, params, vocab, seed)
    requests = gen.serve_requests(traffic, seed, seconds, vocab)
    t = time.perf_counter()
    steps = eng.counters["engine_steps"]
    session = (eng, params, *fill(eng, params, requests))
    harness.log(f"set-up: fill {time.perf_counter() - t:.2f}s in "
                f"{eng.counters['engine_steps'] - steps} steps")
    return session


def run(cell: dict, spec: dict, traffic: dict, check: dict, seed: int,
        seconds: float, trace: bool, t_start: float, bench: dict,
        device: dict) -> None:
    clock = harness.CompileClock()
    eng, params, tracked, live, arrivals = open_session(
        spec, traffic, seed, seconds, trace)
    prof = harness.Profile(trace, seconds)
    setup_s = time.perf_counter() - t_start
    compiles0 = clock.compiles
    work, fewest, queued = drive(eng, params, tracked, live, arrivals,
                                 seconds, prof)
    harness.log(f"window: {len(tracked)} requests, fewest live rows "
                f"{fewest} of {eng.ccfg.max_batch}, queued at the close "
                f"{queued}, finished "
                f"{sum(t.req.state == 'done' for t in tracked)}, "
                f"compilations inside the window "
                f"{clock.compiles - compiles0}, set-up compile "
                f"{clock.seconds:.1f}s, persistent-cache hits "
                f"{clock.cache_hits}")
    tokens_per_s, failed = end_to_end(tracked, seconds)
    device = {**device, "memory_peak_bytes": harness.memory_peak_bytes()}
    result = {"correct": False, "attempted": len(tracked), "failed": failed}
    if trace:
        lo, hi = prof.host_span
        harness.read_trace(
            prof, bench, cell["name"], spec, device, result,
            spans=[e for e in eng.tracer.events()
                   if e["ph"] == "X" and lo <= e["ts"] <= hi],
            work=work, engine=traffic["engine"])
    else:
        result["metrics"] = {
            "output_tokens_per_s": {"value": tokens_per_s,
                                    "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["device"] = device
    sample = sample_served(tracked, seed, check["sample_tokens"])
    del eng, params, tracked, live
    gc.collect()
    gap = widest_gap(spec, seed, sample) if sample else None
    limit = check["max_logit_gap"]
    result["correct"] = gap is not None and gap <= limit
    harness.log(f"checked {len(sample)} requests, "
                f"{sum(len(o) for _, o in sample)} served tokens")
    harness.emit(result, {"max_logit_gap": {"value": gap, "limit": limit}})
