"""Observability layer: metrics registry (labeled counters / gauges /
log-bucketed histograms, mergeable snapshots), span tracer (ring buffer,
injectable clock, Chrome-trace export), engine/batcher instrumentation
(lifecycle latency metrics, counters-dict compatibility, snapshot
round-trip incl. old-format snapshots), and FT event plumbing.

The two hard contracts pinned here and gated in benchmarks/obs_stats.py:
disabled observability adds nothing to any jitted computation, and the
registry rides the engine snapshot/restore path exactly as the old
``counters`` dict did."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models.model import build_model
from repro.obs import (MetricsRegistry, Observability, Tracer,
                       merge_snapshots, summary_line, validate_chrome_trace)
from repro.obs.metrics import BASE, bucket_index
from repro.obs.trace import NULL_TRACER
from repro.serve.engine import ContinuousConfig, ContinuousEngine

RNG = np.random.default_rng(7)


@pytest.fixture(scope="module")
def stack():
    cfg = get_smoke("smollm-135m")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.models.layers import salo_pattern
    from repro.serve.paged_cache import layout_for_pattern
    lay = layout_for_pattern(salo_pattern(cfg, causal=True), 8)
    return cfg, model, params, lay


def _engine(model, lay, *, max_batch=4, obs=None, n_pages=None):
    return ContinuousEngine(model, ContinuousConfig(
        n_pages=n_pages or 1 + max_batch * lay.pages_per_req, page=8,
        chunk=8, max_batch=max_batch), obs=obs)


# ============================ registry ================================== #
def test_registry_counters_gauges_labels():
    reg = MetricsRegistry()
    reg.inc("steps")
    reg.inc("steps", 2)
    assert reg.value("steps") == 3
    reg.inc("finished", priority=0)
    reg.inc("finished", priority=1)
    reg.inc("finished", priority=1)
    assert reg.value("finished", priority=0) == 1
    assert reg.value("finished", priority=1) == 2
    assert reg.total("finished") == 3
    reg.set("resident", 7.0)
    reg.set("resident", 5.0)           # gauges overwrite
    assert reg.value("resident") == 5.0
    # label mismatch and kind re-declaration are hard errors
    with pytest.raises(ValueError):
        reg.inc("finished", tenant="a")
    with pytest.raises(ValueError):
        reg.set("steps", 1.0)


def test_histogram_percentiles_nearest_rank():
    reg = MetricsRegistry()
    for v in (0.01, 0.02, 0.03, 0.5):
        reg.observe("lat", v)
    p = reg.percentiles("lat", qs=(0.5, 0.99))
    # nearest-rank: p99 of 4 samples is the max sample's bucket, and the
    # estimate is clamped to the exact observed [min, max]
    assert abs(p["p50"] - 0.02) / 0.02 < 0.25
    assert abs(p["p99"] - 0.5) / 0.5 < 0.25
    assert p["count"] == 4
    assert p["mean"] == pytest.approx(0.14)
    h = reg.merged_hist("lat")
    assert h.min == 0.01 and h.max == 0.5
    # every estimate stays within one bucket width of the true quantile
    for q in (0.1, 0.5, 0.9):
        est = h.percentile(q)
        assert 0.01 <= est <= 0.5
    # empty histogram: NaN percentiles, zero count
    empty = reg.percentiles("never_observed_family_x")
    assert math.isnan(empty["p50"]) and empty["count"] == 0


def test_bucket_index_resolution():
    # adjacent bucket edges differ by BASE (~19%) — the resolution claim
    for x in (1e-6, 0.004, 1.0, 37.5):
        i = bucket_index(x)
        assert BASE ** i <= x < BASE ** (i + 1)


def _random_snapshot(rng):
    reg = MetricsRegistry()
    for _ in range(rng.integers(1, 5)):
        reg.inc("c", float(rng.integers(1, 10)), shard=int(rng.integers(3)))
    reg.set("g", float(rng.integers(100)))
    for _ in range(int(rng.integers(1, 20))):
        reg.observe("h", float(rng.uniform(1e-4, 10.0)))
    return reg.snapshot()


def test_merge_snapshots_associative_commutative():
    snaps = [_random_snapshot(RNG) for _ in range(3)]
    a, b, c = snaps
    left = merge_snapshots(merge_snapshots(a, b), c)
    right = merge_snapshots(a, merge_snapshots(b, c))
    assert left == right
    assert merge_snapshots(a, b) == merge_snapshots(b, a)
    # counters add, gauges max, histogram counts add
    m = merge_snapshots(a, b)
    ca = sum(a["c"]["cells"].values())
    cb = sum(b["c"]["cells"].values())
    assert sum(m["c"]["cells"].values()) == pytest.approx(ca + cb)
    ga = list(a["g"]["cells"].values())[0]
    gb = list(b["g"]["cells"].values())[0]
    assert list(m["g"]["cells"].values())[0] == max(ga, gb)
    ha = list(a["h"]["cells"].values())[0]["count"]
    hb = list(b["h"]["cells"].values())[0]["count"]
    assert list(m["h"]["cells"].values())[0]["count"] == ha + hb


def test_registry_state_roundtrip_exact():
    snap = _random_snapshot(RNG)
    reg = MetricsRegistry()
    reg.load_state(snap)
    assert reg.state_dict() == snap
    # and the image is pure JSON
    assert json.loads(json.dumps(snap)) == snap


# ============================= tracer =================================== #
def _fake_clock():
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]
    return clock


def test_tracer_nested_spans_and_chrome_export():
    trc = Tracer(clock=_fake_clock())
    with trc.span("outer", step=0):
        with trc.span("inner"):
            pass
        trc.instant("mark", kind="x")
    trc.counter("queue_depth", 3)
    evs = trc.events()
    by = {e["name"]: e for e in evs}
    # inner closes first (ring holds completion order) and nests deeper
    assert [e["name"] for e in evs] == ["inner", "mark", "outer",
                                       "queue_depth"]
    assert by["inner"]["depth"] == 1 and by["outer"]["depth"] == 0
    # containment: inner's interval inside outer's
    o, i = by["outer"], by["inner"]
    assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    doc = trc.to_chrome_trace()
    validate_chrome_trace(doc)
    phases = {e["name"]: e["ph"] for e in doc["traceEvents"]
              if e["ph"] != "M"}
    assert phases == {"outer": "X", "inner": "X", "mark": "i",
                      "queue_depth": "C"}


def test_tracer_deterministic_under_fake_clock():
    def run():
        trc = Tracer(clock=_fake_clock())
        with trc.span("a", step=1):
            trc.instant("b")
        return trc.to_json()
    assert run() == run()


def test_tracer_ring_eviction():
    trc = Tracer(capacity=4, clock=_fake_clock())
    for i in range(10):
        trc.instant(f"e{i}")
    assert len(trc) == 4
    assert trc.dropped == 6
    assert [e["name"] for e in trc.events()] == ["e6", "e7", "e8", "e9"]
    validate_chrome_trace(trc.to_chrome_trace())


def test_disabled_tracer_is_noop():
    assert not NULL_TRACER.enabled
    with NULL_TRACER.span("x"):
        NULL_TRACER.instant("y")
    NULL_TRACER.counter("z", 1)
    assert len(NULL_TRACER) == 0
    # exception safety: a raising body still propagates, span still closes
    trc = Tracer(clock=_fake_clock())
    with pytest.raises(ValueError):
        with trc.span("boom"):
            raise ValueError("body")
    assert trc.find("boom")


# ================== engine instrumentation + compat ===================== #
def test_counters_view_compat_and_metrics(stack):
    cfg, model, params, lay = stack
    eng = _engine(model, lay)
    prompts = [RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (11, 6)]
    for p in prompts:
        eng.submit(p, 4)
    eng.run(params)
    # the dict-compat view: iteration, membership, int values
    c = dict(eng.counters)
    assert c["engine_steps"] > 0 and isinstance(c["engine_steps"], int)
    assert set(c) == set(eng.counters.KEYS)
    assert eng.counters["prefill_launches"] == \
        sum(-(-len(p) // 8) for p in prompts)
    # the same numbers ARE registry counters
    assert eng.registry.value("serve_engine_steps") == c["engine_steps"]
    # lifecycle latency histograms populated per priority
    assert eng.registry.percentiles("serve_ttft_s",
                                    priority=0)["count"] == 2
    assert eng.registry.percentiles("serve_tpot_s",
                                    priority=0)["count"] == 2 * 3
    assert eng.registry.percentiles("serve_queue_wait_s",
                                    priority=0)["count"] == 2
    assert summary_line(eng.registry).startswith("steps=")


def test_engine_snapshot_roundtrip_and_old_format(stack):
    cfg, model, params, lay = stack
    prompts = [RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (9, 13)]

    def mk():
        eng = _engine(model, lay)
        for p in prompts:
            eng.submit(p, 6)
        return eng

    ref = mk()
    full = ref.run(params)

    # run half, snapshot, restore into a fresh engine: registry AND tokens
    eng = mk()
    for _ in range(4):
        eng.step(params)
    snap = eng.state_dict()
    eng2 = mk()
    eng2.load_state(snap)
    assert eng2.registry.state_dict() == eng.registry.state_dict()
    assert dict(eng2.counters) == dict(eng.counters)
    while eng2.step(params):
        pass
    res = eng2.batcher.results()
    assert all(np.array_equal(full[r], res[r]) for r in full)

    # OLD-format snapshot: strip the "metrics" key (pre-registry snapshots
    # carried only the counters dict) — must still load, counters intact
    leaves, treedef = jax.tree_util.tree_flatten(snap)
    old = jax.tree_util.tree_unflatten(treedef, leaves)
    ctl_leaf = None
    for i, leaf in enumerate(leaves):
        try:
            d = json.loads(bytes(np.asarray(leaf)).decode())
            if isinstance(d, dict) and "counters" in d:
                ctl_leaf, ctl, idx = leaf, d, i
        except Exception:
            continue
    assert ctl_leaf is not None and "metrics" in ctl
    del ctl["metrics"]
    blob = np.frombuffer(json.dumps(ctl).encode(), np.uint8)
    leaves[idx] = blob
    old = jax.tree_util.tree_unflatten(treedef, leaves)
    eng3 = mk()
    eng3.load_state(old)
    assert dict(eng3.counters) == dict(eng.counters)
    while eng3.step(params):
        pass
    res3 = eng3.batcher.results()
    assert all(np.array_equal(full[r], res3[r]) for r in full)


def test_engine_trace_lifecycle_events(stack):
    cfg, model, params, lay = stack
    obs = Observability(tracing=True)
    eng = _engine(model, lay, obs=obs)
    p = RNG.integers(0, cfg.vocab_size, (10,)).astype(np.int32)
    eng.submit(p, 4)
    eng.run(params)
    names = {e["name"] for e in obs.tracer.events()}
    for want in ("engine.step", "assemble", "chunk_prefill", "ragged_decode",
                 "sample", "request.submitted", "request.admitted",
                 "request.first_token", "request.finished"):
        assert want in names, want
    # spans nest: phases sit at depth 1 inside engine.step on one track
    steps = obs.tracer.find("engine.step")
    assert len(steps) == eng.counters["engine_steps"]
    assert all(e["depth"] == 0 for e in steps)
    assert all(e["depth"] == 1 for e in obs.tracer.find("assemble"))
    ft = obs.tracer.find("request.first_token")[0]
    assert ft["args"]["ttft_s"] > 0
    validate_chrome_trace(obs.tracer.to_chrome_trace())


def test_engine_default_obs_disabled(stack):
    """No obs argument: tracer is the shared no-op, metrics still count."""
    cfg, model, params, lay = stack
    eng = _engine(model, lay)
    assert eng.tracer is NULL_TRACER
    assert not eng.obs.tracing


# ============ step phases, decode-grid counts, the profiler clock ======== #
# Where each phase span sits: (parent, depth) on the engine track.
PHASES = {"chunk_plan": ("chunk_prefill", 2),
          "chunk_launch": ("chunk_prefill", 2),
          "first_token": ("chunk_prefill", 2),
          "decode_launch": ("ragged_decode", 2),
          "logits_to_host": ("ragged_decode", 2),
          "sample": ("engine.step", 1)}


def _within(inner, outer) -> bool:
    return (outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


def _record_jit_args(eng) -> dict:
    """Wrap the engine's two jitted programs so the first call of each
    keeps its arguments, for lowering afterwards."""
    seen = {}
    for attr in ("_chunk_jit", "_decode_jit"):
        fn = getattr(eng, attr)

        def rec(*args, _fn=fn, _attr=attr):
            seen.setdefault(_attr, (_fn, args))
            return _fn(*args)
        setattr(eng, attr, rec)
    return seen


def test_engine_phase_spans_nest(stack):
    cfg, model, params, lay = stack
    obs = Observability(tracing=True)
    eng = _engine(model, lay, obs=obs)
    for L in (19, 6):
        eng.submit(RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32), 4)
    eng.run(params)
    spans = [e for e in obs.tracer.events() if e["ph"] == "X"]
    assert "window" not in {e["name"] for e in spans}
    for name, (parent, depth) in PHASES.items():
        mine = [e for e in spans if e["name"] == name]
        assert mine, name
        parents = [e for e in spans if e["name"] == parent]
        for e in mine:
            assert e["depth"] == depth, (name, e["depth"])
            assert any(p["depth"] == depth - 1 and _within(e, p)
                       for p in parents), (name, parent)
    # one plan and one launch per chunk, a first token per request
    chunks = eng.counters["prefill_launches"]
    assert len(obs.tracer.find("chunk_plan")) == chunks
    assert len(obs.tracer.find("chunk_launch")) == chunks
    assert len(obs.tracer.find("first_token")) == 2
    launches = eng.counters["decode_launches"]
    assert len(obs.tracer.find("decode_launch")) == launches
    assert len(obs.tracer.find("logits_to_host")) == launches
    # sample stays childless: nothing nests inside it
    for s in obs.tracer.find("sample"):
        assert not [e for e in spans if e is not s and e["depth"] > 1
                    and _within(e, s)]


def test_decode_grid_counts(stack):
    """Rows at known positions: with a chunk of 32 both prompts prefill in
    the first step, so the first decode launch has row A at t=5 and row B
    at t=30. A holds positions 0..5: the sink page (0, 1) and ring page 1
    (positions 2..5 at slots 8..11), 2 pages; B has wrapped the 16-slot
    ring and needs all 3. Two layers; the grid walks 4 rows x 3 pages."""
    cfg, model, params, lay = stack
    assert (lay.n_global, lay.n_sink, lay.ring_cap, lay.pages_per_req) \
        == (2, 8, 16, 3)
    obs = Observability(tracing=True)
    eng = ContinuousEngine(model, ContinuousConfig(
        n_pages=1 + 4 * lay.pages_per_req, page=8, chunk=32, max_batch=4),
        obs=obs)
    for L in (5, 30):
        eng.submit(RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32), 3)
    eng.run(params)
    launches = obs.tracer.find("decode_launch")
    assert launches[0]["args"] == {"rows_live": 2, "pages_walked": 24,
                                   "pages_live": 2 * (2 + 3)}
    assert all(e["args"]["pages_walked"] == 24 for e in launches)
    reg = obs.registry
    assert reg.value("serve_decode_pages_walked") == sum(
        e["args"]["pages_walked"] for e in launches)
    assert reg.value("serve_decode_pages_live") == sum(
        e["args"]["pages_live"] for e in launches)
    assert sum(e["args"]["rows_live"] for e in launches) \
        == eng.counters["decode_tokens"]
    # the counters ride snapshot/restore with the other serve_* counters
    eng2 = _engine(model, lay)
    eng2.load_state(eng.state_dict())
    assert eng2.counters["decode_pages_live"] \
        == reg.value("serve_decode_pages_live")
    # vectorised over rows, pages_needed keeps its piecewise definition
    def ref(t):
        if t <= 0:
            return 0
        if t <= lay.n_global:
            return -(-t // lay.page)
        if t - lay.n_global >= lay.ring_cap:
            return lay.pages_per_req
        return lay.sink_pages + -(-(t - lay.n_global) // lay.page)
    t = np.arange(-1, 40)
    assert lay.pages_needed(t).tolist() == [ref(int(x)) for x in t]
    assert lay.pages_needed(31) == 3 and isinstance(lay.pages_needed(6), int)


def test_engine_spans_land_in_profiler_trace(stack, tmp_path):
    from jax.profiler import ProfileData, TraceAnnotation

    cfg, model, params, lay = stack
    eng = _engine(model, lay, obs=Observability(tracing=True))
    for L in (19, 6):                      # 19: a chunk left for step 3
        eng.submit(RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32), 3)
    eng.step(params)                       # compile outside the trace
    eng.step(params)
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("window"):
            eng.run(params)
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]
    steps = [(s, e) for n, s, e in host if n == "engine.step"]
    assert steps
    for name in ("chunk_plan", "logits_to_host", "sample"):
        mine = [(s, e) for n, s, e in host if n == name]
        assert mine, name
        assert all(any(a <= s and e <= b for a, b in steps)
                   for s, e in mine), name
    # names only: the profiler would fold a span's args into its name
    names = {n for n, _, _ in host}
    assert {"chunk_plan", "chunk_launch", "decode_launch"} <= names
    assert not [n for n in names if "#" in n and n.split("#")[0] in (
        set(PHASES) | {"engine.step", "assemble", "chunk_prefill",
                       "ragged_decode"})]


def test_tracing_off_enters_no_annotation_and_keeps_jaxprs(stack,
                                                           monkeypatch):
    cfg, model, params, lay = stack
    entered = []
    real = jax.profiler.TraceAnnotation

    class Counting(real):
        def __enter__(self):
            entered.append(1)
            return super().__enter__()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    prompts = [RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (13, 5)]

    def run(obs):
        eng = _engine(model, lay, obs=obs)
        seen = _record_jit_args(eng)
        for p in prompts:
            eng.submit(p, 3)
        return eng, eng.run(params), seen

    plain, plain_out, plain_args = run(None)
    assert not entered
    traced, traced_out, traced_args = run(Observability(tracing=True))
    assert entered                         # the patch does see annotations
    assert all(np.array_equal(plain_out[r], traced_out[r])
               for r in plain_out)
    for attr, raw in (("_chunk_jit", "_chunk_fn"),
                      ("_decode_jit", "_decode_fn")):
        a = str(jax.make_jaxpr(getattr(plain, raw))(*plain_args[attr][1]))
        b = str(jax.make_jaxpr(getattr(traced, raw))(*traced_args[attr][1]))
        assert a == b, attr


def test_step_program_names(stack):
    """The device trace names programs after the jitted function:
    ``prefill_device_ms_per_chunk`` reads ``_chunk_fn`` from them."""
    import re

    cfg, model, params, lay = stack
    eng = _engine(model, lay)
    seen = _record_jit_args(eng)
    eng.submit(RNG.integers(0, cfg.vocab_size, (9,)).astype(np.int32), 2)
    eng.run(params)
    for attr, module in (("_chunk_jit", "jit__chunk_fn"),
                         ("_decode_jit", "jit__decode_fn")):
        fn, args = seen[attr]
        text = fn.lower(*args).as_text()
        assert re.search(r"module @(\S+)", text).group(1) == module


# ======================= FT events through the tracer =================== #
def test_supervisor_fault_events_land_in_trace(stack, tmp_path):
    from repro.ft import FaultInjector, FaultPlan, ServeSupervisor

    cfg, model, params, lay = stack
    prompts = [RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (9, 7)]
    obs = Observability(tracing=True)

    def mk():
        eng = _engine(model, lay, obs=obs)
        for p in prompts:
            eng.submit(p, 4)
        return eng

    sup = ServeSupervisor(
        mk, params, str(tmp_path / "ck"), checkpoint_every=2,
        injector=FaultInjector(FaultPlan(crash_steps=frozenset({3}))),
        obs=obs)
    eng, hist = sup.run()
    assert hist["restarts"] == 1
    names = [e["name"] for e in obs.tracer.events()]
    assert "ft.fault" in names and "ft.restart" in names \
        and "ft.snapshot" in names
    fault = obs.tracer.find("ft.fault")[0]
    assert fault["args"]["kind"] == "StepCrash"
    # crash at attempt 3 lands after the step-2 checkpoint: a restore event
    assert obs.tracer.find("ft.restore")
    assert obs.registry.value("ft_restarts") == 1
    assert obs.registry.value("ft_faults", kind="StepCrash") == 1
    # engine spans and supervisor instants share one exported timeline
    doc = obs.tracer.to_chrome_trace()
    validate_chrome_trace(doc)
    tracks = {e["args"]["name"] for e in doc["traceEvents"]
              if e["ph"] == "M"}
    assert {"engine", "requests", "ft"} <= tracks


def test_run_with_restarts_events(tmp_path):
    from repro.ft import CheckpointManager, run_with_restarts

    obs = Observability(tracing=True)
    mgr = CheckpointManager(tmp_path / "ck", keep=2, async_write=False)
    state, hist = run_with_restarts(
        lambda s, i: s + 1, 0, 8, mgr, checkpoint_every=2,
        fail_at={5}, obs=obs)
    assert state == 8 and hist["restarts"] == 1
    assert obs.tracer.find("ft.fault") and obs.tracer.find("ft.restore")
    assert len(obs.tracer.find("train.step")) == hist["steps_run"]
    assert obs.registry.value("ft_faults", kind="StepCrash") == 1


# ==================== runtime-ExecutionPlan metrics ===================== #
def test_dynamic_plan_build_metrics():
    """Tracing a plan="dynamic" attention accounts one build and one
    keep-ratio observation in the process-wide registry (host-side, at
    trace time — the same pattern as the kernel launch accounting)."""
    from repro.core import patterns as P
    from repro.core.attention import hybrid_attention
    from repro.obs.metrics import global_registry

    reg = global_registry()
    builds0 = (reg.value("dynamic_plan_builds")
               if "dynamic_plan_builds" in reg.families() else 0)
    h0 = (reg.hist("dynamic_plan_keep_ratio")
          if "dynamic_plan_keep_ratio" in reg.families() else None)
    count0 = h0.count if h0 is not None else 0

    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 96, 16)), jnp.float32)
               for _ in range(3))
    # an odd shape/keep combination, so this trace can't be jit-cached by
    # an earlier test (the accounting runs at trace time only)
    out = hybrid_attention(q, k, v, P.causal_sliding_window(31, n_sinks=3),
                           plan="dynamic", dynamic_keep=5,
                           block_q=16, block_k=16)
    assert np.all(np.isfinite(np.asarray(out)))
    assert reg.value("dynamic_plan_builds") >= builds0 + 1
    h = reg.hist("dynamic_plan_keep_ratio")
    assert h is not None and h.count >= count0 + 1
    # keep=5 of max 5-ish candidate steps: ratio lies in (0, 1]
    assert 0.0 < h.max <= 1.0
