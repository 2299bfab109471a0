"""Fault-tolerant continuous serving: engine snapshot/restore with
exactly-once token emission, supervisor kill/resume, page-pressure
preemption + chunked re-prefill, admission control, deadlines, and the
deterministic fault-injection harness. Parity oracle throughout: the
lockstep ``ServeEngine`` (and, for kill/resume, the uninterrupted
continuous run)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.ft import (CheckpointManager, FaultInjector, FaultPlan,
                      ServeSupervisor, StragglerWatchdog, run_with_restarts,
                      save, sweep_stale_tmp)
from repro.ft.faults import (QueueFull, RejectedRequest, ResourceExhausted,
                             RestartsExhausted, StepCrash)
from repro.models.model import build_model
from repro.serve.engine import (ContinuousConfig, ContinuousEngine,
                                ServeConfig, ServeEngine)

RNG = np.random.default_rng(11)
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(scope="module")
def stack():
    cfg = get_smoke("smollm-135m")   # window=16, page 8 -> 3 pages/request
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.models.layers import salo_pattern
    from repro.serve.paged_cache import layout_for_pattern
    lay = layout_for_pattern(salo_pattern(cfg, causal=True), 8)
    return cfg, model, params, lay


def _refs(model, params, prompts, n_new):
    out = []
    for p in prompts:
        eng = ServeEngine(model, ServeConfig(max_len=len(p) + n_new))
        out.append(np.asarray(
            eng.generate(params, jnp.asarray(p)[None], n_new))[0])
    return out


def _engine(model, lay, *, n_pages=None, max_batch=4, clock=None,
            max_queue=None):
    return ContinuousEngine(model, ContinuousConfig(
        n_pages=n_pages or 1 + max_batch * lay.pages_per_req, page=8,
        chunk=8, max_batch=max_batch, max_queue=max_queue), clock=clock)


# ===================== restart loop + checkpoint hygiene ================ #
def test_run_with_restarts_bounded(tmp_path):
    """A deterministically failing step no longer spins forever: after
    ``max_restarts`` restarts the loop raises RestartsExhausted (chaining
    the fault) instead of retrying — and bare RuntimeError is NOT in the
    recoverable taxonomy, so it propagates without a single restart."""
    mgr = CheckpointManager(tmp_path / "ck", keep=2, async_write=False)

    def bad_step(state, step):
        raise StepCrash("always")

    with pytest.raises(RestartsExhausted, match="after 3 restarts"):
        run_with_restarts(bad_step, 0, 5, mgr, checkpoint_every=2,
                          max_restarts=3)

    calls = []

    def rt_step(state, step):
        calls.append(step)
        raise RuntimeError("not a taxonomy fault")

    with pytest.raises(RuntimeError, match="not a taxonomy"):
        run_with_restarts(rt_step, 0, 5, mgr, checkpoint_every=2,
                          max_restarts=3)
    assert len(calls) == 1   # no retry on unclassified failures


def test_stale_tmp_sweep(tmp_path):
    """Orphaned ``tmp.<step>.<pid>`` staging dirs from crashed writers are
    garbage-collected: dead-pid and own-pid (pre-crash leftover) dirs go,
    a live foreign writer's dir survives, and ``save`` sweeps on entry."""
    d = tmp_path / "ck"
    d.mkdir()
    dead = subprocess.Popen([sys.executable, "-c", "pass"])
    dead.wait()   # reaped: the pid no longer exists
    for name in (f"tmp.3.{os.getpid()}", f"tmp.4.{dead.pid}", "tmp.5.1"):
        (d / name).mkdir()
        (d / name / "leaf.npy").write_bytes(b"x")
    assert sweep_stale_tmp(d) == 2
    assert sorted(p.name for p in d.iterdir()) == ["tmp.5.1"]
    (d / f"tmp.9.{dead.pid}").mkdir()
    save(d, {"x": np.arange(3)}, step=1)
    names = sorted(p.name for p in d.iterdir())
    assert names == ["step_00000001", "tmp.5.1"]


# ======================= lifecycle snapshotting ======================== #
def test_batcher_state_roundtrip(stack):
    """The scheduler's full lifecycle — queue, resident rows, finished,
    allocator free-list ORDER, counters, remaining deadlines — survives a
    state_dict/load_state roundtrip into a fresh batcher."""
    from repro.serve.batcher import DECODE, Batcher
    _, _, _, lay = stack
    clk = [100.0]
    b = Batcher(lay, n_pages=7, max_batch=2, max_queue=8,
                clock=lambda: clk[0])
    r0 = b.submit(np.arange(12) + 1, 6, priority=1, deadline_s=9.0)
    r1 = b.submit(np.arange(5) + 1, 4)
    r2 = b.submit(np.arange(3) + 1, 2)
    b.admit()
    req0 = next(q for q in b.rows if q is not None and q.rid == r0)
    req0.state = DECODE
    req0.out.extend([7, 8])
    st = b.state_dict()

    clk[0] = 200.0   # restore on a shifted clock: deadlines re-anchor
    b2 = Batcher(lay, n_pages=7, max_batch=2, clock=lambda: clk[0])
    b2.load_state(st)
    q0 = next(q for q in b2.rows if q is not None and q.rid == r0)
    assert q0.state == DECODE and q0.out == [7, 8] and q0.priority == 1
    assert q0.deadline == pytest.approx(209.0)   # 9s remaining, re-anchored
    np.testing.assert_array_equal(
        q0.pages, next(q for q in b.rows if q.rid == r0).pages)
    assert [q.rid for q in b2.queue] == [q.rid for q in b.queue]
    assert b2._next_rid == 3 and r2 in {q.rid for q in b2.queue}
    for a, a2 in zip(b.allocs, b2.allocs):
        assert a._free == a2._free   # order-exact: same future page grants
    assert b2.submit(np.arange(4) + 1, 2) == 3


def test_engine_snapshot_restore_parity(stack, tmp_path):
    """Snapshot mid-flight (rows prefilling AND decoding), push through the
    atomic checkpoint writer, restore into a FRESH engine: the resumed run
    emits exactly the remaining tokens — full outputs match both the
    uninterrupted run and the lockstep oracle (exactly-once emission)."""
    cfg, model, params, lay = stack
    n_new = 8
    prompts = [RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (5, 9, 13, 26)]
    refs = _refs(model, params, prompts, n_new)

    eng = _engine(model, lay)
    rids = [eng.submit(p, n_new) for p in prompts]
    for _ in range(5):
        eng.step(params)
    save(tmp_path / "ck", eng.state_dict(), step=5)
    while eng.step(params):
        pass
    uninterrupted = eng.batcher.results()

    from repro.ft import restore
    eng2 = _engine(model, lay)
    eng2.load_state(restore(tmp_path / "ck", eng2.state_dict()))
    assert eng2.counters["engine_steps"] == 5
    while eng2.step(params):
        pass
    resumed = eng2.batcher.results()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(resumed[rid], uninterrupted[rid])
        np.testing.assert_array_equal(resumed[rid], ref)


def test_supervisor_kill_resume_parity(stack, tmp_path):
    """Injected step crashes mid-serve: the supervisor restores the latest
    snapshot into a rebuilt engine and finishes with token output
    identical to the lockstep oracle; work lost per crash is bounded by
    the checkpoint interval."""
    cfg, model, params, lay = stack
    n_new = 8
    prompts = [RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (20, 18, 22)]
    refs = _refs(model, params, prompts, n_new)

    def make_engine():
        eng = _engine(model, lay, max_batch=4)
        for p in prompts:
            eng.submit(p, n_new)
        return eng

    sup = ServeSupervisor(
        make_engine, params, tmp_path / "snap", checkpoint_every=2,
        injector=FaultInjector(FaultPlan(crash_steps=frozenset({3, 6}))))
    eng, hist = sup.run()
    res = eng.batcher.results()
    for rid, ref in zip(sorted(res), refs):
        np.testing.assert_array_equal(res[rid], ref)
    assert hist["restarts"] == 2
    assert hist["max_step_loss"] <= 2   # bounded by checkpoint_every
    assert all(a.n_free == eng.ccfg.n_pages - 1
               for a in eng.batcher.allocs)


def test_supervisor_restart_budget(stack, tmp_path):
    """Crashing on every attempt exhausts the restart budget and raises
    RestartsExhausted instead of looping."""
    cfg, model, params, lay = stack

    def make_engine():
        eng = _engine(model, lay)
        eng.submit(np.arange(4) + 1, 2)
        return eng

    sup = ServeSupervisor(
        make_engine, params, tmp_path / "snap", max_restarts=2,
        injector=FaultInjector(FaultPlan(crash_steps=frozenset(range(50)))))
    with pytest.raises(RestartsExhausted):
        sup.run()


# ================ preemption, admission control, deadlines ============= #
def test_preemption_reprefill_parity(stack):
    """Page pressure with a higher-priority arrival: low-priority decoding
    requests are evicted (pages released, requeued with their emitted
    tokens), the high-priority request runs, and the victims recover via
    chunked re-prefill — every request still matches the lockstep oracle
    token-for-token, nothing double-emitted."""
    cfg, model, params, lay = stack
    n_new = 8
    pa, pb, pc = (RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
                  for L in (20, 18, 22))
    refs = _refs(model, params, [pa, pb, pc], n_new)
    eng = _engine(model, lay, n_pages=1 + 2 * lay.pages_per_req)
    ra = eng.submit(pa, n_new, priority=0)
    rb = eng.submit(pb, n_new, priority=0)
    while True:   # both resident and decoding -> pool fully occupied
        eng.step(params)
        if len(eng.batcher.assemble()[1]) == 2:
            break
    rc = eng.submit(pc, n_new, priority=1)
    res = eng.run(params)
    for rid, ref in zip((ra, rb, rc), refs):
        np.testing.assert_array_equal(res[rid], ref, err_msg=str(rid))
    assert eng.batcher.preemptions >= 1
    victim = next(r for r in eng.batcher.finished.values()
                  if r.preemptions > 0)
    assert victim.priority == 0
    assert all(a.n_free == eng.ccfg.n_pages - 1
               for a in eng.batcher.allocs)


def test_small_footprint_fits_small_pool(stack):
    """Regression of the old drain-time dead-end: a pool smaller than the
    WORST-CASE footprint (pages_per_req) now serves a request whose actual
    span fits (variable footprints) — previously this exact scenario
    raised 'page pool too small' at drain time."""
    cfg, model, params, lay = stack
    eng = _engine(model, lay, n_pages=lay.pages_per_req)  # 2 usable < 3
    prompt = (np.arange(4) + 1).astype(np.int32)
    rid = eng.submit(prompt, 2)    # spans 5 positions -> 1 page
    res = eng.run(params)
    np.testing.assert_array_equal(
        res[rid], _refs(model, params, [prompt], 2)[0])


def test_admission_control_at_submit(stack):
    """Truly oversized requests are rejected AT SUBMIT with a sizing
    message (not discovered at drain time), and a bounded queue applies
    backpressure via QueueFull."""
    cfg, model, _, lay = stack
    eng = _engine(model, lay, n_pages=lay.pages_per_req, max_queue=2)
    with pytest.raises(RejectedRequest, match="can never fit"):
        eng.submit(np.arange(40) + 1, 8)   # needs all 3 pages, pool has 2
    eng.submit(np.arange(4) + 1, 2)
    eng.submit(np.arange(4) + 1, 2)
    with pytest.raises(QueueFull, match="max_queue=2"):
        eng.submit(np.arange(4) + 1, 2)


def test_deadline_expiry_frees_pages(stack):
    """A request past its deadline moves to the failed-with-reason
    terminal state and releases its pages/row; co-resident traffic is
    unaffected and the pool fully recycles."""
    cfg, model, params, lay = stack
    clk = [0.0]
    n_new = 8
    pa, pb = (RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
              for L in (20, 18))
    ref_b = _refs(model, params, [pb], n_new)[0]
    eng = _engine(model, lay, clock=lambda: clk[0])
    rd = eng.submit(pa, n_new, deadline_s=5.0)
    ro = eng.submit(pb, n_new)
    for _ in range(4):
        eng.step(params)
    clk[0] = 10.0   # past rd's deadline mid-decode
    res = eng.run(params)
    assert rd not in res
    assert "deadline expired" in eng.batcher.failures()[rd]
    np.testing.assert_array_equal(res[ro], ref_b)
    assert eng.batcher.expired == 1
    assert all(a.n_free == eng.ccfg.n_pages - 1
               for a in eng.batcher.allocs)


# ========================= fault injection ============================= #
def test_injected_exhaustion_recovery(stack, tmp_path):
    """An injected allocator-exhaustion window (admission sees zero free
    pages): the bare engine raises the RECOVERABLE ResourceExhausted when
    nothing is in flight; under the supervisor the same plan just costs
    restarts — final tokens still match the oracle."""
    cfg, model, params, lay = stack
    n_new = 6
    prompts = [RNG.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
               for L in (7, 12)]
    refs = _refs(model, params, prompts, n_new)

    def make_engine():
        eng = _engine(model, lay)
        for p in prompts:
            eng.submit(p, n_new)
        return eng

    inj = FaultInjector(FaultPlan(exhaust_steps=frozenset({0, 1})))
    eng = make_engine()
    inj.attach(eng)
    inj.before_step(0)
    with pytest.raises(ResourceExhausted, match="admission stalled"):
        eng.step(params)

    sup = ServeSupervisor(
        make_engine, params, tmp_path / "snap",
        injector=FaultInjector(FaultPlan(exhaust_steps=frozenset({0, 1}))))
    eng, hist = sup.run()
    res = eng.batcher.results()
    for rid, ref in zip(sorted(res), refs):
        np.testing.assert_array_equal(res[rid], ref)
    assert hist["restarts"] == 2   # one per exhausted attempt


def test_injected_stragglers_flagged(stack, tmp_path):
    """Straggler injection + the step watchdog: slept steps are counted by
    the injector and flagged by a watchdog fed synthetic step times (the
    EWMA machinery itself is deterministic)."""
    cfg, model, params, lay = stack
    naps = []
    plan = FaultPlan(straggle_steps=frozenset({5}), straggle_s=0.3)
    inj = FaultInjector(plan, sleep=naps.append)

    def make_engine():
        eng = _engine(model, lay)
        eng.submit(RNG.integers(0, cfg.vocab_size, (9,)).astype(np.int32),
                   6)
        return eng

    sup = ServeSupervisor(make_engine, params, tmp_path / "snap",
                          injector=inj)
    sup.run()
    assert inj.injected["stragglers"] == 1 and naps == [0.3]

    wd = StragglerWatchdog(threshold=3.0, warmup_steps=1)
    times = [0.1, 0.1, 0.1, 0.1, 0.9, 0.1]   # one 9x outlier
    assert [wd.observe(t) for t in times].count(True) == 1
    assert wd.events == 1


def test_fault_plan_sampling_deterministic():
    plan1 = FaultPlan.sample(3, 100, crash_rate=0.1, exhaust_rate=0.05)
    plan2 = FaultPlan.sample(3, 100, crash_rate=0.1, exhaust_rate=0.05)
    assert plan1 == plan2
    assert plan1.crash_steps and plan1.crash_steps < frozenset(range(100))


# ===================== sequence-parallel kill/resume =================== #
def test_sharded_kill_resume_parity():
    """8-shard engine under the supervisor: crashes mid-serve, snapshots
    restored into freshly-built sharded engines (mesh re-placement), final
    tokens identical to the single-device uninterrupted run."""
    prog = textwrap.dedent("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.configs import get_smoke
        from repro.models.model import build_model
        from repro.models.layers import salo_pattern
        from repro.serve.paged_cache import layout_for_pattern
        from repro.serve.engine import ContinuousConfig, ContinuousEngine
        from repro.ft import FaultInjector, FaultPlan, ServeSupervisor

        cfg = get_smoke("smollm-135m")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(1))
        rng = np.random.default_rng(3)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("seq",))
        pat = salo_pattern(cfg, causal=True)
        lens, n_new = (5, 11, 7, 9), 6
        prompts = [rng.integers(0, cfg.vocab_size, (L,)).astype(np.int32)
                   for L in lens]

        l1 = layout_for_pattern(pat, 8)
        e1 = ContinuousEngine(model, ContinuousConfig(
            n_pages=1 + 4 * l1.pages_per_req, page=8, chunk=8, max_batch=4))
        r1 = [e1.submit(p, n_new) for p in prompts]
        ref = e1.run(params)

        l8 = layout_for_pattern(pat, 8, shards=8)
        def mk():
            e = ContinuousEngine(model, ContinuousConfig(
                n_pages=1 + 4 * l8.pages_per_shard, page=8, chunk=8,
                max_batch=4, seq_shards=8), mesh=mesh)
            for p in prompts:
                e.submit(p, n_new)
            return e

        with tempfile.TemporaryDirectory() as d:
            sup = ServeSupervisor(mk, params, d, checkpoint_every=2,
                injector=FaultInjector(
                    FaultPlan(crash_steps=frozenset({3, 6}))))
            e8, hist = sup.run()
        out = e8.batcher.results()
        for a, b in zip(r1, sorted(out)):
            np.testing.assert_array_equal(ref[a], out[b])
        assert hist["restarts"] == 2
        assert hist["max_step_loss"] <= 2
        for al in e8.batcher.allocs:
            assert al.n_free == e8.ccfg.n_pages - 1
        print("SHARDED-KILL-RESUME-OK")
    """)
    r = subprocess.run([sys.executable, "-c", prog],
                       env={**os.environ, "PYTHONPATH": SRC},
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "SHARDED-KILL-RESUME-OK" in r.stdout
