"""Runtime concurrency checker (dynamo_tpu/utils/concurrency.py): the
dynarace runtime half.

Covers the acceptance contract end to end: the affinity assertion fires
on a cross-context touch, the lock-order tracker raises on an observed
inversion (seeded races — each detector is PROVEN to fire, not assumed),
``DYNTPU_CHECK_THREADS`` unset is a structural no-op (plain
``threading.Lock``, unchanged functions, immediate returns) with no
measurable overhead on a mocker-bench-step-shaped hot loop, and the
CompileStats fix the DT007 burn-down landed holds under a real
two-thread hammer.
"""

from __future__ import annotations

import os
import threading
import time

import pytest

from dynamo_tpu.utils import concurrency as ck


@pytest.fixture
def checker_on(monkeypatch):
    # Teardown restores the OUTER env value (ci.sh's dynarace leg runs
    # this module with DYNTPU_CHECK_THREADS=1 for the whole session) and
    # refreshes AFTER the restore — delenv+refresh would leave the
    # checker silently disarmed for every later test in the armed leg.
    prev = os.environ.get("DYNTPU_CHECK_THREADS")
    monkeypatch.setenv("DYNTPU_CHECK_THREADS", "1")
    ck.refresh_enabled()
    ck.reset_tracking()
    ck.bind_thread("main-test")  # never leak a stale binding into asserts
    yield
    if prev is None:
        monkeypatch.delenv("DYNTPU_CHECK_THREADS", raising=False)
    else:
        monkeypatch.setenv("DYNTPU_CHECK_THREADS", prev)
    ck.refresh_enabled()
    ck.reset_tracking()


@pytest.fixture
def checker_off(monkeypatch):
    prev = os.environ.get("DYNTPU_CHECK_THREADS")
    monkeypatch.delenv("DYNTPU_CHECK_THREADS", raising=False)
    ck.refresh_enabled()
    yield
    if prev is not None:
        monkeypatch.setenv("DYNTPU_CHECK_THREADS", prev)
    ck.refresh_enabled()


def _in_thread(fn, name="t"):
    """Run fn() in a fresh thread; re-raise its exception here."""
    box: dict = {}

    def run():
        try:
            box["ret"] = fn()
        except BaseException as exc:  # noqa: BLE001 — ferried to the caller
            box["exc"] = exc

    t = threading.Thread(target=run, name=name)
    t.start()
    t.join(10)
    assert not t.is_alive(), "seeded-race thread hung"
    if "exc" in box:
        raise box["exc"]
    return box.get("ret")


# ---------------------------------------------------------------------------
# thread affinity
# ---------------------------------------------------------------------------


def test_affinity_assertion_fires_cross_thread(checker_on):
    """Seeded race #1: an engine-owned method touched from a thread
    bound to another context raises ThreadAffinityError."""

    class EngineOwned:
        def __init__(self):
            self.steps = 0

        def step(self):
            ck.assert_context("engine", what="EngineOwned.step")
            self.steps += 1

    obj = EngineOwned()

    def engine_thread():
        ck.bind_thread("engine")
        obj.step()

    _in_thread(engine_thread, name="engine")
    assert obj.steps == 1

    def wrong_thread():
        ck.bind_thread("loop")
        obj.step()

    with pytest.raises(ck.ThreadAffinityError, match="owned by 'engine'"):
        _in_thread(wrong_thread, name="loop")
    assert obj.steps == 1  # the violating touch did not land


def test_owned_by_decorator_fires_and_unbound_threads_pass(checker_on):
    calls = []

    @ck.owned_by("engine")
    def hot():
        calls.append(1)

    def bound_wrong():
        ck.bind_thread("worker")
        hot()

    with pytest.raises(ck.ThreadAffinityError):
        _in_thread(bound_wrong)
    # An UNBOUND thread passes: the checker judges only threads it was
    # told about, so partial wiring can't false-alarm.
    _in_thread(hot, name="unbound")
    assert calls == [1]


def test_bound_scope_restores_previous_binding(checker_on):
    ck.bind_thread("loop")
    with ck.bound("worker"):
        assert ck.current_context() == "worker"
        with ck.bound("engine"):
            assert ck.current_context() == "engine"
        assert ck.current_context() == "worker"
    assert ck.current_context() == "loop"


# ---------------------------------------------------------------------------
# lock-order tracking
# ---------------------------------------------------------------------------


def test_lock_order_inversion_detected(checker_on):
    """Seeded race #2: A→B observed on one thread, then B→A on another
    raises LockOrderError — deterministically, without needing the
    unlucky interleaving that would actually deadlock."""
    a = ck.TrackedLock("A")
    b = ck.TrackedLock("B")

    def order_ab():
        with a:
            with b:
                pass

    _in_thread(order_ab, name="ab")

    def order_ba():
        with b:
            with a:
                pass

    with pytest.raises(ck.LockOrderError, match="inversion"):
        _in_thread(order_ba, name="ba")


def test_lock_order_consistent_and_reacquisition(checker_on):
    a = ck.TrackedLock("A2")
    b = ck.TrackedLock("B2")
    for _ in range(3):  # same order every time: fine
        with a, b:
            pass
    with pytest.raises(ck.LockOrderError, match="reacquisition"):
        with a:
            a.acquire()  # raises BEFORE deadlocking; with-exit releases
    assert not a.locked()


def test_make_lock_tracked_when_on(checker_on):
    lock = ck.make_lock("test.lock")
    assert isinstance(lock, ck.TrackedLock)
    with lock:
        assert lock.locked()
    assert not lock.locked()


# ---------------------------------------------------------------------------
# env off: structural no-op, no measurable overhead
# ---------------------------------------------------------------------------


def test_env_off_is_structural_noop(checker_off):
    # make_lock returns the PLAIN lock type — zero wrapper, zero cost.
    lock = ck.make_lock("off.lock")
    assert type(lock) is type(threading.Lock())
    # owned_by returns the function object unchanged — no wrapper frame.
    def fn():
        return 42
    assert ck.owned_by("engine")(fn) is fn
    # assert_context / bind_thread return immediately, raise nothing.
    ck.bind_thread("engine")
    ck.assert_context("loop", what="anything")  # would raise if enabled
    # ...and inversion sequences are invisible.
    a, b = ck.make_lock("offA"), ck.make_lock("offB")
    with a:
        with b:
            pass
    with b:
        with a:
            pass


def test_env_off_no_measurable_overhead_on_step_shaped_loop(checker_off):
    """A mocker bench step takes ~1e-3 s and acquires a handful of
    checker-built locks (flight ring, tracer, recorder). 10k iterations
    of lock + assert_context must stay far under one step's budget —
    i.e. per-step checker cost is unmeasurable."""
    lock = ck.make_lock("bench.lock")
    t0 = time.perf_counter()
    for _ in range(10_000):
        with lock:
            pass
        ck.assert_context("engine", what="bench")
    dt = time.perf_counter() - t0
    # Generous bound: even slow CI does 10k plain-lock cycles in well
    # under 100 ms; a step does ~10 of these, so per-step cost is <0.1 ms.
    assert dt < 0.5, f"checker-off hot loop took {dt:.3f}s for 10k iters"


def test_refresh_enabled_flips_make_lock(monkeypatch):
    prev = os.environ.get("DYNTPU_CHECK_THREADS")
    monkeypatch.setenv("DYNTPU_CHECK_THREADS", "1")
    assert ck.refresh_enabled() is True
    assert isinstance(ck.make_lock("x"), ck.TrackedLock)
    monkeypatch.setenv("DYNTPU_CHECK_THREADS", "0")
    assert ck.refresh_enabled() is False
    assert type(ck.make_lock("x")) is type(threading.Lock())
    # Re-arm per the OUTER env before the next test (see checker_on).
    if prev is None:
        monkeypatch.delenv("DYNTPU_CHECK_THREADS", raising=False)
    else:
        monkeypatch.setenv("DYNTPU_CHECK_THREADS", prev)
    ck.refresh_enabled()


# ---------------------------------------------------------------------------
# production wiring drills (the chaos-subset leg runs these with the env
# set for real — ci.sh "dynarace chaos subset")
# ---------------------------------------------------------------------------


def test_recorder_cross_thread_writes_stay_clean_under_checker(
    checker_on, tmp_path
):
    """The Recorder seam from the motivation: engine-thread and loop-
    thread writers interleave through the tracked write lock with no
    inversion and no corrupt JSONL."""
    from dynamo_tpu.utils.recorder import Recorder

    rec = Recorder(tmp_path / "cap.jsonl", max_bytes=4096, max_files=3)
    assert isinstance(rec._write_lock, ck.TrackedLock)
    errs: list = []

    def writer(ctx, n):
        def run():
            ck.bind_thread(ctx)
            for i in range(n):
                rec.record({"ctx": ctx, "i": i})
        return run

    threads = [
        threading.Thread(target=writer("engine", 200)),
        threading.Thread(target=writer("loop", 200)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(20)
        assert not t.is_alive()
    rec.close()
    assert not errs
    events = [e for _, e in Recorder.load(tmp_path / "cap.jsonl")]
    # Rotation may age out early lines; whatever survived parsed cleanly
    # and the newest records are intact.
    assert len(events) > 0 and events[-1]["i"] == 199


def test_compile_stats_concurrent_observe_is_exact(checker_on):
    """Regression for the dynarace fix rider: CompileStats.observe ran
    unlocked from the engine thread and stepcast executor threads —
    concurrent first-executions dropped increments and double-counted
    keys. With the lock, totals are exact under a two-thread hammer."""
    from dynamo_tpu.engine.compile_cache import CompileStats

    cs = CompileStats()
    N = 300

    def hammer(ctx):
        def run():
            ck.bind_thread(ctx)
            for i in range(N):
                # Every key observed by BOTH threads: each first
                # execution must count exactly once.
                with cs.observe("stub", t=i):
                    pass
        return run

    threads = [
        threading.Thread(target=hammer("engine")),
        threading.Thread(target=hammer("worker")),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
        assert not t.is_alive()
    snap = cs.snapshot()
    assert snap["mid_traffic_compiles_total"] == N
    assert len(cs.seen) == N


def test_engine_thread_binding_via_flush_side_channels(checker_on):
    """TpuEngine._flush_side_channels asserts engine affinity: called
    from a thread bound elsewhere it raises; from an unbound thread
    (unit tests driving the engine directly) it passes."""
    from dynamo_tpu.engine.engine import TpuEngine

    eng = TpuEngine.__new__(TpuEngine)  # no device build needed
    eng._remote = {}
    eng._external_kv_event = None
    eng._kv_events_buffer = []
    eng._kv_actuals_buffer = []
    eng.scheduler = None
    eng._on_metrics = None

    def wrong():
        ck.bind_thread("loop")
        eng._flush_side_channels()

    with pytest.raises(ck.ThreadAffinityError):
        _in_thread(wrong)

    _in_thread(eng._flush_side_channels, name="unbound")  # passes
