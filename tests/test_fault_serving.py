"""Fault-injected serving: the deterministic fault model, the
schedulers' timeout detection / bounded failover / health accounting,
the engine-level drop accounting for an all-dead pool, and the sharded
layer's shard-kill recovery (watchdog restart + evacuation) and replica
lending.  Plus the pre-existing robustness bugs this PR fixes as
satellites: the WRR zero-weight round expansion, the Proportional
reweighting with a dead executor's stale EWMA, ``backlog`` edge cases,
and the blocking-dispatch fail-fast contract.

Everything here is a pure function of ``(trace, FaultSchedule)`` — the
chaos-marked tests replay bit-identically, which is what makes chaos
assertable."""
import numpy as np
import pytest

from repro.core import evaluate_streams, proxy_detect_fn_streams
from repro.core.executor import (DEVICE_PROFILES, MODEL_PROFILES,
                                 DetectorExecutor)
from repro.core.scheduler import NoHealthyExecutorError, make_scheduler
from repro.serving import (DetectionEngine, FaultEvent, FaultSchedule,
                           FrameRequest, ShardedDetectionEngine,
                           ShardFaultCursor, Watchdog, make_nvr_streams)

pytestmark = pytest.mark.chaos


def ncs2(n, **kw):
    return [DetectorExecutor(DEVICE_PROFILES["ncs2"],
                             MODEL_PROFILES["yolov3"], **kw)
            for _ in range(n)]


def attach(execs, sched: FaultSchedule, shard: int = 0):
    for i, e in enumerate(execs):
        e.faults = sched.view(shard, i)
    return execs


def stub_detect(images, rids=None):
    b = len(images)
    return (np.zeros((b, 4, 4), np.float32),
            np.zeros((b, 4), np.float32),
            np.zeros((b, 4), np.int32),
            np.zeros((b, 4), bool))


# ===================================================== fault model units
def test_fault_event_validation():
    with pytest.raises(ValueError):
        FaultEvent(1.0, "explode", replica=0)
    with pytest.raises(ValueError):
        FaultEvent(1.0, "kill")                      # replica required
    with pytest.raises(ValueError):
        FaultEvent(1.0, "shard_kill", replica=0)     # replica forbidden
    with pytest.raises(ValueError):
        FaultEvent(1.0, "slow", replica=0, factor=0.5)  # speedups aren't


def test_replica_view_fold():
    v = FaultSchedule([
        FaultEvent(1.0, "slow", replica=0, factor=4.0),
        FaultEvent(2.0, "kill", replica=0),
        FaultEvent(3.0, "revive", replica=0),
    ]).view(0, 0)
    assert v.alive(0.5) and v.factor(0.5) == 1.0
    assert v.alive(1.5) and v.factor(1.5) == 4.0
    assert not v.alive(2.5)
    assert v.alive(3.5) and v.factor(3.5) == 1.0     # revive comes back clean
    # an in-flight frame spanning the kill is lost even if revived after
    assert v.alive_through(0.5, 1.9)
    assert not v.alive_through(1.9, 2.1)
    assert not v.alive_through(2.5, 2.6)             # dead at dispatch
    assert v.alive_through(3.1, 9.0)


def test_schedule_sorted_falsy_and_composable():
    a = FaultSchedule.replica_kill(5.0, replica=1, revive_t=7.0)
    b = FaultSchedule.replica_slowdown(1.0, replica=0, factor=2.0)
    s = a + b
    assert [e.t for e in s] == [1.0, 5.0, 7.0]
    assert s.last_event_t == 7.0
    assert len(s) == 3 and bool(s)
    assert not FaultSchedule() and len(FaultSchedule()) == 0
    assert s.view(0, 1).events == tuple(a)
    assert s.view(1, 0).events == ()                 # other shard: clean


def test_random_schedule_deterministic():
    a = FaultSchedule.random(7, 10.0, n_shards=2, n_replicas=3,
                             n_replica_events=4, n_shard_events=1)
    b = FaultSchedule.random(7, 10.0, n_shards=2, n_replicas=3,
                             n_replica_events=4, n_shard_events=1)
    assert list(a) == list(b)
    assert a.has_shard_events
    c = FaultSchedule.random(8, 10.0, n_shards=2, n_replicas=3,
                             n_replica_events=4, n_shard_events=1)
    assert list(a) != list(c)


def test_shard_cursor_kill_revive_and_restart():
    sched = FaultSchedule.shard_kill(2.5, shard=0, revive_t=5.0)
    cur = ShardFaultCursor(sched, 2)
    # epoch [0,4): kill strikes mid-window -> cut at 2.5, shard down
    assert cur.begin_epoch(0, 0.0, 4.0) == 2.5
    assert cur.is_down(0) and not cur.is_down(1)
    assert cur.begin_epoch(1, 0.0, 4.0) is None
    # epoch [4,8): revive at 5.0 has NOT folded yet (boundary fold only
    # consumes t <= window_start), so the shard is down entering it...
    assert cur.begin_epoch(0, 4.0, 8.0) == 2.5
    # ...and up again from the next boundary on
    assert cur.begin_epoch(0, 8.0, 12.0) is None
    assert not cur.is_down(0)


def test_shard_cursor_watchdog_restart_and_permanent():
    sched = FaultSchedule.shard_kill(2.5, shard=0)
    cur = ShardFaultCursor(sched, 1)
    assert cur.begin_epoch(0, 0.0, 4.0) == 2.5
    assert cur.restart(0, 4.0) is True               # watchdog repairs it
    # the kill event (t=2.5 <= 4.0) folds at the next boundary but the
    # restart already reconciled it: the shard stays up
    assert cur.begin_epoch(0, 4.0, 8.0) is None
    perm = ShardFaultCursor(FaultSchedule.shard_kill(2.5, shard=0,
                                                     permanent=True), 1)
    assert perm.begin_epoch(0, 0.0, 4.0) == 2.5
    assert perm.restart(0, 4.0) is False             # refused
    assert perm.begin_epoch(0, 4.0, 8.0) == 2.5      # still down


# ============================================== scheduler failure handling
def test_timeout_detection_and_failover():
    sched = FaultSchedule.replica_kill(0.0, replica=0)
    execs = attach(ncs2(2), sched)
    s = make_scheduler("fcfs", execs)
    a = s.assign(0, 0.0)
    # replica 0 is dead: the dispatcher times out (holding the slot for
    # k x expected), marks it unhealthy, and rescues the frame on 1
    assert a is not None and a.executor_idx == 1
    assert s.healthy == [False, True]
    assert s.retries == {0: 1} and s.failovers == {0: 1}
    assert s.frames_lost == {}
    # and the timeout charged replica 0's slot
    assert execs[0].busy_until == pytest.approx(
        s.timeout_k / execs[0].mu_effective, rel=1e-6)


def test_bounded_retry_exhaustion_loses_frame():
    sched = (FaultSchedule.replica_kill(0.0, replica=0)
             + FaultSchedule.replica_kill(0.0, replica=1))
    s = make_scheduler("fcfs", attach(ncs2(2), sched))
    assert s.assign(0, 0.0) is None                  # both dead: lost
    assert s.healthy == [False, False]
    assert sum(s.frames_lost.values()) == 1
    assert s.fault_counts()["retries"] == {0: 1, 1: 1}


def test_probe_health_restores_revived_replica():
    sched = FaultSchedule.replica_kill(0.0, replica=0, revive_t=1.0)
    s = make_scheduler("fcfs", attach(ncs2(2), sched))
    s.assign(0, 0.0)
    assert s.healthy == [False, True]
    s.probe_health(0.5)
    assert s.healthy == [False, True]                # still dead at 0.5
    s.probe_health(1.5)
    assert s.healthy == [True, True]                 # revived


def test_slowdown_past_timeout_is_suspected():
    # a replica degraded by >= timeout_k cannot beat the timeout rule:
    # it is detected exactly like a death (and probe_health refuses to
    # restore it, avoiding suspect/restore thrash)
    sched = FaultSchedule.replica_slowdown(0.0, replica=0, factor=8.0)
    s = make_scheduler("fcfs", attach(ncs2(2), sched))
    a = s.assign(0, 0.0)
    assert a.executor_idx == 1 and s.healthy == [False, True]
    s.probe_health(10.0)
    assert s.healthy == [False, True]
    # a mild slowdown sails through (slower, but no suspicion)
    mild = FaultSchedule.replica_slowdown(0.0, replica=0, factor=2.0)
    s2 = make_scheduler("fcfs", attach(ncs2(1), mild))
    a2 = s2.assign(0, 0.0)
    assert a2 is not None and s2.healthy == [True]
    assert (a2.t_done - a2.t_start) == pytest.approx(
        2.0 / s2.executors[0].mu_effective * (1 + s2.sync_overhead))


def test_fault_free_scheduler_untouched():
    """No fault view -> the failure machinery never engages and the
    virtual timeline is bit-identical to the pre-fault scheduler."""
    for kind in ("fcfs", "rr", "wrr", "proportional"):
        s = make_scheduler(kind, ncs2(3))
        out = [s.assign(i, i * 0.05) for i in range(40)]
        s2 = make_scheduler(kind, ncs2(3))
        out2 = [s2.assign(i, i * 0.05) for i in range(40)]
        assert [(a.executor_idx, a.t_start, a.t_done)
                for a in out if a] == \
               [(a.executor_idx, a.t_start, a.t_done)
                for a in out2 if a]
        assert s.fault_counts() == {"retries": {}, "failovers": {},
                                    "frames_lost": {}}


def test_lockstep_rr_skips_dead_slot():
    sched = FaultSchedule.replica_kill(0.0, replica=1)
    s = make_scheduler("rr", attach(ncs2(3), sched))
    got = []
    t = 0.0
    for i in range(6):
        a = s.blocking_assign(i, t)
        assert a is not None
        got.append(a.executor_idx)
        t = a.t_start
    # slot 1 dies on its first dispatch (charged one retry), after which
    # the strict order renormalizes over {0, 2}
    assert 1 not in got[1:]
    assert set(got) <= {0, 2} or got[0] in (0, 1)
    assert s.retries.get(1, 0) >= 1


# ===================================== satellite: WRR zero-weight rounds
def test_wrr_zero_weight_expansion_regression():
    """Regression: ``_expand`` with any zero weight raised StopIteration
    (with [1, 0] no emitted slot had w[j] < wmax, so the head-rotation's
    ``next()`` found nothing).  A zero weight must simply contribute no
    slots."""
    s = make_scheduler("wrr", ncs2(2), weights=[1, 0])   # raised before
    assert s._slots == [0]
    a = s.assign(0, 0.0)
    assert a is not None and a.executor_idx == 0
    s3 = make_scheduler("wrr", ncs2(3), weights=[4, 0, 1])
    assert 1 not in s3._slots and sorted(set(s3._slots)) == [0, 2]
    assert len(s3._slots) == 5
    dead = make_scheduler("wrr", ncs2(2), weights=[0, 0])
    assert dead._slots == []
    assert dead.assign(0, 0.0) is None               # no slots -> drop
    with pytest.raises(NoHealthyExecutorError):
        dead.blocking_assign(0, 0.0)                 # ... not a hang


def test_proportional_reweight_ignores_dead_executor():
    """A suspected-dead executor's stale EWMA must not anchor the rate
    normalization (it would inflate every live weight), and its own
    weight must renormalize to zero slots."""
    execs = ncs2(3)
    execs[0].ewma_service = 0.01                     # blazing... and dead
    execs[1].ewma_service = 0.5
    execs[2].ewma_service = 0.5
    s = make_scheduler("proportional", execs)
    s.healthy[0] = False
    s._refresh_weights()
    assert s.weights[0] == 0
    # live weights normalize against the live min (equal -> both 1), not
    # against the dead executor's 100 fps ghost rate
    assert s.weights[1] == s.weights[2] == 1
    assert 0 not in s._slots


# ======================================= satellite: blocking fail-fast
def test_blocking_assign_empty_pool_fails_fast():
    s = make_scheduler("fcfs", [])
    with pytest.raises(NoHealthyExecutorError, match="empty"):
        s.blocking_assign(0, 0.0)


def test_blocking_assign_all_dead_fails_fast():
    sched = (FaultSchedule.replica_kill(0.0, replica=0)
             + FaultSchedule.replica_kill(0.0, replica=1))
    s = make_scheduler("fcfs", attach(ncs2(2), sched))
    # first call: the pool LOOKS healthy, dispatch discovers both dead
    # (bounded retry), the frame is lost — returns None, not a hang
    assert s.blocking_assign(0, 0.0) is None
    # second call: nothing left to wait for -> fail fast
    with pytest.raises(NoHealthyExecutorError, match="unhealthy"):
        s.blocking_assign(1, 0.0)
    for kind in ("rr", "wrr", "proportional"):
        s2 = make_scheduler(kind, attach(ncs2(2), sched))
        s2.healthy = [False, False]
        with pytest.raises(NoHealthyExecutorError):
            s2.blocking_assign(0, 0.0)


# ============================================ satellite: backlog edges
def test_backlog_empty_pool_and_pre_dispatch():
    assert make_scheduler("fcfs", []).backlog(0.0) == 0.0
    s = make_scheduler("fcfs", ncs2(4))
    # an untouched executor's busy_until of 0.0 is a clock origin, not a
    # commitment: probing before the first arrival must read zero, not
    # -n x t
    assert s.backlog(-5.0) == 0.0
    assert s.backlog(0.0) == 0.0
    assert s.backlog(100.0) == 0.0


def test_backlog_counts_only_inflight_residual():
    s = make_scheduler("fcfs", ncs2(2))
    a0 = s.assign(0, 0.0)
    a1 = s.assign(1, 0.0)
    t_mid = min(a0.t_done, a1.t_done) / 2
    expect = (a0.t_done - t_mid) + (a1.t_done - t_mid)
    assert s.backlog(t_mid) == pytest.approx(expect)
    # all work drained -> zero again; and an idle executor alongside an
    # in-flight one contributes nothing
    assert s.backlog(max(a0.t_done, a1.t_done)) == 0.0
    assert s.backlog(a0.t_start) == pytest.approx(
        (a0.t_done - a0.t_start) + (a1.t_done - a0.t_start))


# ================================================= engine-level chaos
def nvr_engine(sched=None, n=8, **kw):
    frames, frame_of, videos, dets = make_nvr_streams(2, n, rate=4.0)
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    eng = DetectionEngine(detect_fn=oracle, n_replicas=2,
                          service_time=0.05, faults=sched, **kw)
    return eng, frames


def test_engine_no_fault_bit_identical():
    eng0, frames = nvr_engine(None)
    eng1, _ = nvr_engine(FaultSchedule())            # empty == inert
    r0, r1 = eng0.serve(frames), eng1.serve(frames)
    assert set(r0) == set(r1)
    assert r0["retries"] == r1["retries"] == {}
    assert [(r.rid, r.replica, r.t_start, r.t_done)
            for r in r0["responses"]] == \
           [(r.rid, r.replica, r.t_start, r.t_done)
            for r in r1["responses"]]
    assert r0["dropped"] == r1["dropped"]


def test_engine_replica_kill_reported_and_survives():
    sched = FaultSchedule.replica_kill(0.5, replica=1)
    eng, frames = nvr_engine(sched, n=16)
    rep = eng.serve(frames)
    assert rep["retries"].get(1, 0) >= 1
    assert rep["failovers"].get(1, 0) >= 1
    # blocking mode + a surviving replica: every frame still served
    assert rep["coverage"] == 1.0
    assert all(r.replica == 0 for r in rep["responses"]
               if r.t_start > 0.5 + eng.scheduler.timeout_k
               / eng.replicas[1].mu_effective)
    rep2 = eng.serve(frames)                         # replays identically
    assert rep["retries"] == rep2["retries"]
    assert [r.rid for r in rep["responses"]] == [r.rid
                                                 for r in rep2["responses"]]


def test_engine_all_dead_drops_instead_of_hanging():
    sched = (FaultSchedule.replica_kill(0.2, replica=0)
             + FaultSchedule.replica_kill(0.2, replica=1))
    eng, frames = nvr_engine(sched, n=16)
    rep = eng.serve(frames)                          # must terminate
    assert rep["coverage"] < 1.0
    # every frame is a response or a drop (a scheduler-lost frame is
    # dropped TOO — frames_lost attributes the loss to its executor)
    assert len(rep["dropped"]) + len(rep["responses"]) == len(frames)
    assert sum(rep["frames_lost"].values()) >= 1


def test_engine_track_mode_coasts_through_kill():
    sched = FaultSchedule.replica_kill(0.5, replica=1)
    eng, frames = nvr_engine(sched, n=16, track_and_interpolate=True)
    rep = eng.serve(frames)
    assert sum(rep["retries"].values()) >= 1        # the kill was felt
    # tracker mode never leaves a gap: dropped arrivals are emitted with
    # coasted boxes, so per-stream coverage holds at 1.0 under the kill
    assert rep["coverage"] == 1.0
    assert all(v["coverage"] == 1.0 for v in rep["per_stream"].values())
    # and quality holds within 20% of the fault-free serve
    clean, _ = nvr_engine(None, n=16, track_and_interpolate=True)
    _, _, videos, _ = make_nvr_streams(2, 16, rate=4.0)
    q_clean = evaluate_streams(videos, clean.serve(frames)["streams"], 16)
    q_kill = evaluate_streams(videos, rep["streams"], 16)
    assert q_kill["map_mean"] >= 0.8 * q_clean["map_mean"]


def test_engine_rejects_empty_pool():
    with pytest.raises(ValueError):
        DetectionEngine(detect_fn=stub_detect, n_replicas=0)


# ================================================= sharded-layer chaos
def sharded_nvr(n_frames=24, **kw):
    frames, frame_of, videos, dets = make_nvr_streams(4, n_frames,
                                                      rate=4.0)
    oracle = proxy_detect_fn_streams(videos, dets, frame_of)
    eng = ShardedDetectionEngine(detect_fn=oracle, n_replicas=2,
                                 service_time=0.02, n_shards=2,
                                 rebalance=True, epoch_s=2.0,
                                 track_and_interpolate=True, **kw)
    return eng, frames


def test_shard_events_require_rebalance():
    sched = FaultSchedule.shard_kill(1.0, shard=0)
    with pytest.raises(ValueError, match="rebalance"):
        ShardedDetectionEngine(detect_fn=stub_detect, n_shards=2,
                               faults=sched)
    with pytest.raises(ValueError, match="watchdog|supervisor"):
        ShardedDetectionEngine(detect_fn=stub_detect, n_shards=2,
                               supervisor=Watchdog())
    # replica-level events need no epoch loop
    ShardedDetectionEngine(detect_fn=stub_detect, n_shards=2,
                           faults=FaultSchedule.replica_kill(1.0,
                                                             replica=0))


def test_sharded_no_fault_bit_identical():
    """An empty schedule and an idle watchdog cost nothing: the
    fault-free epoch loop serves the same rids on the same replicas at
    the same clocks with or without them."""
    def served(rep):
        return ([(r.rid, r.replica, r.t_start, r.t_done)
                 for r in rep["responses"]], rep["dropped"],
                rep["migrations"], rep["per_replica"])

    eng0, frames = sharded_nvr()
    eng1, _ = sharded_nvr(faults=FaultSchedule())
    eng2, _ = sharded_nvr(supervisor=Watchdog())
    r0, r1, r2 = (e.serve(frames) for e in (eng0, eng1, eng2))
    assert set(r0) == set(r1)
    assert served(r0) == served(r1) == served(r2)
    assert r2["faults"]["restarts"] == r2["faults"]["loans"] == []


def test_shard_kill_recovers_within_epoch():
    sched = FaultSchedule.shard_kill(2.5, shard=0)
    eng, frames = sharded_nvr(faults=sched, supervisor=Watchdog())
    rep = eng.serve(frames)
    fl = rep["faults"]
    assert fl["n_events"] == 1 and fl["frames_lost_shard"] > 0
    # the watchdog restarted the shard at the FIRST boundary after the
    # kill (within one epoch), and its streams were evacuated
    assert fl["restarts"] == [{"epoch": 1, "shard": 0, "ok": True,
                               "t": 4.0}]
    assert any(m["src"] == 0 for m in rep["migrations"])
    assert rep["recovered_coverage"] == 1.0
    # the lost frames are accounted as drops, stream by stream
    assert len(rep["dropped"]) >= fl["frames_lost_shard"]
    assert sum(v["dropped"] for v in rep["per_stream"].values()) \
        == len(rep["dropped"])
    # pools end at their constructed sizes
    assert all(len(e.replicas) == 2 for e in eng.engines)


def test_shard_kill_replay_deterministic():
    sched = FaultSchedule.shard_kill(2.5, shard=0)
    eng, frames = sharded_nvr(faults=sched, supervisor=Watchdog())
    r1, r2 = eng.serve(frames), eng.serve(frames)
    assert [r.rid for r in r1["responses"]] == [r.rid
                                                for r in r2["responses"]]
    assert r1["dropped"] == r2["dropped"]
    assert r1["faults"] == r2["faults"]
    assert r1["recovered_coverage"] == r2["recovered_coverage"]


def test_permanent_kill_recovers_by_evacuation_alone():
    sched = FaultSchedule.shard_kill(2.5, shard=0, permanent=True)
    eng, frames = sharded_nvr(faults=sched, supervisor=Watchdog())
    rep = eng.serve(frames)
    assert rep["faults"]["restarts"][0]["ok"] is False
    assert any(m["src"] == 0 for m in rep["migrations"])
    assert rep["recovered_coverage"] == 1.0          # evacuation carried it


def test_unsupervised_shard_kill_degrades():
    """Without a watchdog the kill still terminates cleanly (frames lost
    until the schedule's own revive), establishing the baseline the
    supervisor improves on."""
    killed = FaultSchedule.shard_kill(2.5, shard=0, revive_t=4.5)
    eng, frames = sharded_nvr(faults=killed)
    rep = eng.serve(frames)
    assert rep["faults"]["restarts"] == []
    assert rep["faults"]["frames_lost_shard"] > 0
    assert rep["recovered_coverage"] == 1.0          # schedule revive
    sup_eng, _ = sharded_nvr(faults=killed, supervisor=Watchdog())
    sup_rep = sup_eng.serve(frames)
    assert len(sup_rep["dropped"]) <= len(rep["dropped"])


def hot_stream_trace():
    """One 30 fps camera on shard 0, one 1 fps camera on shard 1 — the
    single-hot-stream overload ``rebalance_streams`` rule 3 refuses to
    migrate (moving the only stream just relocates the overload)."""
    events = [(k / 30.0, 0, k) for k in range(240)]
    events += [(k + 0.5, 1, k) for k in range(8)]
    events.sort()
    return [FrameRequest(rid, np.zeros((4, 4, 3), np.float32), t,
                         stream_id=s)
            for rid, (t, s, k) in enumerate(events)]


def lending_engine(**kw):
    return ShardedDetectionEngine(detect_fn=stub_detect, n_replicas=2,
                                  service_time=0.1, drop_when_busy=True,
                                  micro_batch=1, max_micro_batch=1,
                                  n_shards=2, rebalance=True,
                                  epoch_s=2.0, **kw)


def test_replica_lending_strictly_reduces_drops():
    frames = hot_stream_trace()
    rep_no = lending_engine().serve(frames)
    assert not rep_no["migrations"]                  # stealing refused
    eng = lending_engine(supervisor=Watchdog(idle_backlog_s=0.5))
    rep_ln = eng.serve(frames)
    loans = rep_ln["faults"]["loans"]
    assert loans and all(ln["lender"] == 1 and ln["borrower"] == 0
                         for ln in loans)
    assert all(ln["returned_epoch"] is not None for ln in loans)
    assert len(rep_ln["dropped"]) < len(rep_no["dropped"])
    assert all(len(e.replicas) == 2 for e in eng.engines)
    # renumbered guest-replica ids stay within the high-water id space
    assert max(rep_ln["per_replica"]) >= 4           # pool high-water = 3+2
    assert set(rep_ln["per_replica"]) == set(range(5))


def test_lending_disabled_watchdog_is_inert():
    frames = hot_stream_trace()
    rep_no = lending_engine().serve(frames)
    rep_off = lending_engine(
        supervisor=Watchdog(lend=False)).serve(frames)
    assert rep_off["faults"]["loans"] == []
    assert len(rep_off["dropped"]) == len(rep_no["dropped"])
    assert [r.rid for r in rep_off["responses"]] == \
           [r.rid for r in rep_no["responses"]]


def test_seeded_random_chaos_end_to_end():
    sched = FaultSchedule.random(3, 6.0, n_shards=2, n_replicas=2,
                                 n_replica_events=2, n_shard_events=1)
    eng, frames = sharded_nvr(faults=sched, supervisor=Watchdog())
    r1, r2 = eng.serve(frames), eng.serve(frames)
    assert r1["faults"] == r2["faults"]
    assert [r.rid for r in r1["responses"]] == [r.rid
                                                for r in r2["responses"]]
    assert r1["recovered_coverage"] == r2["recovered_coverage"]
    # conservation: every frame is a response, a drop, or scheduler-lost
    lost = sum(r1["frames_lost"].values())
    assert len(r1["responses"]) + len(r1["dropped"]) + lost \
        >= len(frames)
