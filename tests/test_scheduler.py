"""Continuous-batching scheduler + elastic fleet tests (PR 7).

Every test runs the modeled clock with ``execute=False`` (pure
discrete-event simulation, no devices) except the final 8-virtual-device
parity subprocess. The edge cases pinned here are the ISSUE checklist:
a straggler must not stall co-scheduled slots, a queue-skew must
trigger exactly one steal per boundary, a scale-down drain must neither
drop nor double-charge, a scale-up must pay the artifact-restore
latency before serving, and empty-fleet / zero-request streams must
produce well-formed reports.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config
from repro.pipeline import (AutoscalePolicy, ExecutionSpec, Placement,
                            Serving)
from repro.pipeline.artifact import spec_from_dict, spec_to_dict
from repro.serve import (FaultSchedule, Request, ServeEngine,
                         total_cost)
from tests.test_parallel import run_in_mesh_subprocess

CFG = get_config("alexnet")


def _req(rid, t=0.0, cost=1.0):
    return Request(rid=rid, t_arrival=t, cost=cost,
                   image=np.zeros((1, 1, 1), np.float32))


def _engine(**kw):
    kw.setdefault("scheduler", "continuous")
    return ServeEngine(CFG, [], clock="modeled", execute=False, **kw)


# ---------------------------------------------------------------------------
# slot lifecycle
# ---------------------------------------------------------------------------

def test_straggler_does_not_stall_coscheduled_slots():
    """A cost-4 straggler occupies only its own slot: the three
    requests admitted alongside it retire a whole round earlier."""
    B = 4
    tr = total_cost(CFG, B)
    eng = _engine(batch=B, replicas=1)
    reqs = [_req(i, cost=4.0 if i == 0 else 1.0) for i in range(B)]
    done, rep = eng.serve(reqs)
    t_done = {c.rid: c.t_done for c in done}
    assert all(c.status == "ok" for c in done)
    # non-stragglers retire at the first boundary >= t_round
    for rid in (1, 2, 3):
        assert t_done[rid] == pytest.approx(tr, rel=1e-6)
    # the straggler holds its slot for cost * t_round
    assert t_done[0] == pytest.approx(4 * tr, rel=1e-6)
    # gang rounds would have stalled ALL four until 4 * t_round
    geng = ServeEngine(CFG, [], batch=B, replicas=1, clock="modeled",
                       execute=False)
    gdone, _ = geng.serve([_req(i, cost=4.0 if i == 0 else 1.0)
                           for i in range(B)])
    assert all(c.t_done == pytest.approx(4 * tr, rel=1e-6)
               for c in gdone)


def test_queue_skew_triggers_exactly_one_steal():
    """5 requests pre-loaded onto replica 0's queue, skew threshold 3:
    the idle replica steals exactly ONE tail request (one steal per
    boundary, and the rebalanced depths never re-cross the threshold)."""
    eng = _engine(batch=1, replicas=2, steal_threshold=3, retries=1)
    for i in range(5):
        eng.router.queues[0].submit(_req(i))
    done, rep = eng.serve([])
    assert sorted(c.rid for c in done) == list(range(5))
    assert all(c.status == "ok" for c in done)
    assert rep.n_steals == 1
    # the steal charged the stolen request's budget — but is counted as
    # a steal, not a retry
    assert rep.n_retries == 0
    stolen = [c for c in done if c.attempts == 1]
    assert len(stolen) == 1 and stolen[0].replica == 1
    assert all(c.attempts == 0 for c in done if c is not stolen[0])


def test_stealing_is_off_without_retry_budget():
    """A steal charges the retry budget, so retries=0 turns stealing
    off by construction — the skewed queue still drains on its own
    replica and nothing is failed."""
    eng = _engine(batch=1, replicas=2, steal_threshold=3, retries=0)
    for i in range(5):
        eng.router.queues[0].submit(_req(i))
    done, rep = eng.serve([])
    assert sorted(c.rid for c in done) == list(range(5))
    assert all(c.status == "ok" and c.replica == 0 and c.attempts == 0
               for c in done)
    assert rep.n_steals == 0


# ---------------------------------------------------------------------------
# elastic scaling
# ---------------------------------------------------------------------------

def test_scale_down_drains_without_drop_or_double_charge():
    """The drain evacuates the victim's queue free of retry charge and
    lets in-flight slots finish: every request ok with attempts=0, even
    with retries=0 (a charged evacuation would have failed them)."""
    B = 4
    tr = total_cost(CFG, B)
    eng = _engine(batch=B, replicas=2, retries=0,
                  autoscale=AutoscalePolicy(min_replicas=1, max_replicas=2,
                                            interval=tr / 4))
    done, rep = eng.serve([_req(i) for i in range(40)])
    assert sorted(c.rid for c in done) == list(range(40))
    assert all(c.status == "ok" and c.attempts == 0 for c in done)
    assert rep.n_scale_down >= 1 and rep.n_scale_up == 0
    assert rep.replicas_final == 2 - rep.n_scale_down
    kinds = [e["kind"] for e in rep.scale_events]
    assert kinds.count("down") == rep.n_scale_down


def test_scale_up_charges_restore_latency():
    """A scaled-up replica serves only after the modeled artifact
    restore: its first completion lands strictly after the decision
    time plus t_restore."""
    B = 4
    tr = total_cost(CFG, B)
    eng = _engine(batch=B, replicas=1, retries=0,
                  autoscale=AutoscalePolicy(min_replicas=1, max_replicas=2,
                                            interval=tr / 8))
    t_restore = eng._versions[eng._cur_version]["t_restore"]
    # arrivals at exactly the single replica's capacity: slots saturate
    # (util 1.0 > util_high), and the stream outlives the restore so
    # later arrivals dispatch onto the scaled-up replica
    done, rep = eng.serve([_req(i, t=i * tr / 4) for i in range(96)])
    assert sorted(c.rid for c in done) == list(range(96))
    assert rep.n_scale_up >= 1
    ups = [e for e in rep.scale_events if e["kind"] == "up"]
    assert len(ups) == rep.n_scale_up
    new_r = ups[0]["replica"]
    assert new_r != 0
    served_by_new = [c for c in done if c.replica == new_r]
    assert served_by_new, "the scaled-up replica never served"
    t_first = min(c.t_done for c in served_by_new)
    assert t_first > ups[0]["t"] + t_restore
    # consistency between the counters and the final fleet size
    assert rep.replicas_final == 1 + rep.n_scale_up - rep.n_scale_down


def test_zero_requests_is_well_formed():
    done, rep = _engine(batch=4, replicas=2).serve([])
    assert done == [] and rep.n_done == 0
    assert rep.scheduler == "continuous"
    assert rep.n_steals == 0 and rep.scale_events == []
    assert rep.replicas_final == 2


def test_dead_fleet_fails_all_explicitly():
    """Every replica down, no recovery scheduled, no elasticity: every
    outstanding request ends as an explicit failed Completion — never
    stranded (the chaos invariant under the continuous scheduler)."""
    tr = total_cost(CFG, 4)
    eng = _engine(batch=4, replicas=1, retries=1)
    chaos = FaultSchedule.at(tr * 0.5, replica=0)
    done, rep = eng.serve([_req(i, t=i * tr / 8) for i in range(16)],
                          faults=chaos)
    assert sorted(c.rid for c in done) == list(range(16))
    assert rep.n_failures == 1
    by_status = {s: [c for c in done if c.status == s]
                 for s in ("ok", "failed")}
    assert len(by_status["ok"]) + len(by_status["failed"]) == 16
    assert len(by_status["failed"]) > 0
    done2, _ = eng.serve([_req(i, t=i * tr / 8) for i in range(16)],
                         faults=FaultSchedule.at(tr * 0.5, replica=0))
    assert sorted(c.rid for c in done2) == list(range(16))


def test_fail_recover_chaos_all_accounted():
    """Fail + recover mid-stream under continuous batching: in-flight
    slots readmit against the retry budget and the replica rejoins
    after the modeled restore."""
    tr = total_cost(CFG, 4)
    eng = _engine(batch=4, replicas=2, retries=2)
    chaos = FaultSchedule.at(tr * 1.5, tr * 3.0, replica=0)
    # enough requests that the stream outlasts the constant restore
    done, rep = eng.serve([_req(i, t=i * tr / 16) for i in range(144)],
                          faults=chaos)
    assert sorted(c.rid for c in done) == list(range(144))
    assert rep.n_failures == 1 and rep.n_recoveries == 1
    assert all(c.status == "ok" for c in done)


# ---------------------------------------------------------------------------
# acceptance: continuous batching beats gang rounds on a skewed trace
# ---------------------------------------------------------------------------

def _skewed_trace(n, rate, straggler_every=17, straggler_cost=4.0):
    rng = np.random.default_rng(7)
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    return [_req(i, t=float(t[i]),
                 cost=straggler_cost
                 if i % straggler_every == straggler_every - 1 else 1.0)
            for i in range(n)]


def test_cb_beats_gang_p95_on_skewed_trace():
    B = 8
    tr = total_cost(CFG, B)
    trace = _skewed_trace(64, rate=0.8 * 2 * B / tr)
    geng = ServeEngine(CFG, [], batch=B, replicas=2, clock="modeled",
                       execute=False, retries=2)
    _, grep = geng.serve(list(trace))
    ceng = _engine(batch=B, replicas=2, retries=2, steal_threshold=1)
    cdone, crep = ceng.serve(list(trace))
    assert sorted(c.rid for c in cdone) == list(range(64))
    assert crep.p95_ms < grep.p95_ms
    assert crep.n_steals > 0


def test_continuous_schedule_is_deterministic():
    trace = _skewed_trace(48, rate=1e5)
    runs = []
    for _ in range(2):
        eng = _engine(batch=4, replicas=2, retries=2, steal_threshold=1,
                      autoscale=AutoscalePolicy(min_replicas=1,
                                                max_replicas=4,
                                                interval=1e-4))
        done, rep = eng.serve(list(trace))
        runs.append(([(c.rid, c.t_done, c.replica, c.status, c.attempts)
                      for c in done], dataclasses.asdict(rep)))
    assert runs[0] == runs[1]


# ---------------------------------------------------------------------------
# construction-time validation (engine + spec agree)
# ---------------------------------------------------------------------------

def test_engine_validation_errors():
    with pytest.raises(ValueError, match="scheduler"):
        ServeEngine(CFG, [], scheduler="nope", clock="modeled",
                    execute=False)
    with pytest.raises(ValueError, match="modeled"):
        ServeEngine(CFG, [], scheduler="continuous", clock="measured")
    with pytest.raises(ValueError, match="continuous"):
        ServeEngine(CFG, [], clock="modeled", execute=False,
                    steal_threshold=2)   # stealing needs the cb scheduler
    with pytest.raises(ValueError, match="continuous"):
        ServeEngine(CFG, [], clock="modeled", execute=False,
                    autoscale=AutoscalePolicy())
    with pytest.raises(ValueError, match="autoscale range"):
        _engine(replicas=1,
                autoscale=AutoscalePolicy(min_replicas=2, max_replicas=4))


def test_spec_validation_mirrors_engine():
    with pytest.raises(ValueError, match="modeled"):
        ExecutionSpec(serving=Serving(scheduler="continuous"))
    with pytest.raises(ValueError, match="continuous"):
        ExecutionSpec(serving=Serving(clock="modeled", steal_threshold=1))
    with pytest.raises(ValueError, match="continuous"):
        ExecutionSpec(serving=Serving(clock="modeled",
                                      autoscale=AutoscalePolicy()))
    with pytest.raises(ValueError, match="autoscale range"):
        ExecutionSpec(
            placement=Placement(replicas=8),
            serving=Serving(clock="modeled", scheduler="continuous",
                            autoscale=AutoscalePolicy(max_replicas=4)))


def test_autoscale_policy_validation():
    for bad in (dict(min_replicas=0), dict(min_replicas=4, max_replicas=2),
                dict(interval=0.0), dict(cooldown=-1.0),
                dict(util_low=0.9, util_high=0.5), dict(window=0)):
        with pytest.raises(ValueError):
            AutoscalePolicy(**bad)


def test_spec_dict_roundtrip_with_autoscale():
    """The artifact's spec (de)serialization rebuilds the nested
    AutoscalePolicy — a loaded artifact keeps its elastic policy."""
    spec = ExecutionSpec(
        placement=Placement(replicas=2),
        serving=Serving(clock="modeled", execute=False,
                        scheduler="continuous", steal_threshold=2,
                        retries=1,
                        autoscale=AutoscalePolicy(min_replicas=1,
                                                  max_replicas=6)))
    back = spec_from_dict(spec_to_dict(spec))
    assert back == spec
    assert isinstance(back.serving.autoscale, AutoscalePolicy)


# ---------------------------------------------------------------------------
# parity: continuous batching with execute=True (8 virtual devices)
# ---------------------------------------------------------------------------

def test_cb_parity_with_steals_and_autoscale_8dev():
    """ISSUE acceptance: under continuous batching with stealing and
    autoscaling enabled, every served prediction still matches the
    unsharded ``cnn_forward`` bit-for-bit (admission groups run padded
    row-independent forwards, so scheduling cannot change outputs)."""
    run_in_mesh_subprocess("""
        from repro.configs import get_config
        from repro.models.cnn import cnn_forward, init_cnn_params
        from repro.serve import AutoscalePolicy, Request, ServeEngine
        cfg = get_config('alexnet').smoke()
        key = jax.random.key(5)
        params = init_cnn_params(key, cfg)
        N = 48
        x = jax.random.normal(key, (N, cfg.input_hw, cfg.input_hw,
                                    cfg.input_ch), jnp.float32)
        eng = ServeEngine(cfg, params, batch=4, replicas=2,
                          clock='modeled', scheduler='continuous',
                          steal_threshold=1, retries=2,
                          autoscale=AutoscalePolicy(min_replicas=1,
                                                    max_replicas=4,
                                                    interval=1e-4))
        reqs = [Request(rid=i, image=np.asarray(x[i]),
                        t_arrival=i * 5e-5,
                        cost=4.0 if i % 7 == 6 else 1.0)
                for i in range(N)]
        done, rep = eng.serve(reqs)
        assert sorted(c.rid for c in done) == list(range(N))
        assert rep.scheduler == 'continuous'
        want = np.asarray(jnp.argmax(
            cnn_forward(params, x, cfg, use_pallas=True), -1))
        for c in done:
            if c.status == 'ok':
                assert c.pred == int(want[c.rid]), (c.rid, c.pred)
    """)
