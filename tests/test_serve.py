"""Distributed serving subsystem tests: router policy, admission
control, hardened reports, stage planning, the GEMM DSE, and the engine's
three execution modes (parity on 8 virtual devices via subprocess)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import autotune
from repro.serve import (MicroBatcher, Request, Router, latency_report,
                         plan_stages, total_cost)
from repro.serve.engine import ServeEngine
from repro.serve.router import Completion
from repro.serve.stage_planner import group_io_shapes
from tests.test_parallel import run_in_mesh_subprocess

KEY = jax.random.key(11)


def _req(rid, t=0.0, hw=8, ch=3):
    return Request(rid=rid, t_arrival=t,
                   image=np.zeros((hw, hw, ch), np.float32))


# ---------------------------------------------------------------------------
# report hardening (satellite: empty / n=1 edge cases)
# ---------------------------------------------------------------------------

def test_latency_report_empty_is_well_formed():
    rep = latency_report([])
    assert rep["n"] == 0 and rep["throughput"] == 0.0
    assert np.isnan(rep["p50_ms"]) and np.isnan(rep["p95_ms"])


def test_latency_report_nearest_rank_n1():
    """nearest-rank: ceil(q*1)-1 = 0 for every q — p50 == p95 == the one
    sample."""
    done = [Completion(rid=0, pred=1, t_arrival=1.0, t_done=1.25)]
    rep = latency_report(done)
    assert rep["n"] == 1
    assert rep["p50_ms"] == pytest.approx(250.0)
    assert rep["p95_ms"] == pytest.approx(250.0)
    assert rep["throughput"] == pytest.approx(1 / 1.25)


def test_latency_report_nearest_rank_small_n():
    done = [Completion(rid=i, pred=0, t_arrival=0.0, t_done=float(i + 1))
            for i in range(4)]                    # latencies 1,2,3,4 s
    rep = latency_report(done)
    assert rep["p50_ms"] == pytest.approx(2000.0)   # ceil(0.5*4)=2nd
    assert rep["p95_ms"] == pytest.approx(4000.0)   # ceil(0.95*4)=4th


def test_microbatcher_empty_queue_well_formed():
    mb = MicroBatcher(4)
    take, imgs, n_real = mb.next_batch()
    assert take == [] and imgs is None and n_real == 0


def test_microbatcher_pads_partial_chunk():
    mb = MicroBatcher(4)
    for i in range(2):
        mb.submit(_req(i))
    take, imgs, n_real = mb.next_batch()
    assert len(take) == 2 and n_real == 2
    assert imgs.shape[0] == 4                     # padded to the plan batch
    assert np.all(np.asarray(imgs[2:]) == 0)
    assert len(mb) == 0


# ---------------------------------------------------------------------------
# router policy
# ---------------------------------------------------------------------------

def test_router_least_loaded_dispatch():
    r = Router(4, plan_batch=8)
    for i in range(10):
        assert r.dispatch(_req(i))
    depths = sorted(len(q) for q in r.queues)
    assert depths == [2, 2, 3, 3]                 # balanced within 1
    assert r.backlog() == 10 and not r.rejected


def test_router_admission_control_rejects_over_bound():
    r = Router(2, plan_batch=8, max_queue=2)
    admitted = [r.dispatch(_req(i)) for i in range(6)]
    assert admitted == [True] * 4 + [False] * 2   # 2 replicas x bound 2
    assert len(r.rejected) == 2 and r.backlog() == 4


def test_router_drain_round_includes_idle_replicas():
    r = Router(3, plan_batch=2)
    r.dispatch(_req(0))
    round_items = r.drain_round()
    assert len(round_items) == 3
    reals = [n for _, _, _, n in round_items]
    assert sorted(reals) == [0, 0, 1]


# ---------------------------------------------------------------------------
# stage planner
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["alexnet", "vgg16"])
def test_stage_planner_covers_all_groups_contiguously(name):
    from repro.models.cnn import fuse_plan
    cfg = get_config(name)
    plan = plan_stages(cfg, 4, batch=2)
    flat = [g for s in plan.stages for g in s.groups]
    assert flat == fuse_plan(cfg)                 # exact contiguous cover
    # boundary shapes chain: stage s out == stage s+1 in
    for a, b in zip(plan.stages, plan.stages[1:]):
        assert a.out_shape == b.in_shape


def test_stage_planner_balances_roofline_times():
    cfg = get_config("vgg16")
    plan = plan_stages(cfg, 4, batch=2)
    # balanced: the worst stage is far below the whole-network time and
    # within a small factor of the ideal quarter
    assert plan.t_stage_max < plan.t_sum
    assert plan.t_stage_max <= 2.0 * plan.t_sum / 4
    assert 0 < plan.balance <= 1.0


def test_stage_planner_rejects_bad_stage_counts():
    cfg = get_config("alexnet")
    n_groups = len(group_io_shapes(cfg))
    with pytest.raises(ValueError):
        plan_stages(cfg, n_groups + 1, batch=1)
    with pytest.raises(ValueError):
        plan_stages(cfg, 0, batch=1)


def test_total_cost_positive_and_dtype_aware():
    cfg = get_config("alexnet")
    t32 = total_cost(cfg, 8)
    t8 = total_cost(cfg, 8, dtype="int8")
    assert t32 > 0 and t8 > 0
    assert t8 < t32                               # int8 models faster


# ---------------------------------------------------------------------------
# GEMM DSE (satellite: int8 FC plans are tuned)
# ---------------------------------------------------------------------------

def test_gemm_dse_feasible_and_memoised():
    shape = autotune.GemmShape(m=8, k=9216, n=4096)
    plan = autotune.get_gemm_plan(shape)
    assert plan.vmem_bytes <= 16 * 2 ** 20
    assert plan.t_model > 0
    assert autotune.get_gemm_plan(shape) is plan  # memoised
    assert autotune.gemm_vmem_bytes(shape, plan.bm, plan.bn,
                                    plan.bk) == plan.vmem_bytes


def test_gemm_dse_int8_models_faster():
    """The ROADMAP closure: int8 FC plans are tuned dtype-aware — 4x less
    traffic + 2x op rate on a weight-traffic-bound classifier layer."""
    fp32 = autotune.get_gemm_plan(autotune.GemmShape(m=8, k=9216, n=4096))
    int8 = autotune.get_gemm_plan(
        autotune.GemmShape(m=8, k=9216, n=4096, dtype="int8"))
    assert int8.t_model <= 0.5 * fp32.t_model


def test_fc_layers_route_through_gemm_dse(forward):
    from repro.models.cnn import init_cnn_params
    autotune.clear_registry()
    cfg = get_config("alexnet").smoke()
    params = init_cnn_params(KEY, cfg)
    x = jax.random.normal(KEY, (2, cfg.input_hw, cfg.input_hw,
                                cfg.input_ch), jnp.float32)
    forward(params, x, cfg, use_pallas=True)
    gemm = autotune.gemm_registry_snapshot()
    n_fc = sum(1 for l in cfg.layers if l.kind == "fc")
    assert len(gemm) == n_fc                      # one plan per FC layer
    assert all(r["shape"]["m"] == 2 for r in gemm)  # keyed by the batch


# ---------------------------------------------------------------------------
# engine: modeled simulation (no devices needed)
# ---------------------------------------------------------------------------

def _sim(cfg, n, *, batch=8, replicas=1, pp_stages=1, rate=None,
         max_queue=0):
    if rate is None:
        reqs = [_req(i, 0.0, cfg.input_hw, cfg.input_ch) for i in range(n)]
    else:
        t, reqs = 0.0, []
        for i in range(n):
            t += 1.0 / rate
            reqs.append(_req(i, t, cfg.input_hw, cfg.input_ch))
    eng = ServeEngine(cfg, [], batch=batch, replicas=replicas,
                      pp_stages=pp_stages, clock="modeled",
                      max_queue=max_queue, execute=False)
    done, rep = eng.serve(reqs)
    return eng, done, rep


def test_engine_modeled_dp_speedup_at_least_3x():
    """The PR acceptance bound: 4 replicas sharded over the data axis
    achieve >= 3x aggregate modeled throughput vs one replica."""
    cfg = get_config("alexnet")
    _, _, single = _sim(cfg, 96, replicas=1)
    _, _, dp4 = _sim(cfg, 96, replicas=4)
    assert single.n_done == dp4.n_done == 96
    assert dp4.throughput >= 3.0 * single.throughput


def test_engine_modeled_deterministic():
    cfg = get_config("alexnet")
    _, _, a = _sim(cfg, 32, replicas=2)
    _, _, b = _sim(cfg, 32, replicas=2)
    assert a.to_dict() == b.to_dict()


def test_engine_admission_control_accounting():
    cfg = get_config("alexnet")
    # everything arrives at t=0: with a queue bound of 1 per replica,
    # only replicas*1 requests are admitted before the first round
    eng, done, rep = _sim(cfg, 40, replicas=2, max_queue=1)
    assert rep.n_rejected > 0
    assert rep.n_done + rep.n_rejected == 40
    assert rep.n_done == len(done)


def test_engine_pp_round_uses_bubble_model():
    from repro.core.roofline import pipeline_bubble_fraction
    cfg = get_config("alexnet")
    eng, _, rep = _sim(cfg, 16, pp_stages=4)
    sp = eng.stage_plan
    assert eng.t_round_model == pytest.approx(
        sp.round_time(eng.n_micro))
    assert rep.bubble_fraction == pytest.approx(
        pipeline_bubble_fraction(4, eng.n_micro))


def test_engine_rejects_bad_args():
    cfg = get_config("alexnet")
    with pytest.raises(ValueError):
        ServeEngine(cfg, [], replicas=0, execute=False)
    with pytest.raises(ValueError):
        ServeEngine(cfg, [], clock="wall", execute=False)


def test_engine_needs_devices_for_mesh_modes():
    cfg = get_config("alexnet").smoke()
    if jax.device_count() >= 4:
        pytest.skip("single-device check needs an unforced device count")
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        ServeEngine(cfg, [], replicas=4)


# ---------------------------------------------------------------------------
# engine + pipeline parity on 8 virtual devices (subprocess)
# ---------------------------------------------------------------------------

def test_dp_engine_preds_match_single_device():
    run_in_mesh_subprocess("""
        from repro.configs import get_config
        from repro.models.cnn import init_cnn_params
        from repro.pipeline import (ExecutionSpec, Precision, Serving,
                                    compile_cnn)

        def fwd(p, x, quant='none'):
            # the compiled forward at x's batch (the Pallas path)
            return compile_cnn(cfg, ExecutionSpec(
                precision=Precision(quant=quant),
                serving=Serving(batch=x.shape[0])), p,
                with_engine=False).forward(x)
        from repro.serve import Request, ServeEngine
        cfg = get_config('alexnet').smoke()
        key = jax.random.key(3)
        params = init_cnn_params(key, cfg)
        x = jax.random.normal(key, (16, cfg.input_hw, cfg.input_hw,
                                    cfg.input_ch), jnp.float32)
        reqs = [Request(rid=i, image=np.asarray(x[i]), t_arrival=0.0)
                for i in range(16)]
        eng = ServeEngine(cfg, params, batch=4, replicas=4,
                          clock='modeled')
        done, rep = eng.serve(reqs)
        assert rep.n_done == 16 and rep.rounds == 1
        want = np.asarray(jnp.argmax(
            fwd(params, x), -1))
        preds = {c.rid: c.pred for c in done}
        assert all(preds[i] == int(want[i]) for i in range(16))
    """)


def test_pipeline_stages_cnn_fp32_matches_unsharded():
    """Satellite: pipeline_forward with a CNN stage function on 8 virtual
    devices — fp32 parity with the unsharded forward."""
    run_in_mesh_subprocess("""
        from repro.configs import get_config
        from repro.models.cnn import init_cnn_params
        from repro.pipeline import (ExecutionSpec, Precision, Serving,
                                    compile_cnn)

        def fwd(p, x, quant='none'):
            # the compiled forward at x's batch (the Pallas path)
            return compile_cnn(cfg, ExecutionSpec(
                precision=Precision(quant=quant),
                serving=Serving(batch=x.shape[0])), p,
                with_engine=False).forward(x)
        from repro.serve import plan_stages, pipeline_logits
        cfg = get_config('alexnet').smoke()
        key = jax.random.key(3)
        params = init_cnn_params(key, cfg)
        x = jax.random.normal(key, (8, cfg.input_hw, cfg.input_hw,
                                    cfg.input_ch), jnp.float32)
        want = np.asarray(fwd(params, x))
        # pure pipeline (1x4) and hybrid (2x4) must both match
        for dp, mb_n in ((1, 4), (2, 2)):
            mesh = jax.make_mesh((dp, 4), ('data', 'pipe'),
                                 axis_types=(jax.sharding.AxisType.Auto,) * 2)
            sp = plan_stages(cfg, 4, batch=8 // (mb_n * dp))
            got = pipeline_logits(params, x, cfg, mesh, sp,
                                  n_microbatches=mb_n, use_pallas=True,
                                  dp_axis='data')
            np.testing.assert_allclose(np.asarray(got), want,
                                       rtol=1e-5, atol=1e-5)
    """)


def test_pipeline_stages_cnn_int8_bit_exact():
    """Satellite: the quantized path through device-resident pipeline
    stages is BIT-exact vs the unsharded int8 forward (stage slicing
    changes scheduling, never math)."""
    run_in_mesh_subprocess("""
        from repro.configs import get_config
        from repro.models.cnn import init_cnn_params
        from repro.pipeline import (ExecutionSpec, Precision, Serving,
                                    compile_cnn)

        def fwd(p, x, quant='none'):
            # the compiled forward at x's batch (the Pallas path)
            return compile_cnn(cfg, ExecutionSpec(
                precision=Precision(quant=quant),
                serving=Serving(batch=x.shape[0])), p,
                with_engine=False).forward(x)
        from repro.quant import calibrate_cnn
        from repro.serve import plan_stages, pipeline_logits
        cfg = get_config('alexnet').smoke()
        key = jax.random.key(3)
        params = init_cnn_params(key, cfg)
        x = jax.random.normal(key, (8, cfg.input_hw, cfg.input_hw,
                                    cfg.input_ch), jnp.float32)
        qp = calibrate_cnn(params, x, cfg)
        want = np.asarray(fwd(qp, x, 'int8'))
        mesh = jax.make_mesh((1, 4), ('data', 'pipe'),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        sp = plan_stages(cfg, 4, batch=2, dtype='int8')
        got = pipeline_logits(qp, x, cfg, mesh, sp, n_microbatches=4,
                              use_pallas=True, dp_axis='data')
        np.testing.assert_array_equal(np.asarray(got), want)
    """)


# ---------------------------------------------------------------------------
# fault injection: the resilient-fleet layer (device-free discrete-event)
# ---------------------------------------------------------------------------

from repro.serve import FaultEvent, FaultSchedule  # noqa: E402


def _terminal_rids(done):
    return sorted(c.rid for c in done)


def _chaos_sim(n, *, replicas=4, faults=None, retries=0, backoff=0.0,
               slo=0.0, rate=None, swap_to=None, swap_at=0.0):
    cfg = get_config("alexnet")
    if rate is None:
        reqs = [_req(i, 0.0, cfg.input_hw, cfg.input_ch) for i in range(n)]
    else:
        reqs = [_req(i, (i + 1) / rate, cfg.input_hw, cfg.input_ch)
                for i in range(n)]
    eng = ServeEngine(cfg, [], batch=8, replicas=replicas,
                      clock="modeled", execute=False, retries=retries,
                      backoff=backoff, slo=slo)
    if swap_to is not None:
        eng.hot_swap(swap_to, at=swap_at)
    done, rep = eng.serve(reqs, faults=faults)
    return eng, done, rep


def test_fault_schedule_validation():
    with pytest.raises(ValueError, match="expected one of"):
        FaultEvent(t=0.1, replica=0, kind="explode")
    with pytest.raises(ValueError, match="after"):
        FaultSchedule.at(1.0, 0.5)
    with pytest.raises(ValueError, match="not both"):
        FaultSchedule([FaultEvent(t=1.0, replica=0, kind="fail")],
                      mtbf=1.0, mttr=0.5, n_replicas=2)
    with pytest.raises(ValueError, match="mtbf"):
        FaultSchedule.mtbf(1.0, 0.0, 2)          # mttr missing
    fs = FaultSchedule.at(1.0, replica=7)
    with pytest.raises(ValueError, match="replica 7"):
        fs.validate_for(4)
    with pytest.raises(TypeError):
        len(FaultSchedule.mtbf(1.0, 0.5, 2))     # unbounded stream


def test_fault_schedule_mtbf_deterministic_stream():
    a = FaultSchedule.mtbf(3.0, 1.0, 4, seed=5)
    b = FaultSchedule.mtbf(3.0, 1.0, 4, seed=5)
    import itertools as it
    ea = list(it.islice(iter(a), 12))
    eb = list(it.islice(iter(b), 12))
    assert ea == eb
    assert all(x.t <= y.t for x, y in zip(ea, ea[1:]))
    # per replica the stream alternates fail / recover
    for r in range(4):
        kinds = [e.kind for e in ea if e.replica == r]
        assert kinds == (["fail", "recover"] * 6)[:len(kinds)]


def test_engine_fail_mid_burst_none_stranded():
    """A replica dies mid-burst with no recovery: its lost/queued
    requests re-dispatch to survivors; every admitted request is
    terminal (the chaos parity invariant, device-free half)."""
    t_round = total_cost(get_config("alexnet"), 8)
    fs = FaultSchedule.at(t_round * 0.5, replica=0)
    _, done, rep = _chaos_sim(96, faults=fs, retries=2)
    assert _terminal_rids(done) == list(range(96))
    assert rep.n_failures == 1 and rep.n_recoveries == 0
    assert rep.n_retries > 0 and rep.n_failed == 0
    assert rep.degraded_rounds > 0
    # the failed replica got partial-round busy credit only
    assert rep.utilization[0] < min(rep.utilization[1:])


def test_engine_fail_then_recover_charges_restore_latency():
    from repro.serve.engine import RESTORE_OVERHEAD_S
    t_round = total_cost(get_config("alexnet"), 8)
    fs = FaultSchedule.at(t_round * 0.5, t_round * 2.5, replica=1)
    # enough rounds that the stream outlasts the constant restore overhead
    eng, done, rep = _chaos_sim(400, faults=fs, retries=3)
    assert _terminal_rids(done) == list(range(400))
    assert rep.n_failures == 1 and rep.n_recoveries == 1
    assert len(rep.time_to_recover_s) == 1
    # TTR = (recover_at - fail_at) + modeled artifact restore (params
    # are empty here, so the restore is the constant reattach overhead)
    want = 2.0 * t_round + RESTORE_OVERHEAD_S
    assert rep.time_to_recover_s[0] == pytest.approx(want, rel=1e-6)


def test_engine_retry_budget_exhausted_is_explicit_failed():
    t_round = total_cost(get_config("alexnet"), 8)
    fs = FaultSchedule.at(t_round * 0.5, replica=0)
    _, done, rep = _chaos_sim(96, faults=fs, retries=0)
    assert _terminal_rids(done) == list(range(96))
    failed = [c for c in done if c.status == "failed"]
    assert failed and len(failed) == rep.n_failed
    assert all(c.pred == -1 and c.replica == -1 for c in failed)
    # failed completions are excluded from latency/throughput stats
    assert rep.n_done == 96 - len(failed)


def test_engine_fleet_death_fails_all_outstanding():
    """Every replica dies and nothing recovers: the loop must terminate
    with every outstanding request explicitly failed — not deadlock."""
    t_round = total_cost(get_config("alexnet"), 8)
    fs = FaultSchedule([FaultEvent(t=t_round * 0.5, replica=r, kind="fail")
                        for r in range(4)])
    _, done, rep = _chaos_sim(96, faults=fs, retries=1)
    assert _terminal_rids(done) == list(range(96))
    assert rep.n_failures == 4
    assert all(c.status == "failed" for c in done)


def test_engine_backoff_delays_readmission():
    """Exponential backoff: with a large base delay the retried requests
    complete strictly later than with none."""
    t_round = total_cost(get_config("alexnet"), 8)
    fs = FaultSchedule.at(t_round * 0.5, replica=0)
    _, fast, _ = _chaos_sim(96, faults=fs, retries=2, backoff=0.0)
    fs = FaultSchedule.at(t_round * 0.5, replica=0)
    _, slow, rep = _chaos_sim(96, faults=fs, retries=2,
                              backoff=10 * t_round)
    assert rep.n_retries > 0
    assert max(c.t_done for c in slow) > max(c.t_done for c in fast)
    retried = [c for c in slow if c.attempts > 0 and c.status == "ok"]
    assert retried
    # re-admission waits backoff * 2**(attempt-1) after the loss at 0.5R
    assert all(c.t_done >= t_round * 0.5 + 10 * t_round for c in retried)


def test_engine_slo_violations_counted():
    _, _, rep = _chaos_sim(96, slo=1e-9)
    assert rep.slo_s == 1e-9 and rep.slo_violations == rep.n_done
    _, _, rep = _chaos_sim(96, slo=1e9)
    assert rep.slo_violations == 0


def test_engine_mtbf_chaos_deterministic_and_terminal():
    t_round = total_cost(get_config("alexnet"), 8)
    runs = []
    for _ in range(2):
        fs = FaultSchedule.mtbf(t_round * 3, t_round, 4, seed=7)
        _, done, rep = _chaos_sim(300, faults=fs, retries=5)
        assert _terminal_rids(done) == list(range(300))
        runs.append({(c.rid, c.t_done, c.status, c.replica) for c in done})
    assert runs[0] == runs[1]            # seeded chaos is reproducible


def test_hot_swap_rolls_every_replica_and_drops_nothing():
    # enough rounds that the stream outlasts four per-replica restores
    eng, done, rep = _chaos_sim(400, swap_to=[],
                                swap_at=total_cost(get_config("alexnet"),
                                                   8) * 0.5)
    assert _terminal_rids(done) == list(range(400))
    assert all(c.status == "ok" for c in done), \
        "a graceful rolling swap must never drop a request"
    assert rep.n_swapped == 4 and rep.n_failed == 0
    assert {c.version for c in done} == {0, 1}   # served across the roll
    assert eng._cur_version == 1                 # fleet adopted v1


def test_hot_swap_registration_is_exclusive():
    cfg = get_config("alexnet")
    eng = ServeEngine(cfg, [], batch=8, replicas=2, clock="modeled",
                      execute=False)
    eng.hot_swap([])
    with pytest.raises(RuntimeError, match="already registered"):
        eng.hot_swap([])


def test_engine_pp_busy_accounting_counts_padded_replicas():
    """Satellite: in pp/hybrid rounds every replica's devices compute
    the padded super-batch rows — a replica with zero real requests in
    a round must still be credited busy time (it was, physically)."""
    cfg = get_config("alexnet")
    # 9 requests, batch 8, 2 dp replicas x 2 stages: round 1 fills
    # replica 0's batch and gives replica 1 one request; replica 1's
    # devices still compute the full padded round
    reqs = [_req(i, 0.0, cfg.input_hw, cfg.input_ch) for i in range(9)]
    eng = ServeEngine(cfg, [], batch=8, replicas=2, pp_stages=2,
                      clock="modeled", execute=False)
    done, rep = eng.serve(reqs)
    assert rep.n_done == 9
    assert rep.utilization[0] == pytest.approx(rep.utilization[1])
    assert rep.utilization[1] > 0.99


# ---------------------------------------------------------------------------
# chaos + hot-swap parity on 8 virtual devices (subprocess, real forwards)
# ---------------------------------------------------------------------------

def test_chaos_parity_fail_recover_8dev():
    """ISSUE acceptance: with a replica failed mid-stream and recovered,
    every admitted request completes (or is explicitly failed) and every
    completed prediction matches the unsharded forward."""
    run_in_mesh_subprocess("""
        from repro.configs import get_config
        from repro.models.cnn import init_cnn_params
        from repro.pipeline import (ExecutionSpec, Precision, Serving,
                                    compile_cnn)

        def fwd(p, x, quant='none'):
            # the compiled forward at x's batch (the Pallas path)
            return compile_cnn(cfg, ExecutionSpec(
                precision=Precision(quant=quant),
                serving=Serving(batch=x.shape[0])), p,
                with_engine=False).forward(x)
        from repro.serve import FaultSchedule, Request, ServeEngine
        cfg = get_config('alexnet').smoke()
        key = jax.random.key(3)
        params = init_cnn_params(key, cfg)
        N = 64
        x = jax.random.normal(key, (N, cfg.input_hw, cfg.input_hw,
                                    cfg.input_ch), jnp.float32)
        eng = ServeEngine(cfg, params, batch=4, replicas=4,
                          clock='modeled', retries=2)
        # arrivals spread over ~128 ms so the fleet serves through the
        # whole fail (20 ms) -> recover (40 ms + modeled restore) arc
        reqs = [Request(rid=i, image=np.asarray(x[i]),
                        t_arrival=i * 2e-3) for i in range(N)]
        fs = FaultSchedule.at(20e-3, 40e-3, replica=0)
        done, rep = eng.serve(reqs, faults=fs)
        assert sorted(c.rid for c in done) == list(range(N))
        assert rep.n_failures == 1 and rep.n_recoveries == 1
        assert rep.degraded_rounds > 0
        want = np.asarray(jnp.argmax(
            fwd(params, x), -1))
        for c in done:
            if c.status == 'ok':
                assert c.pred == int(want[c.rid]), (c.rid, c.pred)
    """)


def test_hot_swap_under_load_fp32_to_int8_parity_8dev():
    """ISSUE acceptance: rolling hot-swap fp32 -> calibrated int8 under
    load never drops a request; pre-swap completions match the unsharded
    fp32 forward, post-swap completions are bit-exact vs the unsharded
    int8 forward."""
    run_in_mesh_subprocess("""
        from repro.configs import get_config
        from repro.models.cnn import init_cnn_params
        from repro.pipeline import (ExecutionSpec, Precision, Serving,
                                    compile_cnn)

        def fwd(p, x, quant='none'):
            # the compiled forward at x's batch (the Pallas path)
            return compile_cnn(cfg, ExecutionSpec(
                precision=Precision(quant=quant),
                serving=Serving(batch=x.shape[0])), p,
                with_engine=False).forward(x)
        from repro.quant import calibrate_cnn
        from repro.serve import Request, ServeEngine
        cfg = get_config('alexnet').smoke()
        key = jax.random.key(3)
        params = init_cnn_params(key, cfg)
        N = 64
        x = jax.random.normal(key, (N, cfg.input_hw, cfg.input_hw,
                                    cfg.input_ch), jnp.float32)
        qp = calibrate_cnn(params, x[:8], cfg)
        eng = ServeEngine(cfg, params, batch=4, replicas=4,
                          clock='modeled')
        # arrivals spread over ~320 ms: the rolling swap starts at 20 ms
        # and pays the modeled artifact restore (~5 ms) per replica, so
        # both versions serve real traffic during the roll
        reqs = [Request(rid=i, image=np.asarray(x[i]),
                        t_arrival=i * 5e-3) for i in range(N)]
        v = eng.hot_swap(qp, at=20e-3)
        done, rep = eng.serve(reqs)
        assert sorted(c.rid for c in done) == list(range(N))
        assert all(c.status == 'ok' for c in done)
        assert rep.n_swapped == 4
        versions = {c.version for c in done}
        assert versions == {0, v}, versions
        want_fp = np.asarray(jnp.argmax(
            fwd(params, x), -1))
        want_q = np.asarray(jnp.argmax(
            fwd(qp, x, 'int8'), -1))
        for c in done:
            want = want_fp if c.version == 0 else want_q
            assert c.pred == int(want[c.rid]), (c.rid, c.version)
    """)
