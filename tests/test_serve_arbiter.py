"""Online fabric arbiter (repro.serve.arbiter): admission, SLA shedding,
preemption, fault survival — plus the sectioned EngineConfig validation.

Everything runs in virtual time on the sim/planning path: no devices, no
wall clocks, so every scenario is deterministic."""

import math
import random

import pytest

from repro.api import PcclSession
from repro.core import cost_model as cm
from repro.core import topology as T
from repro.runtime.fault import LinkFailure, fail_link
from repro.serve.arbiter import (
    DECODE,
    KV_MIGRATION,
    PREFILL,
    SHED_DEADLINE,
    SHED_QUEUE_FULL,
    ArbiterConfig,
    FabricArbiter,
    SlaTarget,
)
from repro.serve.engine import (
    EngineConfig,
    FabricSection,
    ModelSection,
    RuntimeSection,
)

N = 16


def make_arbiter(**cfg_kwargs) -> FabricArbiter:
    session = PcclSession(cm.H100_DGX, g0=T.ring(N))
    return FabricArbiter(
        session, tp=4, dp=4, d_model=512, cfg=ArbiterConfig(**cfg_kwargs)
    )


# ------------------------------------------------------------- admission
def test_empty_queue_tick_is_noop():
    arb = make_arbiter()
    out = arb.tick()
    assert out["executed"] == 0 and out["round_s"] == 0.0
    assert arb.clock == 0.0 and arb.rounds == 0
    # an idle tick with a future `now` advances the clock but plans nothing
    arb.tick(now=1.5)
    assert arb.clock == 1.5 and arb.rounds == 0
    assert arb.report()["utilization"] == 0.0


def test_all_deadlines_expired_batch_sheds_everything():
    arb = make_arbiter()
    for _ in range(3):
        arb.submit(arb.make_request(DECODE))
    arb.submit(arb.make_request(PREFILL, context_len=256))
    # jump virtual time past every deadline: the whole batch is shed with
    # attributable outcomes, nothing is planned
    out = arb.tick(now=10.0)
    assert out["executed"] == 0 and arb.queue_depth == 0
    shed = [o for o in arb.outcomes if o.status == "shed"]
    assert len(shed) == 4
    assert all(o.reason == SHED_DEADLINE for o in shed)
    assert arb.report()["shed_reasons"][SHED_DEADLINE] == 4


def test_burst_beyond_queue_bound_sheds_with_accounting():
    arb = make_arbiter(queue_bound=4)
    accepted = sum(arb.submit(arb.make_request(DECODE)) for _ in range(10))
    assert accepted == 4 and arb.queue_depth == 4
    rep = arb.report()
    assert rep["shed_reasons"][SHED_QUEUE_FULL] == 6
    assert rep["admitted"] == 4
    # every submission got exactly one outcome or a queue slot
    assert len(arb.outcomes) + arb.queue_depth == 10
    # shedding is deadline-aware: a tighter-deadline newcomer evicts the
    # slackest incumbent instead of being dropped itself
    kv = arb.make_request(KV_MIGRATION, context_len=64)   # slack deadline
    arb2 = make_arbiter(queue_bound=1)
    assert arb2.submit(kv)
    urgent = arb2.make_request(DECODE)                    # tight deadline
    assert arb2.submit(urgent)
    assert arb2.queue_depth == 1
    evicted = [o for o in arb2.outcomes if o.status == "shed"]
    assert [o.rid for o in evicted] == [kv.rid]
    assert evicted[0].reason == SHED_QUEUE_FULL


def test_request_validation():
    arb = make_arbiter()
    with pytest.raises(ValueError, match="kind"):
        arb.make_request("training")
    with pytest.raises(ValueError, match="context_len"):
        arb.make_request(PREFILL, context_len=0)
    with pytest.raises(ValueError, match="tp >= 2"):
        FabricArbiter(PcclSession(cm.H100_DGX), tp=1, dp=4, d_model=64)
    with pytest.raises(ValueError, match="queue_bound"):
        ArbiterConfig(queue_bound=0)


# ------------------------------------------------------------ preemption
def test_preemption_during_fused_dispatch_falls_back():
    """A decode deadline the joint round cannot meet makes decode steal the
    fabric: prefill is preempted back to the queue, the in-flight fused
    dispatch falls back (counted), and the preempted request still
    completes later with its preemption recorded."""
    arb = make_arbiter(
        sla=SlaTarget(prefill_s=10.0, decode_s=1e-7, kv_migration_s=10.0),
        fused_dispatch=True,
    )
    pf = arb.make_request(PREFILL, context_len=512)
    arb.submit(pf)
    arb.submit(arb.make_request(DECODE))
    out = arb.tick()
    assert out["preempted"] is True
    assert out["kinds"] == (DECODE,)
    assert arb.preemptions == 1 and arb.fused_fallbacks == 1
    # prefill went back to the queue, not to an outcome
    assert arb.queue_depth == 1
    done = {o.rid for o in arb.outcomes if o.status == "completed"}
    assert pf.rid not in done
    # next tick (decode pressure gone) completes the preempted prefill
    out2 = arb.tick()
    assert out2["executed"] == 1 and out2["preempted"] is False
    pf_out = [o for o in arb.outcomes if o.rid == pf.rid]
    assert pf_out and pf_out[0].status == "completed"
    assert pf_out[0].preemptions == 1


def test_no_preemption_when_disabled_or_sla_met():
    arb = make_arbiter(preemption=False,
                       sla=SlaTarget(10.0, 1e-7, 10.0))
    arb.submit(arb.make_request(PREFILL, context_len=512))
    arb.submit(arb.make_request(DECODE))
    out = arb.tick()
    assert out["preempted"] is False and out["executed"] == 2
    arb2 = make_arbiter()  # default SLA comfortably above one round
    arb2.submit(arb2.make_request(PREFILL, context_len=512))
    arb2.submit(arb2.make_request(DECODE))
    assert arb2.tick()["preempted"] is False


# ------------------------------------------------------- joint planning
def test_mixed_round_plans_jointly_with_offsets():
    arb = make_arbiter(prefill_lead_rounds=2)
    for _ in range(3):
        arb.submit(arb.make_request(DECODE))
    arb.submit(arb.make_request(PREFILL, context_len=300))
    arb.submit(arb.make_request(KV_MIGRATION, context_len=700))
    out = arb.tick()
    assert out["executed"] == 5
    assert out["kinds"] == (PREFILL, DECODE, KV_MIGRATION)
    assert out["joint_s"] <= out["sequential_s"] * (1 + 1e-12)
    lat = [o.latency_s for o in arb.outcomes if o.status == "completed"]
    assert all(not math.isnan(x) and x > 0 for x in lat)


def test_repeat_shapes_hit_plan_cache():
    """Once the threaded fabric reaches its fixed point, a repeated
    (collective, n, nbytes) admission shape plans in O(1) — pure cache
    hits, the tentpole's serving-loop fast path."""
    arb = make_arbiter()
    for _ in range(3):
        for _ in range(3):
            arb.submit(arb.make_request(DECODE))
        arb.submit(arb.make_request(PREFILL, context_len=300))
        arb.tick()
    hits0, misses0 = arb.session.stats.hits, arb.session.stats.misses
    for _ in range(3):
        arb.submit(arb.make_request(DECODE))
    arb.submit(arb.make_request(PREFILL, context_len=300))
    arb.tick()
    assert arb.session.stats.hits == hits0 + 1
    assert arb.session.stats.misses == misses0


# ------------------------------------------------------------- fault path
def test_replan_under_load_after_fail_link():
    """A mid-stream link failure warm-replans the session; the arbiter
    keeps serving on the degraded fabric with no cold restart."""
    arb = make_arbiter()
    for _ in range(2):
        arb.submit(arb.make_request(DECODE))
    arb.tick()
    failure = fail_link(arb, 0, 1)
    assert isinstance(failure, LinkFailure) and arb.faults == 1
    # the session's fabric permanently lost the link, both directions
    edges = arb.session.fabric(N).edges
    assert (0, 1) not in edges and (1, 0) not in edges
    for _ in range(2):
        arb.submit(arb.make_request(DECODE))
    arb.submit(arb.make_request(PREFILL, context_len=128))
    out = arb.tick()
    assert out["executed"] == 3
    assert out["joint_s"] <= out["sequential_s"] * (1 + 1e-12)


def test_fail_link_on_bare_session():
    sess = PcclSession(cm.H100_DGX, g0=T.ring(8))
    fail_link(sess, 2, 3)
    edges = sess.fabric(8).edges
    assert (2, 3) not in edges and (3, 2) not in edges


# ---------------------------------------------------------- overload guard
# A seeded serving stream at twice the fabric's probed capacity: decode
# waves, prefill bursts over mixed contexts and KV-cache migrations, all
# priced in virtual time by one cost model, so every figure is exact.
TP = DP = 4
OVERLOAD_D_MODEL = 1024
CONTEXTS = (128, 512, 2048)
QUEUE_BOUND = 64
MAX_BATCH = 8
SEED = 20260807


def _session() -> PcclSession:
    return PcclSession(cm.H100_DGX, g0=T.ring(N))


def _mix(rng: random.Random):
    r = rng.random()
    if r < 0.70:
        return DECODE, 1
    if r < 0.92:
        return PREFILL, rng.choice(CONTEXTS)
    return KV_MIGRATION, rng.choice(CONTEXTS)


def _probe():
    """``(round_s, capacity_rps)`` of the saturated fabric: drain a backlog
    of the stream's own mix with no deadlines, no shedding."""
    rng = random.Random(SEED ^ 0xBEEF)
    arb = FabricArbiter(
        _session(), tp=TP, dp=DP, d_model=OVERLOAD_D_MODEL,
        cfg=ArbiterConfig(queue_bound=10_000, max_batch=MAX_BATCH,
                          sla=SlaTarget(1e6, 1e6, 1e6), preemption=False),
    )
    for _ in range(120):
        arb.submit(arb.make_request(*_mix(rng)))
    while arb.queue_depth:
        arb.tick()
    rep = arb.report()
    return rep["clock_s"] / rep["rounds"], rep["completed"] / rep["clock_s"]


def _sla(round_s: float) -> SlaTarget:
    """Decode within a few rounds, prefill within a batch drain, KV slack."""
    return SlaTarget(prefill_s=12.0 * round_s, decode_s=3.0 * round_s,
                     kv_migration_s=40.0 * round_s)


def _poisson_trace(n_events: int, rate: float, seed: int):
    rng = random.Random(seed)
    t, events = 0.0, []
    for _ in range(n_events):
        t += rng.expovariate(rate)
        events.append((t, *_mix(rng)))
    return events


def _bursty_trace(n_events: int, rate: float, seed: int):
    """Same mean rate in phases of 20 arrivals: 4x hot, 0.4x cold."""
    rng = random.Random(seed)
    t, events = 0.0, []
    for i in range(n_events):
        hot = (i // 20) % 2 == 0
        t += rng.expovariate(rate * (4.0 if hot else 0.4))
        events.append((t, *_mix(rng)))
    return events


def _run_trace(events, arb: FabricArbiter) -> FabricArbiter:
    """Drain the rounds due before each arrival, idle across gaps, then
    drain the rest."""
    for t, kind, ctx in events:
        while arb.queue_depth and arb.clock < t:
            arb.tick()
        if arb.clock < t:
            arb.tick(now=t)
        arb.submit(arb.make_request(kind, ctx, arrival_s=t))
    while arb.queue_depth:
        arb.tick()
    return arb


def _pct(lats, p: float) -> float:
    lats = sorted(lats)
    return lats[min(len(lats) - 1, int(p * len(lats)))] if lats else float("nan")


def _token_latencies(arb: FabricArbiter):
    return [o.latency_s for o in arb.outcomes
            if o.status == "completed" and o.kind == DECODE]


@pytest.mark.parametrize("trace", [_poisson_trace, _bursty_trace],
                         ids=["poisson", "bursty"])
def test_overload_sheds_and_bounds_admitted_p99(trace):
    """At 2x the probed capacity the arbiter sheds, the admitted decode
    p99 stays within twice the slowest SLA target, and a replay of the
    same point gives the same figures."""
    round_s, capacity = _probe()
    sla = _sla(round_s)
    events = trace(150, 2.0 * capacity, SEED)

    def serve():
        arb = FabricArbiter(
            _session(), tp=TP, dp=DP, d_model=OVERLOAD_D_MODEL,
            cfg=ArbiterConfig(queue_bound=QUEUE_BOUND, max_batch=MAX_BATCH,
                              sla=sla),
        )
        rep = _run_trace(events, arb).report()
        return _pct(_token_latencies(arb), 0.99), rep["shed_rate"], rep["completed"]

    p99, shed_rate, completed = serve()
    assert shed_rate > 0.0
    assert p99 <= 2.0 * max(sla.prefill_s, sla.decode_s, sla.kv_migration_s)
    assert serve() == (p99, shed_rate, completed)


# ------------------------------------------------------- EngineConfig split
def test_engine_config_flat_kwargs_back_compat():
    c = EngineConfig(batch_size=2, max_len=32, tp=4, dp=4)
    assert (c.batch_size, c.max_len, c.tp, c.dp, c.greedy) == (2, 32, 4, 4, True)
    assert c.runtime == RuntimeSection(2, 32)
    assert c.fabric == FabricSection(tp=4, dp=4)
    assert c.fabric.n == 16
    assert EngineConfig() == EngineConfig()  # defaults are stable


def test_engine_config_sections_equal_flat():
    flat = EngineConfig(batch_size=2, max_len=32, greedy=False, tp=2, dp=2)
    sectioned = EngineConfig(
        model=ModelSection(greedy=False),
        runtime=RuntimeSection(batch_size=2, max_len=32),
        fabric=FabricSection(tp=2, dp=2),
    )
    assert flat == sectioned and hash(flat) == hash(sectioned)


def test_engine_config_validation_is_attributable():
    with pytest.raises(ValueError, match="batch_size"):
        EngineConfig(batch_size=0)
    with pytest.raises(ValueError, match="KV slots"):
        EngineConfig(batch_size=64, max_len=32)
    with pytest.raises(ValueError, match="mesh_n=16"):
        FabricSection(tp=4, dp=2, mesh_n=16)
    assert FabricSection(tp=4, dp=4, mesh_n=16).n == 16
    with pytest.raises(ValueError, match="not both"):
        EngineConfig(tp=2, fabric=FabricSection(tp=2))
    with pytest.raises(ValueError, match="not both"):
        EngineConfig(greedy=False, model=ModelSection())
    with pytest.raises(ValueError, match="not both"):
        EngineConfig(max_len=64, runtime=RuntimeSection())
