"""Hierarchical + incremental planning: pods=1 bit-identity, stitched-cost
quality vs the flat exact DP, warm replanning vs cold replanning, invariant
replay of both levels, and byte-charged cache eviction."""

import numpy as np
import pytest

from conftest import hypothesis_or_stubs

from repro.analysis.invariants import (
    check_hierarchical_plan,
    check_plan,
)
from repro.api.session import PcclSession, StructureCache
from repro.core import cost_model as cm
from repro.core.cost_model import STRUCTURE_TABLE, StructureTable
from repro.core.pccl import (
    CollectiveRequest,
    default_standard_set,
    plan_collective,
    plan_collective_hierarchical,
    replan_collective,
)
from repro.core.planner import (
    build_structure,
    clear_planner_caches,
    plan,
    plan_hierarchical,
    replan,
    trans_cache_stats,
)
from repro.core.schedules import get_schedule, pod_subschedules
from repro.core.topology import (
    degrade_topology,
    derive_pods,
    induced_topology,
    quotient_topology,
    ring,
)
from repro.runtime import fault as fault_mod

given, settings, st = hypothesis_or_stubs()

HW = cm.H100_DGX
MB = 1 << 20

COLLECTIVES = [
    ("all_reduce", "ring"),
    ("reduce_scatter", "ring"),
    ("all_gather", "ring"),
    ("all_to_all", "direct"),
]

MODES = ["serial", "partial", "overlap"]


def _hw_for(mode):
    if mode == "serial":
        return HW
    r_link = HW.reconfig_delay / 64
    return HW.with_link_reconfig(r_link, overlap=(mode == "overlap"))


# ------------------------------------------------------------- decomposition


def test_derive_pods_partition():
    pods = derive_pods(1024)
    assert len(pods) == 32 and all(len(p) == 32 for p in pods)
    assert sorted(r for p in pods for r in p) == list(range(1024))
    with pytest.raises(ValueError):
        derive_pods(16, pod_size=5)


def test_pod_subschedules_conserves_transfers():
    n = 16
    pods = derive_pods(n, pod_size=4)
    for coll, algo in COLLECTIVES:
        sched = get_schedule(coll, algo, n, float(MB))
        intra, rep, boundary = pod_subschedules(sched, pods)
        for i, rnd in enumerate(sched.rounds):
            want_cross = {}
            want_local = {p: [] for p in range(len(pods))}
            for t in rnd.transfers:
                if t.src == t.dst:
                    continue
                ps, pd = t.src // 4, t.dst // 4
                if ps == pd:
                    want_local[ps].append((t.src % 4, t.dst % 4))
                else:
                    want_cross[(ps, pd)] = want_cross.get((ps, pd), 0) + 1
            assert tuple(sorted(want_cross.items())) == boundary[i]
            for p in range(len(pods)):
                got = sorted(
                    (t.src, t.dst)
                    for t in intra[rep[p]].rounds[i].transfers
                )
                assert got == sorted(want_local[p]), (coll, i, p)


# ---------------------------------------------------------- pods=1 identity


@pytest.mark.parametrize("coll,algo", COLLECTIVES)
def test_single_pod_is_flat_dp_bit_identical(coll, algo):
    n = 16
    g0, std = ring(n), default_standard_set(n)
    sched = get_schedule(coll, algo, n, float(MB))
    flat = plan(g0, std, sched, HW)
    hp = plan_hierarchical(g0, std, sched, HW, pod_size=n)
    assert hp.inter_plan is None
    assert hp.pod_plans[0].plan.steps == flat.steps
    assert hp.pod_plans[0].plan.total_cost == flat.total_cost
    assert hp.total_cost == flat.total_cost


# --------------------------------------------------------- stitched quality


@pytest.mark.parametrize("n", [16, 64, 128])
@pytest.mark.parametrize("mode", MODES)
def test_hierarchical_within_ten_percent_of_flat(n, mode):
    hw = _hw_for(mode)
    g0, std = ring(n), default_standard_set(n)
    for coll, algo in COLLECTIVES:
        sched = get_schedule(coll, algo, n, float(MB))
        flat = plan(g0, std, sched, hw)
        hp = plan_hierarchical(g0, std, sched, hw)
        ratio = hp.total_cost / flat.total_cost
        assert ratio <= 1.1, (n, mode, coll, algo, ratio)


def test_hierarchical_invariant_replay():
    n = 64
    g0, std = ring(n), default_standard_set(n)
    for coll, algo in COLLECTIVES:
        sched = get_schedule(coll, algo, n, float(MB))
        hp = plan_hierarchical(g0, std, sched, HW)
        violations = check_hierarchical_plan(hp, g0, std)
        assert not violations, [str(v) for v in violations]


def test_hierarchical_invariant_attributes_tampering():
    from dataclasses import replace

    n = 16
    g0, std = ring(n), default_standard_set(n)
    sched = get_schedule("all_to_all", "direct", n, float(MB))
    hp = plan_hierarchical(g0, std, sched, HW, pod_size=4)

    bad = replace(hp, total_cost=hp.total_cost * 2)
    assert any(
        v.kind == "total-cost" for v in check_hierarchical_plan(bad, g0, std)
    )
    bad = replace(hp, round_costs=(hp.round_costs[0] * 3,) + hp.round_costs[1:])
    assert any(
        v.kind == "round-cost-stitching"
        for v in check_hierarchical_plan(bad, g0, std)
    )
    bad = replace(hp, boundary=(((0, 1), 99),) * len(hp.boundary))
    kinds = {v.kind for v in check_hierarchical_plan(bad, g0, std)}
    assert "boundary-conservation" in kinds or "boundary-length" in kinds


def test_hierarchical_arbitration_facade():
    n = 64
    g0 = ring(n)
    req = CollectiveRequest("all_reduce", n, float(MB))
    pp = plan_collective_hierarchical(req, g0, HW)
    assert pp.plan.total_cost == pp.cost
    assert pp.plan.final_topology is None


# ------------------------------------------------------------------- replan


def _degraded_inputs(n, failed_edges):
    fe = [e for (u, v) in failed_edges for e in ((u, v), (v, u))]
    g0 = degrade_topology(ring(n), fe)
    std = [degrade_topology(t, fe) for t in default_standard_set(n)]
    return g0, std


@pytest.mark.parametrize("coll,algo", COLLECTIVES)
def test_replan_equals_cold_on_degraded_fabric(coll, algo):
    n = 16
    g0, std = ring(n), default_standard_set(n)
    sched = get_schedule(coll, algo, n, float(MB))
    structure = build_structure(g0, std, sched, HW)
    failed = ((3, 4), (4, 3))
    warm, new_structure = replan(
        g0, std, sched, HW, structure, changed_edges=failed
    )
    d_g0, d_std = _degraded_inputs(n, [(3, 4)])
    cold = plan(d_g0, d_std, sched, HW)
    assert warm.steps == cold.steps
    assert warm.total_cost == cold.total_cost
    assert not check_plan(warm, d_g0, d_std)
    # the refreshed structure warm-replans a second failure too
    warm2, _ = replan(
        d_g0, d_std, sched, HW, new_structure, changed_edges=((8, 9), (9, 8))
    )
    d2_g0 = degrade_topology(d_g0, ((8, 9), (9, 8)))
    d2_std = [degrade_topology(t, ((8, 9), (9, 8))) for t in d_std]
    cold2 = plan(d2_g0, d2_std, sched, HW)
    assert warm2.steps == cold2.steps


@given(
    edge=st.integers(min_value=0, max_value=15),
    coll_idx=st.integers(min_value=0, max_value=3),
)
@settings(max_examples=20, deadline=None)
def test_replan_property_single_link_failures(edge, coll_idx):
    n = 16
    coll, algo = COLLECTIVES[coll_idx]
    g0, std = ring(n), default_standard_set(n)
    sched = get_schedule(coll, algo, n, float(MB))
    structure = build_structure(g0, std, sched, HW)
    failed = ((edge, (edge + 1) % n), ((edge + 1) % n, edge))
    warm, _ = replan(g0, std, sched, HW, structure, changed_edges=failed)
    d_g0, d_std = _degraded_inputs(n, [failed[0]])
    cold = plan(d_g0, d_std, sched, HW)
    assert warm.steps == cold.steps
    assert warm.total_cost == cold.total_cost


def test_replan_without_structure_falls_back_cold():
    n = 16
    g0, std = ring(n), default_standard_set(n)
    sched = get_schedule("all_reduce", "ring", n, float(MB))
    warm, _ = replan(g0, std, sched, HW, None, changed_edges=((0, 1), (1, 0)))
    d_g0, d_std = _degraded_inputs(n, [(0, 1)])
    cold = plan(d_g0, d_std, sched, HW)
    assert warm.steps == cold.steps


def test_replan_collective_facade_matches_cold_arbitration():
    n = 16
    g0, std = ring(n), default_standard_set(n)
    req = CollectiveRequest("all_to_all", n, float(MB), algorithm="direct")
    warm = replan_collective(
        req, g0, HW, standard=std, changed_edges=((5, 6), (6, 5))
    )
    d_g0, d_std = _degraded_inputs(n, [(5, 6)])
    cold = plan_collective(req, d_g0, HW, standard=d_std)
    assert warm.cost == cold.cost
    assert warm.plan.steps == cold.plan.steps


# --------------------------------------------------------------- session API


def test_session_replan_is_warm_and_permanent():
    n = 128
    clear_planner_caches()
    STRUCTURE_TABLE.clear()
    s = PcclSession(HW, g0=ring(n), thread_fabric=False)
    s.plan("all_to_all", float(MB), algorithm="direct")
    cold_routes = STRUCTURE_TABLE.stats.routing_calls
    assert cold_routes > 0

    before = STRUCTURE_TABLE.stats.routing_calls
    rp = s.replan(
        "all_to_all", float(MB), algorithm="direct", failed_edges=[(0, 1)]
    )
    warm_routes = STRUCTURE_TABLE.stats.routing_calls - before
    # warm path re-routes only states the dead link touched: a small
    # fraction of the cold structure phase (routing calls are the
    # deterministic proxy for the planner's wall-clock work)
    assert warm_routes <= 0.2 * cold_routes, (warm_routes, cold_routes)

    # permanence: fabric, initial fabric and standards all lost the link
    for topo in (s.fabric(n), s.initial_fabric(n), *s.standard_set(n)):
        assert (0, 1) not in topo.edges and (1, 0) not in topo.edges

    # the warm plan equals a cold plan of the degraded scenario
    d_g0, d_std = _degraded_inputs(n, [(0, 1)])
    s2 = PcclSession(HW, g0=d_g0, standard_set=d_std, thread_fabric=False)
    cold = s2.plan("all_to_all", float(MB), algorithm="direct")
    assert rp.plan.steps == cold.plan.steps
    assert rp.cost == cold.cost
    assert not check_plan(rp.plan, d_g0, d_std)


def test_session_replan_via_fault_event():
    n = 16
    s = PcclSession(HW, g0=ring(n), thread_fabric=False)
    s.plan("all_reduce", float(MB))
    ev = fault_mod.LinkFailure(edges=((2, 3),))
    p = fault_mod.replan_after_failure(s, ev, "all_reduce", float(MB), n=n)
    assert (2, 3) not in s.fabric(n).edges
    assert p.cost > 0
    with pytest.raises(ValueError):
        fault_mod.LinkFailure()


def test_session_plan_hierarchical_cached():
    s = PcclSession(HW, g0=ring(64), thread_fabric=False)
    hp = s.plan_hierarchical("all_reduce", float(MB))
    assert s.plan_hierarchical("all_reduce", float(MB)) is hp
    assert hp.plan.final_topology is None
    # no fabric threading from hierarchical plans
    assert s.fabric(64).edges == ring(64).edges


def test_communicator_replan_forwards():
    s = PcclSession(HW, g0=ring(16), thread_fabric=False)
    comm = s.communicator("x", 16, algorithm="paper_default")
    p = comm.replan("all_reduce", float(MB), failed_edges=[(1, 2)])
    assert (1, 2) not in s.fabric(16).edges
    assert p.cost > 0


# ----------------------------------------------------------- byte accounting


def test_structure_table_byte_eviction():
    t = StructureTable(max_entries=1000, max_bytes=8_000)
    topo = ring(8)
    for i in range(200):
        key = frozenset({((i, (i + 1) % 1000), 1)})
        t.store(topo, key, (1, 1, True))
    st_ = t.stats
    assert st_.bytes <= 8_000
    assert st_.evictions > 0
    assert st_.size >= 1


def test_trans_cache_reports_and_bounds_bytes():
    clear_planner_caches()
    n = 16
    g0, std = ring(n), default_standard_set(n)
    sched = get_schedule("all_reduce", "ring", n, float(MB))
    plan(g0, std, sched, HW)
    entries, nbytes = trans_cache_stats()
    assert entries >= 1 and nbytes > 0


def test_session_structure_cache_byte_eviction():
    c = StructureCache(max_entries=100, max_bytes=1)
    n = 16
    g0, std = ring(n), default_standard_set(n)
    s1 = build_structure(g0, std, get_schedule("all_reduce", "ring", n, 1.0), HW)
    s2 = build_structure(
        g0, std, get_schedule("all_gather", "ring", n, 1.0), HW
    )
    c.store(("a",), {"ring": s1})
    assert c.stats.size == 1  # a single oversized bundle still caches
    c.store(("b",), {"ring": s2})
    assert c.stats.size == 1 and c.stats.evictions >= 1
    assert c.stats.bytes <= max(c._charge({"ring": s1}), c._charge({"ring": s2}))
    # re-storing a mutated bundle replaces its charge instead of accumulating
    c.clear()
    bundle = {"ring": s1}
    c.store(("a",), bundle)
    b1 = c.stats.bytes
    bundle["ring2"] = s2
    c.store(("a",), bundle)
    assert c.stats.bytes > b1
    c.store(("a",), bundle)
    assert c.stats.bytes == c._charge(bundle)


def test_session_structure_stats_totals():
    s = PcclSession(HW, g0=ring(16), thread_fabric=False)
    s.plan("all_reduce", float(MB))
    st_ = s.structure_stats
    assert st_.bytes > 0
    assert st_.table_bytes > 0 and st_.table_entries > 0
    assert st_.trans_bytes > 0 and st_.trans_entries > 0
    assert st_.misses >= 1  # CacheStats interface intact


def test_build_structure_prunes_dead_states():
    n = 8
    fe = [(0, 1), (1, 0), (0, 7), (7, 0)]  # isolate rank 0 in the ring
    g0 = degrade_topology(ring(n), fe)
    std = [degrade_topology(t, fe) for t in default_standard_set(n)]
    sched = get_schedule("all_reduce", "ring", n, float(MB))
    structure = build_structure(g0, std, sched, HW)
    # disconnected standards are pruned but recorded for reuse validation
    names = {s.topo.edges for s in structure.states}
    for pruned in structure.pruned_standard:
        assert pruned not in names
    # healthy fabric: nothing pruned, bit-identical planning
    healthy = build_structure(ring(n), default_standard_set(n), sched, HW)
    assert not healthy.pruned_standard
