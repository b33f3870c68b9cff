"""bench/scopes.py and bench/gaps.py on traces worked out by hand."""

import json
from pathlib import Path

import pytest

from bench import harness
from bench import scopes as sc
from bench import trace_reduce as tr

ROOT = Path(__file__).resolve().parents[2]
RECORDED = Path(__file__).parent / "data"
FWD = "jit(step)/jvp(train_forward)/dot_general"
REMAT = "jit(step)/transpose(jvp(train_forward))/while/body/checkpoint/rematted_computation/dot"
BWD = "jit(step)/transpose(jvp(train_forward))/dot_general"
GATHER = "jit(step)/train_grad_sync/pccl_all_reduce_ring/group0/while/body/gather"
PERMUTE = "jit(step)/train_grad_sync/pccl_all_reduce_ring/group0/while/body/ppermute"
OPT = "jit(step)/train_optimizer/mul"

# Device 0 (ns, window 0-100): forward fusion.1 0-10; backward (remat)
# fusion.2 10-30; the engine's gather fusion.3 30-40 and its permute,
# start 40-41, done 55-56, on the async line 40-56; backward fusion.5
# 45-50 under the permute; optimizer fusion.6 60-70; fusion.7 70-75 with no
# op_name; a forward while loop 0-80 (a container); forward fusion.9
# 95-110, clipped to 95-100.  Device 1: forward fusion.1 0-20.
DEVICE0 = [("%while.8 = (s32[]) while((s32[]) %t), body=%b", 0, 80, FWD),
           ("fusion.1", 0, 10, FWD), ("fusion.2", 10, 20, REMAT), ("fusion.3", 30, 10, GATHER),
           ("collective-permute-start.4", 40, 1, PERMUTE), ("fusion.5", 45, 5, BWD),
           ("collective-permute-done.4", 55, 1, PERMUTE), ("fusion.6", 60, 10, OPT),
           ("fusion.7", 70, 5, None), ("fusion.9", 95, 15, FWD)]
ASYNC0 = [("collective-permute-start.4", 40, 16, PERMUTE)]
DEVICE1 = [("fusion.1", 0, 20, FWD)]
HOST = [("bench.window", 0, 100, None), ("bench.step", 0, 2, None), ("bench.block", 2, 98, None)]


def _plane(pid, name, lines, stat="tf_op"):
    """A plane in text proto; each event's op_name a ``stat`` of its metadata,
    given by reference to the stat metadata for the forward scope, as the
    profiler interns strings, and by value otherwise."""
    names = {n: op for events in lines.values() for n, _, _, op in events}
    ids = {n: i + 1 for i, n in enumerate(sorted(names))}
    text = f'planes {{\n  id: {pid}\n  name: "{name}"\n'
    for lid, (line, events) in enumerate(lines.items()):
        text += f'  lines {{ id: {lid + 1} name: "{line}" timestamp_ns: 0\n'
        text += "".join(f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                        f"duration_ps: {d * 1000} }}\n" for n, s, d, _ in events)
        text += "  }\n"
    for n, op in names.items():
        stats = ""
        if op == FWD:
            stats = "stats { metadata_id: 1 ref_value: 2 }"
        elif op:
            stats = f"stats {{ metadata_id: 1 str_value: {json.dumps(op)} }}"
        text += (f"  event_metadata {{ key: {ids[n]} value {{ id: {ids[n]} "
                 f"name: {json.dumps(n)} {stats} }} }}\n")
    text += (f'  stat_metadata {{ key: 1 value {{ id: 1 name: "{stat}" }} }}\n'
             f'  stat_metadata {{ key: 2 value {{ id: 2 name: {json.dumps(FWD)} }} }}\n')
    return text + "}\n"


def _write(path, stat="tf_op"):
    from jax.profiler import ProfileData

    text = (_plane(1, "/device:TPU:0", {"XLA Ops": DEVICE0, "Async XLA Ops": ASYNC0}, stat)
            + _plane(2, "/device:TPU:1", {"XLA Ops": DEVICE1}, stat)
            + _plane(3, "/host:CPU", {"python": HOST}, stat))
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    return _write(tmp_path_factory.mktemp("scopes") / "hand.xplane.pb")


def test_op_names_read_from_event_metadata(trace):
    names = sc.read_op_names(trace)
    assert names[0]["fusion.1"] == FWD  # by reference
    assert names[0]["fusion.3"] == GATHER  # by value
    assert "fusion.7" not in names[0]
    assert names[1] == {"fusion.1": FWD}


def test_phases():
    assert sc.phase(FWD) == "forward"
    assert sc.phase(REMAT) == sc.phase(BWD) == "backward"
    assert sc.phase(OPT) == "optimizer"
    assert sc.phase("jit(step)/sin") is None
    assert sc.in_engine(GATHER) and sc.in_engine("pccl_all_gather_rhd/group2/add")
    assert not sc.in_engine("jit(step)/xpccl_x") and not sc.in_engine(FWD)


def test_scope_ns_worked_by_hand(trace):
    s = sc.read_scopes(trace, (0, 100))
    assert s.scope_ns[0] == {"forward": 15, "backward": 25, "optimizer": 10,
                             "engine": 26, "engine_exposed": 21}
    assert s.scope_ns[1] == {"forward": 20, "backward": 0, "optimizer": 0,
                             "engine": 0, "engine_exposed": 0}
    assert s.mean("forward") == 17.5


def test_round_group_time_worked_by_hand(trace):
    # every engine op of the hand trace is in round group 0
    assert sc.group_ns(trace, (0, 100)) == {0: {"pccl_all_reduce_ring/group0": 26}, 1: {}}


def test_engine_exposed_is_engine_less_every_other_op():
    # engine 0-30; other ops 5-10 and 20-40; the async permute 12-18 is
    # the engine's own and hides nothing of it
    ops = {0: [(GATHER, 0, 30), (FWD, 5, 10), (OPT, 20, 40), (PERMUTE, 12, 18)]}
    s = sc.summarize(ops, (0, 100))
    assert s.scope_ns[0]["engine"] == 30
    assert s.scope_ns[0]["engine_exposed"] == 5 + 10


def _as_run_cell(summary, path, fn):
    """Call ``fn`` from a function named ``run_cell`` of a file ``run.py``
    whose locals hold ``summary`` and ``path``, as bench/run.py's does."""
    scope = {}
    exec(compile("def run_cell(summary, path, fn):\n    return fn()\n",
                 str(ROOT / "bench" / "run.py"), "exec"), scope)
    return scope["run_cell"](summary, path, fn)


def test_metric_readers_find_the_window_trace(trace):
    summary = tr.read_trace(trace)
    run = {"steps": 2, "chips": 2, "flops_per_step": 1.0, "peak_flops": 1.0}
    names = ("forward_ms", "backward_ms", "optimizer_ms", "engine_ms", "engine_exposed_ms")
    read = _as_run_cell(summary, trace, lambda: {
        m: harness.metric_reader(m).read(summary, run) for m in names})
    assert read == pytest.approx({"forward_ms": 17.5e-6 / 2, "backward_ms": 12.5e-6 / 2,
                                  "optimizer_ms": 5e-6 / 2, "engine_ms": 13e-6 / 2,
                                  "engine_exposed_ms": 10.5e-6 / 2})
    # outside a run_cell frame there is no trace to read
    assert harness.metric_reader("forward_ms").read(summary, run) is None


def test_trace_without_op_names_reads_nothing(tmp_path):
    """A program without scopes, or a profiler that keeps no op_name:
    every scope metric is missing, and nothing raises."""
    path = _write(tmp_path / "other_stat.xplane.pb", stat="long_name")
    summary = tr.read_trace(path)
    assert sc.read_scopes(path, summary.window) is None
    run = {"steps": 1, "chips": 2, "flops_per_step": 1.0, "peak_flops": 1.0}
    assert _as_run_cell(summary, path, lambda: sc.ms_per_step(summary, run, "forward")) is None


def test_recorded_one_chip_window_unchanged_and_consistent():
    """The recorded window of a program without scopes: no scope time, and
    the scope reduction counts exactly the ops whose union is busy."""
    from jax.profiler import ProfileData

    path = str(RECORDED / "bert1chip_window.xplane.pb")
    s = tr.read_trace(path)
    assert sc.read_scopes(path, s.window) is None
    d = s.devices[0]
    assert (d.busy_ns, d.compute_ns, d.collective_ns, d.exposed_collective_ns) == (
        11_372_047, 11_372_047, 0, 0)
    assert max(d.gaps, key=lambda g: g[1] - g[0]) == (224_658_486, 233_285_917)
    lines = {ln.name: [(e.name, e.start_ns, e.end_ns) for e in ln.events]
             for p in ProfileData.from_file(path).planes if p.name == "/device:TPU:0"
             for ln in p.lines}
    ops = sc.scoped_ops(lines["XLA Ops"], lines.get("Async XLA Ops", []), {})
    every_op_forward = sc.summarize({0: [(FWD, s_, e_) for _, s_, e_ in ops]}, s.window)
    assert every_op_forward.scope_ns[0]["forward"] == d.busy_ns


def test_recorded_scoped_window():
    """52.5 ms of a bert.1chip traced run on the TPU v5e (with the scopes,
    seed 3141592653), from the end of one step's backward pass through its
    AdamW update, the idle gap, and the next step's weight casts into its
    forward pass; each op keeps the ``tf_op`` stat of its metadata."""
    from jax.profiler import ProfileData

    path = str(RECORDED / "bert1chip_scoped_window.xplane.pb")
    s = tr.read_trace(path)
    assert s.window == (190_132_863, 242_641_303)
    d = s.devices[0]
    assert d.busy_ns == d.compute_ns == 44_354_227
    scopes = sc.read_scopes(path, s.window)
    assert scopes.scope_ns[0] == {"forward": 2_160_415, "backward": 2_987_362,
                                  "optimizer": 33_686_946, "engine": 0, "engine_exposed": 0}
    # what no scope names, counted op by op: the next step's weight casts,
    # which XLA hoists out of both loops and merges, dropping their op_names
    names = sc.read_op_names(path)[0]
    lines = {ln.name: ln.events for p in ProfileData.from_file(path).planes
             if p.name == "/device:TPU:0" for ln in p.lines}
    unscoped = [e for e in lines["XLA Ops"] if tr.parse_op(e.name)[1] not in tr.CONTAINERS
                and sc.phase(names.get(e.name, "")) is None]
    clipped = sum(min(e.end_ns, s.window[1]) - max(e.start_ns, s.window[0]) for e in unscoped)
    assert sum(scopes.scope_ns[0][k] for k in ("forward", "backward", "optimizer")) + clipped == (
        d.busy_ns)
    assert {tr.parse_op(e.name)[1] for e in unscoped if e.duration_ns > 5e5} == {"convert"}


def test_recorded_four_chip_scopes():
    """bench/tests/record_scoped_trace.py on four TPU v5e chips: per step a
    product under train_forward, one ppermute round under
    pccl_all_reduce_ring/group0, an update under train_optimizer, and a
    psum under none."""
    from jax.profiler import ProfileData

    path = str(RECORDED / "scoped_4chip.xplane.pb")
    s = tr.read_trace(path)
    scopes = sc.read_scopes(path, s.window)
    assert sorted(scopes.scope_ns) == [0, 1, 2, 3]
    assert scopes.scope_ns[0] == {"forward": 22_965, "backward": 0, "optimizer": 3_166,
                                  "engine": 96_984, "engine_exposed": 96_984}
    names = sc.read_op_names(path)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        dev = int(plane.name.rsplit(":", 1)[1])
        permutes = [names[dev].get(e.name, "") for ln in plane.lines if ln.name == "XLA Ops"
                    for e in ln.events if "collective-permute" in tr.parse_op(e.name)[1]]
        assert permutes and all(sc.in_engine(n) for n in permutes)
        # the engine holds every collective but the psum, which no scope names
        psum = tr.total(tr.clip([(e.start_ns, e.end_ns) for ln in plane.lines
                                 if ln.name == "XLA Ops" for e in ln.events
                                 if tr.parse_op(e.name)[1] == "all-reduce"], *s.window))
        assert scopes.scope_ns[dev]["engine"] >= s.devices[dev].collective_ns - psum > 0
    groups = sc.group_ns(path, s.window)
    assert {d: g["pccl_all_reduce_ring/group0"] for d, g in groups.items()} == {
        d: v["engine"] for d, v in scopes.scope_ns.items()}


# ------------------------------------------------------------------ gaps

def test_gaps_offset_and_labels(tmp_path):
    from jax.profiler import ProfileData

    from bench import gaps

    # each module starts 3 ns after the bench.step span that dispatched it
    dev = {"XLA Modules": [("jit_step(1)", 3, 20, None), ("jit_step(1)", 53, 20, None)],
           "XLA Ops": [("fusion.1", 3, 20, None), ("fusion.1", 53, 10, None),
                       ("fusion.2", 68, 5, None)]}
    host = {"python": [("bench.window", 0, 100, None), ("bench.step", 0, 1, None),
                       ("bench.block", 1, 49, None), ("bench.step", 50, 1, None),
                       ("bench.block", 51, 49, None)],
            "runtime": [("TransferFromDevice", 20, 28, None), ("GcCollect", 70, 20, None)]}
    text = (_plane(1, "/device:TPU:0", dev) + _plane(2, "/host:CPU", host))
    path = tmp_path / "gaps.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    summary = tr.read_trace(str(path))
    host_spans, modules = gaps.read_spans(str(path))
    assert gaps.clock_offset(modules[0], host_spans, summary.window) == 3
    rows = gaps.explain(str(path))
    # gaps 23-53 (between steps: the host reads the loss, then
    # dispatches), 73-100 (after the last op, while the host collects
    # garbage), 63-68 (inside the second module: dropped events), 0-3
    assert [round(r["start_ms"] * 1e6) for r in rows] == [23, 73, 63, 0]
    assert [r["ms"] * 1e6 for r in rows] == pytest.approx([30, 27, 5, 3])
    by_start = {round(r["start_ms"] * 1e6): r for r in rows}
    assert by_start[23]["label"] == "bench.block/TransferFromDevice"
    assert by_start[73]["label"] == "bench.block/GcCollect"
    assert by_start[63]["in_module_ms"] * 1e6 == pytest.approx(5)
    assert by_start[23]["in_module_ms"] == 0


def test_recorded_gap_between_steps():
    """The recorded window's one long gap lies between two steps' modules,
    on a device clock 0.44 ms behind the host's."""
    from bench import gaps

    path = str(RECORDED / "bert1chip_scoped_window.xplane.pb")
    host, modules = gaps.read_spans(path)
    assert gaps.clock_offset(modules[0], host, tr.read_trace(path).window) == pytest.approx(
        -441_116)
    (row,) = gaps.explain(path, top=1)
    assert row["label"] == "bench.block"
    assert row["ms"] == pytest.approx(8.135396)
    assert row["in_module_ms"] < 0.01
