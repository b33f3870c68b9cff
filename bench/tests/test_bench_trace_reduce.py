"""trace_reduce on traces whose numbers are worked out by hand."""

import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

# Device 0 (ns): fusion.1 10-30; collective-permute-start/done.1 25-27 and
# 40-42, on the async line one collective 25-42; fusion.2 35-50;
# all-reduce.2 60-70; copy.3 95-110, clipped to the window 0-100.
# Device 1, named by HLO text: a while loop 0-100 (a container) around
# fusion.9 0-100.
# Host: bench.window 0-100; bench.step 0-12, bench.block 12-60,
# bench.next_batch 60-62, bench.step 62-80, bench.block 80-100.
DEVICE0 = [("fusion.1", 10, 20), ("collective-permute-start.1", 25, 2),
           ("fusion.2", 35, 15), ("collective-permute-done.1", 40, 2),
           ("all-reduce.2", 60, 10), ("copy.3", 95, 15)]
ASYNC0 = [("collective-permute-start.1", 25, 17)]
DEVICE1 = [("%while.5 = (s32[]{:T(128)}, f32[8]{0}) while((s32[]{:T(128)}, f32[8]{0}) %t), "
            "condition=%cond, body=%body", 0, 100),
           ("%fusion.9 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(f32[8]{0} %a), kind=kLoop", 0, 100)]
HOST = [("bench.window", 0, 100), ("bench.step", 0, 12), ("bench.block", 12, 48),
        ("bench.next_batch", 60, 2), ("bench.step", 62, 18), ("bench.block", 80, 20)]


def _plane(pid, name, lines):
    names = sorted({n for events in lines.values() for n, _, _ in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    text = f'planes {{\n  id: {pid}\n  name: "{name}"\n'
    for lid, (line, events) in enumerate(lines.items()):
        evs = "\n".join(f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                        f"duration_ps: {d * 1000} }}" for n, s, d in events)
        text += (f'  lines {{\n    id: {lid + 1}\n    name: "{line}"\n    timestamp_ns: 0\n'
                 f'{evs}\n  }}\n')
    meta = "\n".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: {json.dumps(n)} }} }}'
                     for n, i in ids.items())
    return text + meta + "\n}\n"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    from jax.profiler import ProfileData

    text = (_plane(1, "/device:TPU:0", {"XLA Ops": DEVICE0, "Async XLA Ops": ASYNC0})
            + _plane(2, "/device:TPU:1", {"XLA Ops": DEVICE1})
            + _plane(3, "/host:CPU", {"python": HOST}))
    path = tmp_path_factory.mktemp("trace") / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return tr.read_trace(str(path))


def test_parse_op():
    assert tr.parse_op(DEVICE1[1][0]) == ("fusion.9", "fusion")
    assert tr.parse_op(DEVICE1[0][0]) == ("while.5", "while")
    assert tr.parse_op("%psum.7 = f32[]{:T(128)} all-reduce(f32[] %div), channel_id=1") == (
        "psum.7", "all-reduce")
    assert tr.parse_op("%collective-permute-start.2 = (f32[1,8,128]{2,1,0}, f32[1,8,128]{2,1,0}) "
                       "collective-permute-start(f32[1,8,128]{2,1,0} %x)") == (
        "collective-permute-start.2", "collective-permute-start")
    assert tr.op_class("all-gather-start.1", "async-start") == "collective"
    assert tr.op_class("slice-start.8", "async-start") == "compute"
    assert tr.parse_op("fusion.3") == ("fusion.3", "fusion")


def test_classes():
    for name in ("collective-permute-start.4", "collective-permute-done.4", "all-reduce.1",
                 "all-gather", "reduce-scatter.2", "all-to-all.7", "send.1", "recv-done.3"):
        assert tr.op_class(name) == "collective", name
    for name in ("fusion.12", "copy.3", "all-reduce-fusion-like.1", "convolution.2"):
        assert tr.op_class(name) == "compute", name


def test_busy_compute_collective_exposed(summary):
    assert summary.window == (0, 100)
    d0, d1 = summary.devices[0], summary.devices[1]
    assert (d0.busy_ns, d0.compute_ns, d0.collective_ns, d0.exposed_collective_ns) == (55, 40, 27, 15)
    assert (d1.busy_ns, d1.compute_ns, d1.collective_ns, d1.exposed_collective_ns) == (100, 100, 0, 0)
    assert summary.mean("busy_ns") == 77.5
    assert summary.mean("exposed_collective_ns") == 7.5


def test_gaps_labelled_by_host_span(summary):
    assert summary.devices[0].gaps == [(0, 10), (50, 60), (70, 95)]
    gaps = summary.idle_gaps()
    assert [g[0] for g in gaps] == ["bench.block", "bench.block", "bench.step"]
    assert [g[1] for g in gaps] == pytest.approx([25e-9, 10e-9, 10e-9])


def test_device_ops(summary):
    ops = dict(summary.device_ops())
    assert ops == pytest.approx({
        "fusion.9": 50e-9, "fusion.1": 10e-9, "collective-permute-start.1 start-to-done": 8.5e-9,
        "fusion.2": 7.5e-9, "all-reduce.2": 5e-9, "copy.3": 2.5e-9,
        "collective-permute-start.1": 1e-9, "collective-permute-done.1": 1e-9})


def test_metric_readers(summary):
    from bench import harness

    run = {"steps": 2, "chips": 2, "flops_per_step": 1e3, "peak_flops": 1e12}
    read = {m: harness.metric_reader(m).read(summary, run) for m in
            ("idle_share", "compute_ms", "collective_ms", "exposed_collective_ms", "mfu")}
    assert read["idle_share"] == pytest.approx(22.5)
    assert read["compute_ms"] == pytest.approx(70e-6 / 2)
    assert read["collective_ms"] == pytest.approx(13.5e-6 / 2)
    assert read["exposed_collective_ms"] == pytest.approx(7.5e-6 / 2)
    # 2 steps of 1e3 FLOPs in 100 ns on 2 chips of 1e12 FLOP/s: 1e4 %
    assert read["mfu"] == pytest.approx(100.0 * 2e3 / (100e-9 * 2 * 1e12))


def test_no_collective_reads_nothing():
    s = tr.summarize({0: [("fusion.1", "compute", 0, 10)]}, [(0, 10, "bench.window")])
    from bench import harness

    run = {"steps": 1, "chips": 1, "flops_per_step": 1.0, "peak_flops": 1.0}
    assert harness.metric_reader("collective_ms").read(s, run) is None
    assert harness.metric_reader("exposed_collective_ms").read(s, run) is None


def test_interval_helpers():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


RECORDED = Path(__file__).parent / "data"


def _brute_busy(path):
    """Busy nanoseconds by marking every covered nanosecond, from the raw
    events: an independent count of what the reduction's unions give."""
    import numpy as np
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    lo, hi = next((e.start_ns, e.end_ns) for p in data.planes if p.name.startswith("/host:")
                  for ln in p.lines for e in ln.events if e.name == "bench.window")
    covered = np.zeros(int(hi - lo), bool)
    for p in data.planes:
        if p.name == "/device:TPU:0":
            for ln in p.lines:
                if ln.name == "XLA Ops":
                    for e in ln.events:
                        if tr.parse_op(e.name)[1] not in tr.CONTAINERS:
                            covered[int(max(e.start_ns, lo) - lo):int(min(e.end_ns, hi) - lo)] = True
    return int(covered.sum())


def test_recorded_one_chip_window():
    """20 ms of a bert.1chip run's own trace on the TPU v5e (seed 3000000012),
    across the boundary between its first two timed steps."""
    path = RECORDED / "bert1chip_window.xplane.pb"
    s = tr.read_trace(str(path))
    d = s.devices[0]
    assert list(s.devices) == [0]
    assert s.window == (220_000_000, 240_000_000)
    assert d.busy_ns == 11_372_047 == d.compute_ns == _brute_busy(path)
    assert d.collective_ns == 0 and d.exposed_collective_ns == 0
    # the device waits 8.627431 ms between the two steps, while the host
    # is still in bench.block reading the loss (the device clock runs
    # about a millisecond ahead of the host's in this trace)
    longest = max(d.gaps, key=lambda g: g[1] - g[0])
    assert longest == (224_658_486, 233_285_917)
    assert s.idle_gaps(1) == [["bench.block", pytest.approx(8.627431e-3)]]
    assert s.device_ops(2) == [["fusion.311", pytest.approx(2.450475e-3)],
                               ["fusion.329", pytest.approx(2.103803e-3)]]
