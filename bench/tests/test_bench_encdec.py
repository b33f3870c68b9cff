"""bench/encdec_scopes.py on traces worked out by hand and recorded on the
chip, and bench/flops.py for the whisper-small cells."""

import json
from pathlib import Path

import pytest

from bench import encdec_scopes as es
from bench import flops, harness
from bench import trace_reduce as tr

ROOT = Path(__file__).resolve().parents[2]
RECORDED = Path(__file__).parent / "data"
ENC = "jit(step)/jvp(train_forward)/encdec_encoder/while/body/checkpoint/dot_general"
ENC_BWD = "jit(step)/transpose(jvp(train_forward))/encdec_encoder/while/body/checkpoint/mul"
XATT = "jit(step)/jvp(train_forward)/while/body/checkpoint/encdec_cross_attention/dot_general"
XATT_BWD = ("jit(step)/transpose(jvp(train_forward))/while/body/checkpoint/"
            "rematted_computation/encdec_cross_attention/exp")
DEC = "jit(step)/jvp(train_forward)/while/body/checkpoint/dot_general"
PERMUTE = "jit(step)/train_grad_sync/pccl_all_reduce_ring/group1/while/body/ppermute"
ADD = "jit(step)/train_grad_sync/pccl_all_reduce_ring/group1/while/body/add"

# Device 0 (ns, window 0-100): encoder 0-20 and its backward 15-30
# (overlapping: union 0-30); cross-attention 30-40 and 60-65; a decoder op
# 40-50 under neither; the engine's permute on the async line 70-90 and
# its add 85-95 (union 70-95); a cross-attention op 95-110, clipped to
# 95-100.  Device 1: encoder 0-10, engine 50-60.
DEVICE0 = [("fusion.1", 0, 20, ENC), ("fusion.2", 15, 15, ENC_BWD), ("fusion.3", 30, 10, XATT),
           ("fusion.4", 40, 10, DEC), ("fusion.5", 60, 5, XATT_BWD),
           ("collective-permute-start.6", 70, 1, PERMUTE),
           ("collective-permute-done.6", 89, 1, PERMUTE), ("fusion.7", 85, 10, ADD),
           ("fusion.8", 95, 15, XATT)]
ASYNC0 = [("collective-permute-start.6", 70, 20, PERMUTE)]
DEVICE1 = [("fusion.1", 0, 10, ENC), ("fusion.7", 50, 10, ADD)]
HOST = [("bench.window", 0, 100, None), ("bench.step", 0, 2, None), ("bench.block", 2, 98, None)]


def _plane(pid, name, lines):
    names = {n: op for events in lines.values() for n, _, _, op in events}
    ids = {n: i + 1 for i, n in enumerate(sorted(names))}
    text = f'planes {{\n  id: {pid}\n  name: "{name}"\n'
    for lid, (line, events) in enumerate(lines.items()):
        text += f'  lines {{ id: {lid + 1} name: "{line}" timestamp_ns: 0\n'
        text += "".join(f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
                        f"duration_ps: {d * 1000} }}\n" for n, s, d, _ in events)
        text += "  }\n"
    for n, op in names.items():
        stats = f"stats {{ metadata_id: 1 str_value: {json.dumps(op)} }}" if op else ""
        text += (f"  event_metadata {{ key: {ids[n]} value {{ id: {ids[n]} "
                 f"name: {json.dumps(n)} {stats} }} }}\n")
    text += '  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n'
    return text + "}\n"


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    from jax.profiler import ProfileData

    text = (_plane(1, "/device:TPU:0", {"XLA Ops": DEVICE0, "Async XLA Ops": ASYNC0})
            + _plane(2, "/device:TPU:1", {"XLA Ops": DEVICE1})
            + _plane(3, "/host:CPU", {"python": HOST}))
    path = tmp_path_factory.mktemp("encdec") / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


def _as_run_cell(summary, path, fn):
    """Call ``fn`` from a function named ``run_cell`` of a file ``run.py``
    whose locals hold ``summary`` and ``path``, as bench/run.py's does."""
    scope = {}
    exec(compile("def run_cell(summary, path, fn):\n    return fn()\n",
                 str(ROOT / "bench" / "run.py"), "exec"), scope)
    return scope["run_cell"](summary, path, fn)


def _read(path, steps=2):
    summary = tr.read_trace(path)
    run = {"steps": steps, "chips": 1, "flops_per_step": 1.0, "peak_flops": 1.0}
    names = ("encoder_ms", "cross_attention_ms", "encdec_engine_ms")
    return _as_run_cell(summary, path, lambda: {
        m: harness.metric_reader(m).read(summary, run) for m in names})


def test_parts():
    assert es.part(ENC) == es.part(ENC_BWD) == "encoder"
    assert es.part(XATT) == es.part(XATT_BWD) == "cross_attention"
    assert es.part(PERMUTE) == es.part(ADD) == "engine"
    assert es.part(DEC) is None and es.part("jit(step)/xpccl_x/encdec") is None


def test_part_ns_worked_by_hand(trace):
    from bench import scopes

    parts = es.summarize(scopes.read_device_ops(trace), (0, 100))
    assert parts == {0: {"encoder": 30, "cross_attention": 20, "engine": 25},
                     1: {"encoder": 10, "cross_attention": 0, "engine": 10}}


def test_metric_readers_find_the_window_trace(trace):
    assert _read(trace) == pytest.approx({"encoder_ms": 20e-6 / 2,
                                          "cross_attention_ms": 10e-6 / 2,
                                          "encdec_engine_ms": 17.5e-6 / 2})
    # outside a run_cell frame there is no trace to read
    summary = tr.read_trace(trace)
    run = {"steps": 2, "chips": 1, "flops_per_step": 1.0, "peak_flops": 1.0}
    assert harness.metric_reader("encoder_ms").read(summary, run) is None


@pytest.mark.parametrize("recorded,engine", [("bert1chip_scoped_window.xplane.pb", False),
                                             ("scoped_4chip.xplane.pb", True),
                                             ("bert1chip_window.xplane.pb", False)])
def test_trace_without_encdec_scopes_reads_nothing(recorded, engine):
    """A program without the encoder-decoder's scopes (bert-base-paper's
    step, or the parent of the scopes): the encoder and cross-attention
    readers report nothing and raise nothing; the engine's reads where the
    trace has ``pccl_*`` ops."""
    read = _read(str(RECORDED / recorded))
    assert read["encoder_ms"] is None and read["cross_attention_ms"] is None
    assert (read["encdec_engine_ms"] is not None) == engine


def test_whisper_dp4_counts_every_chip():
    """dp4.b16x448: 16 rows a chip over 4 chips, 64 a step, twice
    whisper.1chip's 32 rows and 51.5 TFLOP."""
    cfg = harness.config("whisper-small")
    one = flops.step_flops(cfg, harness.traffic("single.b32x448"))
    traffic = harness.traffic("dp4.b16x448")
    assert traffic["batch_per_chip"] * traffic["chips"] == 64
    four = flops.step_flops(cfg, traffic)
    assert four == pytest.approx(2 * one, rel=1e-12)
    assert one == pytest.approx(5.15e13, rel=5e-3)


def test_recorded_four_chip_whisper_window():
    """108.5 ms of a whisper.dp4 traced run on four TPU v5e chips (seed
    3900000007), cut by ``record_scoped_trace.py --excerpt``: the last
    3 ms of one step's gradient all-reduce, its AdamW update, the next
    step's whole encoder forward pass, and the first decoder layer's
    cross-attention."""
    from bench import scopes

    path = str(RECORDED / "whisper_dp4_window.xplane.pb")
    s = tr.read_trace(path)
    assert s.window == (4_371_497_298, 4_479_969_050)
    parts = es.summarize(scopes.read_device_ops(path), s.window)
    assert sorted(parts) == [0, 1, 2, 3]
    assert parts[0] == {"encoder": 66_210_774, "cross_attention": 299_245, "engine": 2_999_267}
    phases = scopes.read_scopes(path, s.window).scope_ns
    for dev, p in parts.items():
        # the two readers agree on the engine, and the encoder and the
        # cross-attention lie inside the forward pass here
        assert p["engine"] == phases[dev]["engine"] > 0
        assert 0 < p["encoder"] + p["cross_attention"] <= phases[dev]["forward"]
        assert p["encoder"] + p["cross_attention"] + p["engine"] <= s.devices[dev].busy_ns
    read = _read(path, steps=1)
    assert read["encoder_ms"] == pytest.approx(66.212872, abs=1e-6)
    assert read["cross_attention_ms"] == pytest.approx(0.295359, abs=1e-6)
    assert read["encdec_engine_ms"] == pytest.approx(2.718147, abs=1e-6)
