"""Record the small scoped trace that the scope-reduction tests read.

    python3 bench/tests/record_scoped_trace.py <out.xplane.pb>
    python3 bench/tests/record_scoped_trace.py --excerpt <src.xplane.pb> <out.xplane.pb> <t0_ns> <t1_ns>

On the TPU chips present (up to four): a jitted ``shard_map`` step whose
ops sit under the program's scope names, a matrix product under
``train_forward``, a ``ppermute`` round under
``pccl_all_reduce_ring/group0``, an update under ``train_optimizer``, and
a ``psum`` under none, run three times under ``bench.window`` /
``bench.step`` / ``bench.block`` host spans, as the benchmark's window
runs them.  The trace is cut down to the device lines that
``bench/scopes.py`` and ``bench/gaps.py`` read (``XLA Modules``,
``XLA Ops``, ``Async XLA Ops``), each op event's ``op_name`` stat, and the
``bench.*`` host spans; each HLO name is cut after its opcode.

``--excerpt`` cuts a recorded trace, such as a window that
``bench/run.py --keep-trace`` kept, down the same way and to the events
that overlap ``[t0, t1)``, with ``bench.window`` set to that interval.
"""

from __future__ import annotations

import glob
import json
import os
import re
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

DEVICE_LINES = ("XLA Modules", "XLA Ops", "Async XLA Ops")
_HLO_HEAD = re.compile(r"^%?[^\s=]+ = .*? [a-z][a-z0-9-]*\(")


def record(out_dir: str) -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    n = min(4, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:n]), ("x",))

    def body(a):
        with jax.named_scope("train_forward"):
            b = a @ a.T
        with jax.named_scope("pccl_all_reduce_ring"), jax.named_scope("group0"):
            c = jax.lax.ppermute(b, "x", [(i, (i + 1) % n) for i in range(n)])
        with jax.named_scope("train_optimizer"):
            d = 0.5 * c + b
        return jax.lax.psum(d, "x")

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    a = jax.device_put(jnp.ones((n * 1024, 1024), jnp.bfloat16), NamedSharding(mesh, P("x")))
    step(a).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                r = step(a)
            with jax.profiler.TraceAnnotation("bench.block"):
                r.block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return path


def excerpt(src: str, out: str, t0: float = float("-inf"), t1: float = float("inf")) -> int:
    from jax.profiler import ProfileData

    from bench import scopes

    def within(e):
        return e.end_ns > t0 and e.start_ns < t1

    op_names = scopes.read_op_names(src)
    planes = []
    for plane in ProfileData.from_file(src).planes:
        keep = {}
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            names = op_names.get(int(m.group(1)), {})
            for line in plane.lines:
                if line.name in DEVICE_LINES:
                    keep[line.name] = [(e.name, e.start_ns, e.end_ns, names.get(e.name))
                                       for e in line.events if within(e)]
        elif plane.name.startswith("/host:"):
            spans = [(e.name, e.start_ns, e.end_ns, None) for line in plane.lines
                     for e in line.events if e.name.startswith("bench.") and within(e)]
            if spans and t0 > float("-inf"):
                spans = [("bench.window", t0, t1, None)] + [x for x in spans if x[0] != "bench.window"]
            keep["python"] = spans
        if any(keep.values()):
            planes.append((plane.name, keep))
    t0 = int(min(s for _, lines in planes for evs in lines.values() for _, s, _, _ in evs))
    text = []
    for pid, (pname, lines) in enumerate(planes):
        short = {n: (m.group(0) if (m := _HLO_HEAD.match(n)) else n)
                 for evs in lines.values() for n, _, _, _ in evs}
        metas = {}
        for evs in lines.values():
            for n, _, _, op in evs:
                metas.setdefault(short[n], op)
        ids = {n: i + 1 for i, n in enumerate(sorted(metas))}
        text.append(f"planes {{ id: {pid + 1} name: {json.dumps(pname)}")
        for lid, (lname, evs) in enumerate(lines.items()):
            text.append(f"  lines {{ id: {lid + 1} name: {json.dumps(lname)} timestamp_ns: {t0}")
            text.extend(f"    events {{ metadata_id: {ids[short[n]]} offset_ps: {round((s - t0) * 1000)} "
                        f"duration_ps: {round((e - s) * 1000)} }}" for n, s, e, _ in evs)
            text.append("  }")
        for n, op in sorted(metas.items()):
            stat = f" stats {{ metadata_id: 1 str_value: {json.dumps(op)} }}" if op else ""
            text.append(f"  event_metadata {{ key: {ids[n]} value {{ id: {ids[n]} "
                        f"name: {json.dumps(n)}{stat} }} }}")
        text.append(f"  stat_metadata {{ key: 1 value {{ id: 1 name: "
                    f"{json.dumps(scopes.OP_NAME_STATS[0])} }} }}")
        text.append("}")
    data = ProfileData.text_proto_to_serialized_xspace("\n".join(text))
    if not ProfileData.from_serialized_xspace(data).planes:
        raise ValueError("record_scoped_trace: the excerpt did not parse")
    with open(out, "wb") as f:
        f.write(data)
    n_ops = sum(1 for n in op_names.values() for _ in n)
    print(f"record_scoped_trace: wrote {out} ({os.path.getsize(out)} bytes, "
          f"{n_ops} ops with an op_name)")
    return 0


def main(out: str) -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 3
    tmp = tempfile.mkdtemp()
    try:
        return excerpt(record(tmp), out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    if sys.argv[1] == "--excerpt":
        sys.exit(excerpt(sys.argv[2], sys.argv[3], float(sys.argv[4]), float(sys.argv[5])))
    sys.exit(main(sys.argv[1]))
