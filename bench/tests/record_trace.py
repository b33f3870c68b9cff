"""Record the small trace that the trace-reduction tests read.

    python3 bench/tests/record_trace.py <out.xplane.pb>
    python3 bench/tests/record_trace.py --excerpt <src.xplane.pb> <out.xplane.pb> <t0_ns> <t1_ns>

On the TPU chips present (up to four): a jitted ``shard_map`` step that
multiplies a matrix, sends it round the ring with ``ppermute`` and sums it
with ``psum``, run three times under ``bench.window`` / ``bench.step`` /
``bench.block`` host spans, as the benchmark's window runs them.

``--excerpt`` cuts a recorded trace down to the device operations
(``XLA Ops``, ``Async XLA Ops``) and ``bench.*`` host spans that overlap
``[t0, t1)``, with ``bench.window`` set to that interval and each HLO name
cut after its opcode, so that a window of a benchmark run's own trace is
small enough to keep beside the tests.
"""

from __future__ import annotations

import glob
import json
import os
import re
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def main(out: str) -> int:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 3
    n = min(4, len(devices))
    mesh = Mesh(np.array(devices[:n]), ("x",))

    def body(a):
        b = a @ a.T
        b = jax.lax.ppermute(b, "x", [(i, (i + 1) % n) for i in range(n)])
        return jax.lax.psum(b, "x")

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    a = jax.device_put(jnp.ones((n * 1024, 1024), jnp.bfloat16), NamedSharding(mesh, P("x")))
    step(a).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    r = step(a)
                with jax.profiler.TraceAnnotation("bench.block"):
                    r.block_until_ready()
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
        shutil.copy(path, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"record_trace: wrote {out} ({os.path.getsize(out)} bytes)")
    return 0


_HLO_HEAD = re.compile(r"^%?[^\s=]+ = .*? [a-z][a-z0-9-]*\(")


def excerpt(src: str, out: str, t0: float, t1: float) -> int:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(src).planes:
        keep = {}
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in ("XLA Ops", "Async XLA Ops"):
                    keep[line.name] = [(m.group(0) if (m := _HLO_HEAD.match(e.name)) else e.name,
                                        e.start_ns, e.end_ns)
                                       for e in line.events if e.end_ns > t0 and e.start_ns < t1]
        elif plane.name.startswith("/host:"):
            spans = [(e.name, e.start_ns, e.end_ns) for line in plane.lines for e in line.events
                     if e.name.startswith("bench.") and e.name != "bench.window"
                     and e.end_ns > t0 and e.start_ns < t1]
            keep["python"] = [("bench.window", t0, t1)] + spans
        if keep:
            planes.append((plane.name, keep))
    names = sorted({n for _, lines in planes for evs in lines.values() for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    text = []
    for pid, (pname, lines) in enumerate(planes):
        text.append(f"planes {{ id: {pid + 1} name: {json.dumps(pname)}")
        for lid, (lname, evs) in enumerate(lines.items()):
            text.append(f"  lines {{ id: {lid + 1} name: {json.dumps(lname)} timestamp_ns: 0")
            text.extend(f"    events {{ metadata_id: {ids[n]} offset_ps: {round(s * 1000)} "
                        f"duration_ps: {round((e - s) * 1000)} }}" for n, s, e in evs)
            text.append("  }")
        used = {n for evs in lines.values() for n, _, _ in evs}
        text.extend(f"  event_metadata {{ key: {ids[n]} value {{ id: {ids[n]} name: {json.dumps(n)} }} }}"
                    for n in sorted(used))
        text.append("}")
    with open(out, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace("\n".join(text)))
    print(f"record_trace: wrote {out} ({os.path.getsize(out)} bytes, {len(names)} names)")
    return 0


if __name__ == "__main__":
    if sys.argv[1] == "--excerpt":
        sys.exit(excerpt(sys.argv[2], sys.argv[3], float(sys.argv[4]), float(sys.argv[5])))
    sys.exit(main(sys.argv[1]))
