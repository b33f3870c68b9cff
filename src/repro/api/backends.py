"""Pluggable execution backends for :class:`repro.api.Communicator`.

Three implementations of one protocol:

* ``interp`` — the compiled-schedule execution engine
  (``repro.comm.exec_engine`` under ``repro.comm.primitives``): every
  planned round lowers to exactly one ``lax.ppermute`` whose permutation
  *is* the circuit set PCCL would program on the photonic fabric, with
  per-round tables compiled once per schedule and runs of like rounds
  fused into a single ``lax.scan``.  Call inside ``shard_map`` — or call
  with a **concrete** ``(axis_size, *local)`` array and the backend runs it
  through a process-wide cache of jitted ``shard_map`` executables keyed by
  ``(schedule fingerprint, shape, dtype, axis name, group fingerprint)``;
  repeated same-shape collectives then dispatch with zero retraces, and
  shape-preserving collectives (all_reduce, all_to_all) donate the input
  chunk buffer to the executable.
* ``xla``    — native ``lax`` collectives; the paper-faithful A/B baseline
  (what ``PcclComm(algorithm="xla")`` used to spell as a string hack).
* ``sim``    — cost-model-only: data passes through with single-copy
  placeholder semantics while the *planned* time of every collective is
  accumulated on ``elapsed_s``.  Lets tests and the serve/launch layers
  drive the identical Communicator API with no devices at all.

JAX is imported lazily so a ``sim``-only process never touches it.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, List, Optional, Protocol, Tuple, runtime_checkable

from repro.comm.errors import ScheduleExecutionError  # JAX-free

if TYPE_CHECKING:  # pragma: no cover
    from repro.core.schedules import Schedule

    from .communicator import Communicator


@runtime_checkable
class Backend(Protocol):
    """Executes the four PCCL primitives for one communicator."""

    name: str

    def all_reduce(self, comm: "Communicator", x): ...

    def reduce_scatter(self, comm: "Communicator", x): ...

    def all_gather(self, comm: "Communicator", x): ...

    def all_to_all(self, comm: "Communicator", x): ...


def _item_bytes(x) -> int:
    return x.dtype.itemsize


def _check_divisible(x, n: int) -> None:
    """Same leading-dim precondition (and error) as the interp interpreter."""
    if x.shape[0] % n:
        raise ScheduleExecutionError(
            f"leading dim {x.shape[0]} not divisible by {n} ranks"
        )


def _xla_groups(comm: "Communicator"):
    return [list(g) for g in comm.groups] if comm.groups is not None else None


class XlaBackend:
    """Native lax collectives (baseline; no PCCL planning involved)."""

    name = "xla"

    def all_reduce(self, comm, x):
        from jax import lax

        return lax.psum(x, comm.axis_name, axis_index_groups=_xla_groups(comm))

    def reduce_scatter(self, comm, x):
        from jax import lax

        return lax.psum_scatter(
            x, comm.axis_name, scatter_dimension=0, tiled=True,
            axis_index_groups=_xla_groups(comm),
        )

    def all_gather(self, comm, x):
        from jax import lax

        return lax.all_gather(
            x, comm.axis_name, axis=0, tiled=True,
            axis_index_groups=_xla_groups(comm),
        )

    def all_to_all(self, comm, x):
        from jax import lax

        b = x.shape[0] // comm.n
        y = x.reshape((comm.n, b) + x.shape[1:])
        y = lax.all_to_all(
            y, comm.axis_name, split_axis=0, concat_axis=0, tiled=False,
            axis_index_groups=_xla_groups(comm),
        )
        return y.reshape(x.shape)


def _eager_eligible(x) -> bool:
    """True only for actual arrays *outside any trace*.

    Checking the operand alone is not enough: a constant created or closed
    over inside a ``shard_map`` body is not a tracer, yet must still take
    the trace path (the axis name is bound there, and re-entering jit
    mid-trace would be wrong).
    """
    import jax

    return not isinstance(x, jax.core.Tracer) and jax.core.trace_ctx.is_top_level()


class InterpBackend:
    """Compiled schedule engine: planned rounds → fused ppermute groups.

    Inside ``shard_map`` the collectives trace as usual (compiled tables
    are memoized process-wide, so retraces skip all Python table
    derivation).  Called with concrete arrays, the backend instead routes
    through :func:`_run_eager`'s jitted-executable cache.

    ``all_reduce`` also takes a pytree of arrays (a gradient tree); a lone
    array is a tree of one leaf.  The leaves are grouped by the
    fingerprint of their schedule, which ignores byte sizes, and each
    group goes to :meth:`_run` together.  On an unsplit communicator a
    group runs through one round loop shared by all its leaves
    (``exec_engine.execute_compiled`` given several buffers, counted in
    ``exec_stats().shared_loop_buffers``): one leaf's gathers and adds can
    then run under another's ``ppermute``.  Every leaf's result is
    bit-identical to its own ``all_reduce``.  Split communicators and
    ``ring_ef8`` run each leaf alone; concrete leaves outside a trace run
    eagerly one by one.

    How an all-reduce operand is chunked (:func:`_chunked_layout`): on an
    unsplit communicator, outside ``ring_ef8``, an operand of two or more
    dimensions keeps its own shape and is cut into the schedule's ``n``
    chunks along its leading axis (counted in
    ``exec_stats().layout_kept_buffers``).  The TPU tiles an array's two
    minor dimensions, so that split is a bitcast both ways, and the result
    reaches its consumer (AdamW, in the DP step) in the leaf's own layout;
    a flattened leaf would need a physical relayout on the way in and
    again on the way out.  Scalars, 1-D operands, ``ring_ef8`` (whose int8
    scale is shared by a flat payload) and split communicators keep the
    flat layout.  Where the leading axis divides by ``n``, chunk ``c``
    holds the elements flat chunk ``c`` would, so the result is
    bit-identical to the flat layout's.
    """

    name = "interp"

    def all_reduce(self, comm, x):
        import jax

        leaves, treedef = jax.tree.flatten(x)
        if all(_eager_eligible(leaf) for leaf in leaves):
            out = [_run_eager(comm, "all_reduce", leaf) for leaf in leaves]
        else:
            out = self._traced(comm, "all_reduce", leaves, None)
        return treedef.unflatten(out)

    def reduce_scatter(self, comm, x):
        return self._collective(comm, "reduce_scatter", x)

    def all_gather(self, comm, x):
        return self._collective(comm, "all_gather", x)

    def all_to_all(self, comm, x):
        return self._collective(comm, "all_to_all", x)

    # ------------------------------------------------------------ dispatch
    def _collective(self, comm, collective, x):
        if _eager_eligible(x):
            return _run_eager(comm, collective, x)
        return self._traced(comm, collective, [x], None)[0]

    def _traced(self, comm, collective, xs, sched: "Optional[Schedule]"):
        """Trace-time body for the local operands ``xs``; returns their
        results in order.  ``sched`` is pre-resolved on the eager path (the
        executable must run exactly the schedule its cache key names).
        Operands whose schedules share a fingerprint (which ignores byte
        sizes) run together through :meth:`_run`.  Every op it adds,
        chunking and padding included, is named
        ``pccl_<collective>_<algorithm>`` (``exec_engine.collective_scope``)."""
        from repro.comm import exec_engine

        shared = {}  # schedule fingerprint -> (schedule, operand indices)
        for i, x in enumerate(xs):
            exec_engine.note_trace()
            s = sched if sched is not None else _traced_schedule(comm, collective, x)
            shared.setdefault(s.fingerprint(), (s, []))[1].append(i)
        out = list(xs)
        for s, idx in shared.values():
            with exec_engine.collective_scope(s.collective, s.algorithm):
                ops = [xs[i] for i in idx]
                if collective == "all_reduce":
                    keep = comm.groups is None and s.algorithm != "ring_ef8"
                    ops = [_chunked_layout(comm.n, x, keep) for x in ops]
                    exec_engine.note_layout_kept(sum(y.ndim >= 2 for y in ops))
                for i, y in zip(idx, self._run(comm, collective, ops, s)):
                    out[i] = _unpadded(y, xs[i]) if collective == "all_reduce" else y
        return out

    # -- dispatch: ungrouped → primitives; grouped → local-rank variants --
    def _run(self, comm, collective, xs, sched):
        """``sched`` run on each operand of ``xs``; ungrouped all-reduce
        operands share one round loop (``primitives.all_reduce``)."""
        from repro.comm import primitives as P

        if comm.groups is not None:
            return [_grouped_collective(comm, collective, x, sched) for x in xs]
        if collective == "all_reduce" and sched.algorithm == "ring_ef8":
            # planner-selected wire compression: int8 payloads per hop
            from repro.comm.fusion import all_reduce_quantized

            return [all_reduce_quantized(x, sched, comm.axis_name) for x in xs]
        if collective == "all_reduce":
            return P.all_reduce(xs, sched, comm.axis_name)
        return [getattr(P, collective)(x, sched, comm.axis_name) for x in xs]


def _traced_schedule(comm, collective, x):
    """The schedule the trace path runs for local operand ``x``."""
    return comm.axis_schedule(
        collective, _local_nbytes(comm, collective, x.shape, _item_bytes(x))
    )


def _sublane_rows(dtype) -> int:
    """Rows of one TPU tile of ``dtype``: 8 of 32-bit words, packed 16 of
    16-bit and 32 of 8-bit elements."""
    return 8 * max(1, 4 // dtype.itemsize)


def _chunked_layout(n: int, x, keep: bool):
    """The all-reduce operand for ``x``, in the layout its ``n`` chunks
    take along its leading axis (``exec_engine.split_chunks``).

    With ``keep`` (an unsplit communicator, not ``ring_ef8``), an ``x`` of
    two or more dimensions keeps its shape and so its TPU layout: the TPU
    tiles the two minor dimensions, so splitting the leading axis into
    ``(n, rows / n)`` is a bitcast.  Leading rows are zero-padded where
    needed: with three or more dimensions to a multiple of ``n``; with two,
    where ``rows`` does not divide by ``n``, to a multiple of ``n`` tiles of
    rows, so that each chunk holds whole tiles.  A 2-D ``x`` whose rows
    divide by ``n`` is not padded (its chunks then hold the elements of
    the flat chunks, so the result stays bit-identical to the flat
    layout's).  Otherwise ``x`` is flattened and zero-padded to a multiple
    of ``n``; :func:`exec_engine._lane_view` lays the flat chunks out.
    """
    import jax.numpy as jnp

    if keep and x.ndim >= 2:
        rows = x.shape[0]
        step = n if x.ndim > 2 or rows % n == 0 else n * _sublane_rows(x.dtype)
        pad = (-rows) % step
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        return x
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat


def _unpadded(y, x):
    """The all-reduced operand ``y`` cut back to ``x``: the pad rows off a
    kept layout, the pad off a flat one."""
    if y.ndim != x.ndim:
        return y[: x.size].reshape(x.shape)
    return y[: x.shape[0]] if y.shape != x.shape else y


# ------------------------------------------------------------- eager path


def _local_nbytes(comm, collective, local_shape, itemsize: int) -> float:
    """The nbytes a collective of a local operand is planned for (an
    all-reduce operand as its flat size padded to a multiple of
    ``comm.n``, whatever layout :func:`_chunked_layout` then gives it)."""
    import math

    size = math.prod(local_shape) if local_shape else 1
    if collective == "all_reduce":
        return float(size + ((-size) % comm.n)) * itemsize
    if collective == "all_gather":
        return float(size) * itemsize * comm.n
    return float(size) * itemsize


def _run_eager(comm, collective, x):
    """Concrete-array path: one cached, jitted shard_map executable.

    ``x`` is the **global** operand: ``(axis_size, *local)``, row ``r``
    being rank ``r``'s local operand of the in-``shard_map`` convention
    (all_reduce: full addend; reduce_scatter: ``(n·k, …)``; all_gather:
    shard; all_to_all: dest-major blocks).  The output keeps the leading
    axis: row ``r`` is rank ``r``'s local result.

    Executables are memoized process-wide in
    ``repro.comm.exec_engine.EXECUTABLES`` keyed by ``(schedule
    fingerprint, collective, global shape, dtype, axis name, group
    fingerprint)`` — a repeated same-shape collective is a cache hit and
    zero retraces.  Shape-preserving collectives donate the input buffer
    to XLA, so steady-state loops reuse the chunk storage.
    """
    import jax

    from repro.comm import exec_engine

    if x.ndim < 1 or x.shape[0] != comm.axis_size:
        raise ScheduleExecutionError(
            f"eager {collective}: expected global (axis_size={comm.axis_size},"
            f" *local) operand, got shape {tuple(x.shape)}; inside shard_map"
            " pass the local operand instead"
        )
    if len(jax.devices()) < comm.axis_size:
        raise ScheduleExecutionError(
            f"eager {collective} over axis {comm.axis_name!r} needs "
            f"{comm.axis_size} devices, found {len(jax.devices())}; call "
            "inside shard_map or set --xla_force_host_platform_device_count"
        )
    sched = comm.axis_schedule(
        collective, _local_nbytes(comm, collective, x.shape[1:], _item_bytes(x))
    )
    key = (
        sched.fingerprint(),
        collective,
        tuple(x.shape),
        str(x.dtype),
        comm.axis_name,
        comm.group_fingerprint(),
    )
    fn = exec_engine.EXECUTABLES.get(key)
    if fn is None:
        fn = _build_executable(comm, collective, sched, tuple(x.shape))
        exec_engine.EXECUTABLES.put(key, fn)
    return fn(x)


class _ExecView:
    """Static execution-time view of a Communicator.

    Everything ``InterpBackend._traced`` touches once the schedule is
    resolved — and nothing more: cached executables live in a
    process-wide LRU, so closing over the live Communicator would pin its
    whole PcclSession (plan + structure caches) for the cache's lifetime.
    """

    __slots__ = ("axis_name", "n", "axis_size", "groups", "_table", "_table_dev")

    def __init__(self, comm: "Communicator") -> None:
        self.axis_name = comm.axis_name
        self.n = comm.n
        self.axis_size = comm.axis_size
        self.groups = comm.groups
        self._table = comm.local_index_table()
        # built outside any trace, so this shares the communicator's own
        # cached upload rather than re-implementing it
        self._table_dev = comm.local_index_device_table()

    def local_index_table(self):
        return self._table

    def local_index_device_table(self):
        return self._table_dev


def _build_executable(comm, collective, sched, global_shape):
    """jit(shard_map(...)) over the resolved schedule; donates when the
    output buffer can alias the input, decided structurally by
    ``exec_engine.donation_compatible`` (whole-array footprints must
    coincide — the same Box model the kernel lint applies to
    ``input_output_aliases``; no tracing, so 0-retrace guarantees hold)."""
    import jax
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.comm import exec_engine

    backend = comm.backend  # stateless InterpBackend
    view = _ExecView(comm)
    axis = view.axis_name

    def inner(xl):
        return backend._traced(view, collective, [xl[0]], sched)[0][None]

    mesh = Mesh(jax.devices()[: view.axis_size], (axis,))
    spec = P(axis, *([None] * (len(global_shape) - 1)))
    fun = jax.shard_map(
        inner, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )
    donate = (
        (0,) if exec_engine.donation_compatible(collective, global_shape) else ()
    )
    return jax.jit(fun, donate_argnums=donate)


def _local_index(comm: "Communicator"):
    """me → index within my group, as a traced lookup of the communicator's
    cached rank→local table (built and uploaded once, not per trace)."""
    import jax.numpy as jnp
    from jax import lax

    me = lax.axis_index(comm.axis_name)
    return jnp.take(comm.local_index_device_table(), me)


def _grouped_collective(comm: "Communicator", collective: str, x, sched):
    """Group-local collectives on a split communicator.

    Mirrors ``repro.comm.primitives`` wrappers with the rank's *group-local*
    index: the composed schedule already routes between global ranks, while
    chunk ids (and local buffers) stay group-local.
    """
    import jax.numpy as jnp
    from jax import lax

    from repro.comm.exec_engine import (
        compile_all_to_all,
        execute_all_to_all_compact,
    )
    from repro.comm.primitives import ScheduleExecutionError, execute_schedule

    m = comm.n
    me_local = _local_index(comm)
    if collective in ("reduce_scatter", "all_reduce", "all_to_all") and x.shape[0] % m:
        raise ScheduleExecutionError(
            f"leading dim {x.shape[0]} not divisible by group size {m}"
        )
    if collective == "reduce_scatter":
        chunks = x.reshape((m, x.shape[0] // m) + x.shape[1:])
        chunks = execute_schedule(chunks, sched, comm.axis_name)
        return jnp.take(chunks, me_local, axis=0)
    if collective == "all_reduce":
        chunks = x.reshape((m, x.shape[0] // m) + x.shape[1:])
        if sched.algorithm == "ring_ef8":
            from repro.comm.exec_engine import compile_schedule
            from repro.comm.fusion import execute_compiled_quantized

            chunks = execute_compiled_quantized(
                chunks, compile_schedule(sched), comm.axis_name
            )
        else:
            chunks = execute_schedule(chunks, sched, comm.axis_name)
        return chunks.reshape(x.shape)
    if collective == "all_gather":
        chunks = jnp.zeros((m,) + x.shape, x.dtype).at[me_local].set(x)
        chunks = execute_schedule(chunks, sched, comm.axis_name)
        return chunks.reshape((m * x.shape[0],) + x.shape[1:])
    if collective == "all_to_all":
        blocks = x.reshape((m, x.shape[0] // m) + x.shape[1:])
        local_of = tuple(int(v) for v in comm.local_index_table())
        compact = compile_all_to_all(sched, m, local_of)
        if compact is not None:
            me = lax.axis_index(comm.axis_name)
            return execute_all_to_all_compact(
                blocks, compact, comm.axis_name, me
            ).reshape(x.shape)
        # dense fallback: O(m²·blk) origin×target state
        state = jnp.zeros((m, m) + blocks.shape[1:], blocks.dtype)
        state = state.at[me_local].set(blocks)
        flat = state.reshape((m * m,) + blocks.shape[1:])
        flat = execute_schedule(flat, sched, comm.axis_name)
        state = flat.reshape((m, m) + blocks.shape[1:])
        return jnp.take(state, me_local, axis=1).reshape(x.shape)
    raise ScheduleExecutionError(f"unknown collective {collective!r}")


class SimBackend:
    """Cost-model-only execution: accumulate planned time, pass data through.

    Data semantics are single-copy placeholders (the caller holds the only
    logical copy): ``all_reduce``/``all_to_all`` return the input unchanged,
    ``reduce_scatter`` returns **rank 0's** shard slice (there is no real
    rank here, so the first ``shape[0] // n`` rows stand in for "my shard" —
    only the shape is meaningful, not which values land in it),
    ``all_gather`` tiles the shard ``n`` times — shapes match the real
    backends so code paths are identical, but no inter-device data movement
    happens (or is needed).  Tiling happens in the input's own array
    namespace (numpy in → numpy out, jax in → jax out), so a sim-backend
    pipeline over device arrays never hops to host mid-graph.  Shape
    preconditions (leading-dim divisibility) raise the same
    :class:`~repro.comm.errors.ScheduleExecutionError` as the ``interp``
    backend instead of silently mis-shaping the output.
    """

    name = "sim"

    def __init__(self) -> None:
        self.elapsed_s = 0.0
        self.events: List[Tuple[str, float, float]] = []  # (coll, nbytes, cost)

    def _charge(self, comm, collective, nbytes) -> None:
        cost = comm.estimate(collective, nbytes)
        self.elapsed_s += cost
        self.events.append((collective, float(nbytes), cost))

    def all_reduce(self, comm, x):
        """``x`` an array or a pytree of arrays: each leaf is charged as
        its own all-reduce."""
        if hasattr(x, "dtype"):
            leaves = [x]
        else:
            import jax

            leaves = jax.tree.leaves(x)
        for leaf in leaves:
            self._charge(comm, "all_reduce", leaf.size * _item_bytes(leaf))
        return x

    def reduce_scatter(self, comm, x):
        _check_divisible(x, comm.n)
        self._charge(comm, "reduce_scatter", x.size * _item_bytes(x))
        return x[: x.shape[0] // comm.n]  # rank 0's shard (placeholder)

    def all_gather(self, comm, x):
        import numpy as np

        self._charge(comm, "all_gather", x.size * _item_bytes(x) * comm.n)
        reps = (comm.n,) + (1,) * (x.ndim - 1)
        if isinstance(x, np.ndarray):
            return np.tile(x, reps)
        import jax.numpy as jnp  # jax array in → jax array out, one tile

        return jnp.tile(x, reps)

    def all_to_all(self, comm, x):
        _check_divisible(x, comm.n)
        self._charge(comm, "all_to_all", x.size * _item_bytes(x))
        return x


_BACKENDS = {"xla": XlaBackend, "interp": InterpBackend, "sim": SimBackend}


def get_backend(name: str) -> Backend:
    """Fresh backend instance by name (``xla`` | ``interp`` | ``sim``)."""
    try:
        return _BACKENDS[name]()
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {sorted(_BACKENDS)}"
        ) from None


def register_backend(name: str, cls) -> None:
    """Extension point: register a custom Backend implementation."""
    _BACKENDS[name] = cls
