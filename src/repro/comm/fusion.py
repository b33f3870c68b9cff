"""Comm/compute fusion: collective rounds stream through producer/consumer
kernels instead of running kernel → barrier → collective.

PCCL's end-to-end win comes from hiding communication behind compute
(PAPER.md §7), and the repo has both halves — Pallas kernels and the
compiled schedule engine (``repro.comm.exec_engine``) — but the unfused
path runs them strictly back to back.  This module fuses three seams:

**Producer-side: tile-streaming matmul + reduce-scatter**
  (:func:`fused_matmul_reduce_scatter`).  The blocked matmul
  (``repro.kernels.matmul``) finishes one output row-chunk at a time; a
  ring reduce-scatter needs its chunks at staggered *deadlines* (rank
  ``i`` first sends chunk ``i-1``, at round ``t`` it receives chunk
  ``i-t-2`` — the ring's own pipelining).  :func:`stream_program` turns a
  :class:`~repro.comm.exec_engine.CompiledSchedule` into a per-rank chunk
  *compute order* (stable sort by deadline) and proves the joint program
  feasible: a double-buffered ``lax.scan`` over steps ``s = 1..n-1``
  computes tile ``order[s]`` and then runs round ``s-1``, so round ``r``
  of chunk ``c`` starts as soon as tile ``c`` is done and all wire time
  except the final round overlaps compute.  The result is **bit-identical**
  to unfused compute-then-communicate: per-chunk kernel calls reproduce the
  whole-``M`` call exactly (see ``kernels/matmul/kernel.py``), and the
  feasibility proof guarantees no round ever reads or accumulates into a
  chunk slot before its tile was stored — every add then sees the same
  operands in the same order as ``execute_schedule_reference``.

**Consumer-side: rmsnorm at all-reduce arrival**
  (:func:`fused_all_reduce_rmsnorm`).  The last all-reduce round's output
  feeds the rmsnorm kernel inside the same jitted executable — the
  post-collective normalization pass (a full extra HBM round trip plus a
  dispatch) disappears.  Row-wise rmsnorm commutes with how the buffer is
  sharded, so this is bit-identical to all_reduce → rmsnorm by
  construction.

**Wire-compressed collectives** (:func:`execute_compiled_quantized`,
  :func:`all_reduce_quantized`).  The int8 error-feedback collective from
  ``repro.comm.pccl_collectives`` promoted to a planner-visible algorithm
  (``ring_ef8``): same transfers as ``ring``, each hop's payload quantized
  to int8 + one fp32 scale (4x less wire), priced by the cost model via
  ``Round.size`` and gated by the documented accuracy bound
  (``repro.core.cost_model.compressed_ef_error_bound``).  Stateful error
  feedback (the residual) remains a caller-side composition
  (``compressed_all_reduce_ef``); the planner prices the wire format.

Both fused entry points are **eager**: they take the global
``(axis_size, *local)`` operand convention of the interp backend's eager
path and memoize one jitted ``shard_map`` executable per (schedule,
shapes, blocks) in ``exec_engine.EXECUTABLES``.  Whenever a precondition
fails — grouped communicator, chunk rows not divisible, blocks that don't
tile, a schedule with no feasible stream program — they **fall back** to
the unfused kernel-then-collective path (never an error, never padding:
padding would break bit-identity).  Every dispatch is counted
(``exec_engine.note_fused_dispatch`` / ``note_fallback_dispatch``) and
surfaced through ``exec_stats()``.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

from repro.comm.errors import ScheduleExecutionError

from . import exec_engine
from .exec_engine import CompiledSchedule

__all__ = [
    "StreamProgram",
    "all_reduce_quantized",
    "execute_compiled_quantized",
    "fused_all_reduce_rmsnorm",
    "fused_matmul_reduce_scatter",
    "stream_program",
]


# ------------------------------------------------------- stream programs


@dataclass(frozen=True)
class StreamProgram:
    """Joint (tile, round) program for a streamable reduce-scatter.

    ``order[r]`` is rank ``r``'s chunk *compute order*: tile ``order[r, 0]``
    is computed in the prologue, then step ``s`` computes tile
    ``order[r, s]`` and runs round ``s-1``.  ``send``/``recv`` are the
    compiled tables with the (always 1 here) chunk axis squeezed.
    """

    perm: Tuple[Tuple[int, int], ...]
    order: np.ndarray  # (n, n_chunks) int32 — per-rank compute order
    send: np.ndarray   # (rounds, n) int32
    recv: np.ndarray   # (rounds, n) int32

    @property
    def rounds(self) -> int:
        return self.send.shape[0]


_STREAM_LOCK = threading.Lock()
_STREAM_PROGRAMS: dict = {}  # fingerprint -> StreamProgram | None
_STREAM_MAX = 64


def stream_program(compiled: CompiledSchedule) -> Optional[StreamProgram]:
    """Derive the per-rank tile order that lets rounds start early.

    A schedule is *streamable* when tiles can be produced one per step and
    every round still only touches chunk slots whose tile is already
    stored.  Requirements (ring reduce-scatter satisfies all of them;
    anything else returns ``None`` and callers run unfused):

    * one reducing :class:`~repro.comm.exec_engine.RoundGroup` with one
      chunk per rank per round (``k == 1``) over ``n`` chunks in
      ``n - 1`` rounds (the scan pairs one fresh tile with one round);
    * per rank, sorting chunks by *deadline* — the first round that sends
      the chunk or accumulates into it (``n - 1`` for untouched chunks) —
      yields an order in which at most ``t + 2`` chunks are needed by the
      end of round ``t`` (prologue tile + one tile per step).

    The deadline check is exact, not heuristic: it is precisely the
    condition under which the fused scan is bit-identical to unfused
    execution (no round observes an unset slot).  Memoized by schedule
    fingerprint, including the ``None`` verdict.
    """
    fp = compiled.fingerprint
    with _STREAM_LOCK:
        if fp in _STREAM_PROGRAMS:
            return _STREAM_PROGRAMS[fp]
    prog = _stream_program(compiled)
    with _STREAM_LOCK:
        if len(_STREAM_PROGRAMS) >= _STREAM_MAX:
            _STREAM_PROGRAMS.clear()
        _STREAM_PROGRAMS[fp] = prog
    return prog


def _stream_program(compiled: CompiledSchedule) -> Optional[StreamProgram]:
    if len(compiled.groups) != 1:
        return None
    grp = compiled.groups[0]
    rounds, n, k = grp.send_ids.shape
    if not grp.reduce or k != 1:
        return None
    n_chunks = int(max(grp.send_ids.max(), grp.recv_ids.max())) + 1
    if n_chunks != n or rounds != n_chunks - 1:
        return None
    send = grp.send_ids[:, :, 0]  # (rounds, n)
    recv = grp.recv_ids[:, :, 0]
    order = np.zeros((n, n_chunks), dtype=np.int32)
    for r in range(n):
        deadline = np.full(n_chunks, rounds, dtype=np.int64)
        for t in range(rounds - 1, -1, -1):
            deadline[send[t, r]] = t
            deadline[recv[t, r]] = t
        rank_order = np.argsort(deadline, kind="stable")
        # feasibility: by the time round t runs, t + 2 tiles are stored
        need = np.zeros(rounds, dtype=np.int64)
        for c in range(n_chunks):
            if deadline[c] < rounds:
                need[deadline[c]] += 1
        if (np.cumsum(need) > np.arange(rounds) + 2).any():
            return None
        order[r] = rank_order.astype(np.int32)
    return StreamProgram(
        perm=grp.perm,
        order=order,
        send=np.ascontiguousarray(send),
        recv=np.ascontiguousarray(recv),
    )


# -------------------------------------- producer fusion: matmul → reduce-scatter


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    import jax

    return jax.default_backend() == "cpu" if interpret is None else interpret


def _eager_eligible(x) -> bool:
    from repro.api.backends import _eager_eligible as eligible

    return eligible(x)


def fused_matmul_reduce_scatter(
    comm,
    x,
    w,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
):
    """``reduce_scatter(x_r @ w)`` with rounds streamed under the matmul.

    Eager entry point (concrete arrays, outside any trace — the global
    operand convention of the interp backend's eager path):

    Args:
      comm: interp-backend :class:`~repro.api.Communicator`.
      x: ``(axis_size, M, K)`` — row ``r`` is rank ``r``'s local activation.
      w: ``(K, N)`` replicated weight.

    Returns ``(axis_size, M // n, N)``: row ``r`` is rank ``r``'s fully
    reduced output shard ``sum_q (x_q @ w)[r·Mc : (r+1)·Mc]``.

    Takes the fused tile-streaming path when the communicator is
    ungrouped, ``M`` divides into ``n`` chunk rows, the (clipped) blocks
    tile each ``(Mc, K, N)`` chunk exactly, and the planned schedule
    admits a :func:`stream_program`; otherwise falls back to the unfused
    kernel-then-collective composition (identical result — the fused path
    is bit-identical by construction).
    """
    from repro.kernels.matmul.ops import tiles_exactly

    if not _eager_eligible(x) or not _eager_eligible(w):
        raise ScheduleExecutionError(
            "fused_matmul_reduce_scatter is an eager entry point; inside a "
            "trace compose the matmul and reduce_scatter directly"
        )
    if x.ndim != 3 or x.shape[0] != comm.axis_size:
        raise ScheduleExecutionError(
            f"expected global (axis_size={comm.axis_size}, M, K) operand, "
            f"got shape {tuple(x.shape)}"
        )
    if w.ndim != 2 or x.shape[2] != w.shape[0]:
        raise ScheduleExecutionError(
            f"weight shape {tuple(w.shape)} does not match x {tuple(x.shape)}"
        )
    n = comm.n
    _, M, K = x.shape
    N = w.shape[1]
    blocks = (block_m, block_n, block_k)
    interpret = _resolve_interpret(interpret)

    fusable = comm.groups is None and M % n == 0
    prog = None
    sched = None
    if fusable and tiles_exactly(
        M // n, K, N, block_m=block_m, block_n=block_n, block_k=block_k
    ):
        sched = comm.axis_schedule(
            "reduce_scatter", float(M) * N * x.dtype.itemsize
        )
        prog = stream_program(exec_engine.compile_schedule(sched))
    if prog is None:
        return _unfused_matmul_reduce_scatter(
            comm, x, w, blocks=blocks, interpret=interpret
        )

    key = (
        "fused_mm_rs",
        sched.fingerprint(),
        tuple(x.shape),
        tuple(w.shape),
        str(x.dtype),
        comm.axis_name,
        comm.group_fingerprint(),
        blocks,
        interpret,
    )
    fn = exec_engine.EXECUTABLES.get(key)
    if fn is None:
        fn = _build_fused_mm_rs(
            comm, prog, tuple(x.shape), N, x.dtype, blocks, interpret
        )
        exec_engine.EXECUTABLES.put(key, fn)
    out = fn(x, w)
    exec_engine.note_fused_dispatch()
    return out


def _unfused_matmul_reduce_scatter(comm, x, w, *, blocks, interpret):
    """Sequential fallback: whole-M kernel dispatch, then the collective."""
    from repro.kernels.matmul.ops import matmul

    S, M, K = x.shape
    bm, bn, bk = blocks
    y = matmul(
        x.reshape(S * M, K), w,
        block_m=bm, block_n=bn, block_k=bk,
        use_pallas=True, interpret=interpret,
    ).reshape(S, M, w.shape[1])
    exec_engine.note_fallback_dispatch()
    return comm.reduce_scatter(y)


def _build_fused_mm_rs(comm, prog, x_shape, N, dtype, blocks, interpret):
    """jit(shard_map(...)) running the joint (tile, round) stream program."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.kernels.matmul.kernel import matmul_pallas

    axis = comm.axis_name
    S, M, K = x_shape
    n = prog.order.shape[1]
    Mc = M // n
    bm, bn, bk = blocks
    order_dev = jnp.asarray(prog.order)
    send_dev = jnp.asarray(prog.send)
    recv_dev = jnp.asarray(prog.recv)
    perm = prog.perm

    def inner(xl, wl):
        exec_engine.note_trace()
        xloc = xl[0]  # (M, K)
        me = lax.axis_index(axis)
        order = jnp.take(order_dev, me, axis=0)  # (n,)
        send = jnp.take(send_dev, me, axis=1)    # (rounds,)
        recv = jnp.take(recv_dev, me, axis=1)

        def tile(c):
            rows = lax.dynamic_slice(xloc, (c * Mc, 0), (Mc, K))
            return matmul_pallas(
                rows, wl, block_m=bm, block_n=bn, block_k=bk,
                interpret=interpret,
            )

        buf = jnp.zeros((n, Mc, N), dtype)
        buf = buf.at[order[0]].set(tile(order[0]))

        def body(b, step):
            c, s_id, r_id = step
            b = b.at[c].set(tile(c))  # tile c is done …
            got = lax.ppermute(b[s_id], axis, perm)
            return b.at[r_id].add(got), None  # … so its round starts now

        buf, _ = lax.scan(body, buf, (order[1:], send, recv))
        return jnp.take(buf, me, axis=0)[None]

    mesh = Mesh(jax.devices()[:S], (axis,))
    fun = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P(axis, None, None), P(None, None)),
        out_specs=P(axis, None, None),
        check_vma=False,
    )
    return jax.jit(fun)


# -------------------------------- consumer fusion: all-reduce → rmsnorm


def fused_all_reduce_rmsnorm(
    comm,
    x,
    gamma,
    *,
    eps: float = 1e-5,
    interpret: Optional[bool] = None,
):
    """``rmsnorm(all_reduce(x), gamma)`` as one dispatch.

    Eager entry point: ``x`` is the global ``(axis_size, *local)`` operand
    (``local[-1] == gamma.shape[0]``), the return keeps the leading axis.
    The rmsnorm kernel runs on the last round's arrival inside the same
    executable, so the post-collective normalization pass (its own HBM
    round trip and dispatch) disappears.  Bit-identical to
    ``comm.all_reduce(x)`` followed by the rmsnorm kernel — rmsnorm is
    row-wise, so fusing it under the shard_map changes nothing numerically.

    Falls back to the sequential two-dispatch composition when the
    communicator is grouped or the flattened local size is not divisible
    by ``n`` (the unfused all_reduce pads; padding inside the fused
    executable would change the chunk layout the schedule was planned
    for).
    """
    import math

    if not _eager_eligible(x) or not _eager_eligible(gamma):
        raise ScheduleExecutionError(
            "fused_all_reduce_rmsnorm is an eager entry point; inside a "
            "trace compose all_reduce and rmsnorm directly"
        )
    if x.ndim < 2 or x.shape[0] != comm.axis_size:
        raise ScheduleExecutionError(
            f"expected global (axis_size={comm.axis_size}, *local) operand "
            f"with a feature axis, got shape {tuple(x.shape)}"
        )
    if gamma.ndim != 1 or x.shape[-1] != gamma.shape[0]:
        raise ScheduleExecutionError(
            f"gamma shape {tuple(gamma.shape)} does not match x feature axis "
            f"{tuple(x.shape)}"
        )
    from repro.kernels.rmsnorm.ops import rmsnorm

    interpret = _resolve_interpret(interpret)
    local_size = math.prod(x.shape[1:])
    if comm.groups is not None or local_size % comm.n:
        exec_engine.note_fallback_dispatch()
        red = comm.all_reduce(x)
        return rmsnorm(red, gamma, eps=eps, use_pallas=True, interpret=interpret)

    sched = comm.axis_schedule("all_reduce", float(local_size) * x.dtype.itemsize)
    key = (
        "fused_ar_rms",
        sched.fingerprint(),
        tuple(x.shape),
        tuple(gamma.shape),
        str(x.dtype),
        comm.axis_name,
        comm.group_fingerprint(),
        float(eps),
        interpret,
    )
    fn = exec_engine.EXECUTABLES.get(key)
    if fn is None:
        fn = _build_fused_ar_rms(comm, sched, tuple(x.shape), eps, interpret)
        exec_engine.EXECUTABLES.put(key, fn)
    out = fn(x, gamma)
    exec_engine.note_fused_dispatch()
    return out


def _build_fused_ar_rms(comm, sched, x_shape, eps, interpret):
    import jax
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from repro.comm import primitives as prims
    from repro.kernels.rmsnorm.ops import rmsnorm

    axis = comm.axis_name
    S = x_shape[0]

    def inner(xl, g):
        exec_engine.note_trace()
        xloc = xl[0]
        flat = xloc.reshape(-1)
        red = prims.all_reduce(flat, sched, axis).reshape(xloc.shape)
        out = rmsnorm(red, g, eps=eps, use_pallas=True, interpret=interpret)
        return out[None]

    mesh = Mesh(jax.devices()[:S], (axis,))
    spec = P(axis, *([None] * (len(x_shape) - 1)))
    fun = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(spec, P(None)),
        out_specs=spec,
        check_vma=False,
    )
    return jax.jit(fun)


# -------------------------------------- wire-compressed (int8) execution


def execute_compiled_quantized(chunks, compiled: CompiledSchedule, axis_name: str, *, me=None):
    """:func:`~repro.comm.exec_engine.execute_compiled` with int8 wire.

    Identical gather/permute/scatter structure, but every hop's payload is
    quantized to int8 with one shared fp32 scale (``max|payload| / 127``)
    before the ``ppermute`` and dequantized on arrival — 4x less wire
    traffic, which is exactly what the ``ring_ef8`` schedule's
    ``Round.size * 0.25`` prices.  Lossy: per hop the round-trip error is
    at most ``scale / 2``; the accumulated bound lives in
    ``repro.core.cost_model.compressed_ef_error_bound`` and gates when
    arbitration may pick the algorithm.
    """
    import jax.numpy as jnp
    from jax import lax

    from .pccl_collectives import _dequantize, _quantize

    if me is None:
        me = lax.axis_index(axis_name)

    def apply_round(buf, send, recv, grp):
        payload = jnp.take(buf, send, axis=0)
        q, scale = _quantize(payload)
        q = lax.ppermute(q, axis_name, grp.perm)
        scale = lax.ppermute(scale, axis_name, grp.perm)
        got = _dequantize(q, scale).astype(buf.dtype)
        return buf.at[recv].add(got) if grp.reduce else buf.at[recv].set(got)

    for grp in compiled.groups:
        send = jnp.take(jnp.asarray(grp.send_ids), me, axis=1)  # (rounds, k)
        recv = jnp.take(jnp.asarray(grp.recv_ids), me, axis=1)
        if grp.rounds == 1:
            chunks = apply_round(chunks, send[0], recv[0], grp)
        else:

            def body(buf, sr, _grp=grp):
                return apply_round(buf, sr[0], sr[1], _grp), None

            chunks, _ = lax.scan(body, chunks, (send, recv))
    return chunks


def all_reduce_quantized(x, schedule, axis_name: str):
    """int8-on-the-wire all_reduce — the executable form of ``ring_ef8``.

    Same wrapper contract as :func:`repro.comm.primitives.all_reduce`
    (call inside ``shard_map``; ``x`` is the full per-rank addend), same
    chunk layout, but rounds run through
    :func:`execute_compiled_quantized`.  ``repro.api.backends`` routes
    ungrouped all_reduce here whenever the planned schedule's algorithm is
    ``ring_ef8``.
    """
    from .primitives import _split_chunks

    compiled = exec_engine.compile_schedule(schedule)
    chunks = _split_chunks(x, schedule.n)
    chunks = execute_compiled_quantized(chunks, compiled, axis_name)
    return chunks.reshape(x.shape)
