"""Compiled schedule execution engine (the interp backend's hot path).

The per-round interpreter (``repro.comm.primitives.execute_schedule_reference``)
re-derives static per-round tables on every trace and emits one
``ppermute`` + scatter pair per round — O(rounds) Python work *and*
O(rounds) trace size per call.  This module lowers a
:class:`~repro.core.schedules.Schedule` **once** into a
:class:`CompiledSchedule` and memoizes it process-wide:

* **one compile pass** derives every round's ``(perm, send_ids, recv_ids,
  reduce)`` table (same validation as the reference interpreter, with the
  round index and the schedule's collective/algorithm in every error), then
* **folds consecutive rounds** that share a permutation, reduce-flag and
  chunk count into one :class:`RoundGroup` whose stacked ``(rounds, n, k)``
  chunk-id tables drive a single ``lax.scan`` — trace size and compile time
  drop from O(rounds) to O(round-groups) (ring RS/AG and every bucket axis
  phase collapse to one group; irregular schedules — RHD, DEX — keep the
  per-round fallback, which is just a group of length 1), and
* an **O(n·blk) all-to-all** compile (:func:`compile_all_to_all`) addresses
  blocks by *current holder slot* instead of the dense origin×target grid:
  a static simulation assigns every in-flight block a slot in an ``(n, blk)``
  buffer — exactly one live block per slot, asserted from the chunk
  metadata — and returns ``None`` (callers fall back to the dense path)
  whenever the metadata cannot be slot-addressed.

Execution (:func:`execute_compiled`) is **bit-identical** to the reference
interpreter: the same integer chunk ids are gathered, permuted and
scattered in the same order, so reductions see the same add order per
receiver.  The ``lax.scan`` merely rolls the identical round body into a
loop.  Several buffers that run one compiled schedule (the leaves of a
gradient tree, which ``repro.api.backends`` hands
``primitives.all_reduce`` as a list, each of two or more dimensions
carried in its own shape) can share that loop: each round
group runs once, and its body is a software pipeline over the buffers,
largest first — one ``ppermute`` in flight at a time, the next buffer's
gather and the last buffer's add running under it.  Each buffer still
sees exactly the rounds it would alone, and one buffer emits the same ops
as before.  Every device op it emits is named in the HLO
``op_name`` (and so in a profiler trace)
``pccl_<collective>_<algorithm>/group<i>/...``, with ``group<i>`` the
round group; :func:`collective_scope` opens the outer
name, and ``repro.api.backends`` opens it around a collective's chunking
and padding too.

Caches and counters (compiled-table LRU, the jitted-executable LRU that
``repro.api.backends`` fills, the trace counter, the count of buffers
traced through a shared round loop and of all-reduce operands that kept
their own layout) are process-wide,
lock-guarded and surfaced through :func:`exec_stats` /
``PcclSession.exec_stats()``.  This module imports JAX lazily — planning-
and sim-only processes can read stats without touching it.
"""

from __future__ import annotations

import contextlib
import os
import re
import sys
import threading
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core.schedules import Round, Schedule

from .errors import ScheduleExecutionError

__all__ = [
    "CompiledSchedule",
    "ExecStats",
    "RoundGroup",
    "clear_exec_caches",
    "collective_scope",
    "compile_all_to_all",
    "compile_schedule",
    "donation_compatible",
    "exec_stats",
    "expected_eager_result_shape",
    "execute_all_to_all_compact",
    "execute_compiled",
    "note_fallback_dispatch",
    "note_fused_dispatch",
    "note_layout_kept",
    "note_shared_loop",
    "note_trace",
    "round_tables",
    "split_chunks",
]


# ----------------------------------------------------------- round tables


def round_tables(
    rnd: Round, n: int, *, ctx: str = ""
) -> Tuple[List[Tuple[int, int]], np.ndarray, np.ndarray, bool]:
    """Static per-round tables: ``(perm, send_ids[n,k], recv_ids[n,k], reduce)``.

    ``ctx`` prefixes every :class:`ScheduleExecutionError` so trace-time
    failures name the round and schedule they came from.
    """

    def err(msg: str) -> ScheduleExecutionError:
        return ScheduleExecutionError(f"{ctx}{msg}" if ctx else msg)

    if not rnd.is_permutation():
        raise err("round is not a permutation (Tx/Rx > 1)")
    senders = {t.src for t in rnd.transfers}
    if len(senders) != n:
        raise err(f"round must have all {n} ranks sending, got {len(senders)}")
    ks = {len(t.chunks) for t in rnd.transfers}
    if len(ks) != 1:
        raise err(f"non-uniform chunk counts per rank: {ks}")
    k = ks.pop()
    if k == 0:
        raise err("schedule has no chunk metadata (e.g. swing)")
    reduces = {t.reduce for t in rnd.transfers}
    if len(reduces) != 1:
        raise err("mixed reduce/store within one round")
    perm = sorted((t.src, t.dst) for t in rnd.transfers)
    send_ids = np.zeros((n, k), dtype=np.int32)
    recv_ids = np.zeros((n, k), dtype=np.int32)
    for t in rnd.transfers:
        send_ids[t.src] = np.asarray(t.chunks, dtype=np.int32)
        recv_ids[t.dst] = np.asarray(t.chunks, dtype=np.int32)
    return perm, send_ids, recv_ids, reduces.pop()


def _ctx(schedule: Schedule, i: int) -> str:
    return (
        f"{schedule.collective}/{schedule.algorithm} "
        f"round {i}/{schedule.num_rounds}: "
    )


# ------------------------------------------------------- compiled schedule


@dataclass(frozen=True)
class RoundGroup:
    """Consecutive rounds sharing ``(perm, reduce, k)``, tables stacked."""

    perm: Tuple[Tuple[int, int], ...]
    reduce: bool
    send_ids: np.ndarray  # (rounds, n, k) int32, read-only
    recv_ids: np.ndarray  # (rounds, n, k) int32, read-only

    @property
    def rounds(self) -> int:
        return self.send_ids.shape[0]


@dataclass(frozen=True)
class CompiledSchedule:
    """A schedule lowered once: validated, stacked, group-folded tables.

    ``final_slots`` is only set by :func:`compile_all_to_all`: row ``r`` maps
    origin (group-local) rank ``o`` to the slot of rank ``r``'s buffer that
    holds the block ``o → r`` after the last round.
    """

    fingerprint: str
    collective: str
    algorithm: str
    n: int  # table rows == schedule.n (the axis span)
    num_rounds: int
    groups: Tuple[RoundGroup, ...]
    final_slots: Optional[np.ndarray] = None  # (n, m) int32 — compact a2a


def _freeze(a: np.ndarray) -> np.ndarray:
    a.flags.writeable = False
    return a


def _fold_groups(
    tables: List[Tuple[List[Tuple[int, int]], np.ndarray, np.ndarray, bool]]
) -> Tuple[RoundGroup, ...]:
    """Stack consecutive rounds with equal (perm, reduce, k) into groups."""
    groups: List[RoundGroup] = []
    i = 0
    while i < len(tables):
        perm, send, recv, reduce = tables[i]
        j = i + 1
        while j < len(tables):
            p2, s2, _, r2 = tables[j]
            if p2 != perm or r2 != reduce or s2.shape != send.shape:
                break
            j += 1
        groups.append(
            RoundGroup(
                perm=tuple(perm),
                reduce=reduce,
                send_ids=_freeze(np.stack([t[1] for t in tables[i:j]])),
                recv_ids=_freeze(np.stack([t[2] for t in tables[i:j]])),
            )
        )
        i = j
    return tuple(groups)


def compile_schedule(schedule: Schedule) -> CompiledSchedule:
    """Lower ``schedule`` to stacked round-group tables (memoized by
    :meth:`Schedule.fingerprint`).

    With ``PCCL_VERIFY=1`` in the environment, every schedule is first run
    through the static chunk-dataflow verifier
    (:func:`repro.analysis.verify.assert_verified`) — a compile-time proof
    of the collective's postcondition.  The check runs only on a cache
    miss (compiles are fingerprint-memoized) and the env var is read only
    on that miss, so the disabled path costs nothing.
    """
    fp = schedule.fingerprint()
    cached = _COMPILED.get(fp)
    if cached is not None:
        return cached
    if os.environ.get("PCCL_VERIFY", "0") not in ("", "0"):
        from repro.analysis.verify import assert_verified  # lazy: avoids cycle

        assert_verified(schedule)
    tables = [
        round_tables(rnd, schedule.n, ctx=_ctx(schedule, i))
        for i, rnd in enumerate(schedule.rounds)
    ]
    compiled = CompiledSchedule(
        fingerprint=fp,
        collective=schedule.collective,
        algorithm=schedule.algorithm,
        n=schedule.n,
        num_rounds=schedule.num_rounds,
        groups=_fold_groups(tables),
    )
    _COMPILED.put(fp, compiled)
    return compiled


# ------------------------------------------------ compact (O(n)) all-to-all


def compile_all_to_all(
    schedule: Schedule, m: int, local_of: Tuple[int, ...]
) -> Optional[CompiledSchedule]:
    """Slot-addressed all-to-all: O(m·blk) state instead of O(m²·blk).

    The dense path keeps an origin×target grid so any set of in-flight
    blocks can coexist; but every generated all-to-all schedule keeps at
    most ``m`` live blocks per rank, so ``m`` slots suffice.  This compile
    statically simulates the chunk metadata: each rank starts holding its
    ``m`` outgoing blocks dest-major (slot ``t`` = block for group-local
    rank ``t``, matching ``x.reshape(m, …)``), each round's sends vacate
    slots and its receives land on free ones (gather-before-scatter, so a
    slot sent from this round can be reused this round), and a final
    ``(len(local_of), m)`` table maps origins to slots for the post-pass
    gather.

    Args:
      schedule: an all_to_all schedule over ``len(local_of)`` ranks with
        group-local chunk ids ``o*m + t`` (full-axis: ``local_of`` is the
        identity and ``m == schedule.n``).
      m: group size (blocks per rank).
      local_of: global rank → group-local index.

    Returns ``None`` whenever the metadata cannot be slot-addressed — a
    sender not holding a chunk it sends, a duplicated live block, a reduce
    round, or an unmet post-condition — in which case callers use the
    dense path.  Memoized by ``(fingerprint, local_of)``; the sentinel for
    "checked, infeasible" is cached too so the simulation runs once.
    """
    n_rows = schedule.n
    if len(local_of) != n_rows:
        raise ScheduleExecutionError(
            f"local_of covers {len(local_of)} ranks, schedule has {n_rows}"
        )
    key = (schedule.fingerprint(), m, tuple(local_of))
    cached = _COMPILED.get(key)
    if cached is not None:
        return None if cached is _INFEASIBLE else cached

    compiled = _compile_all_to_all(schedule, m, tuple(local_of))
    _COMPILED.put(key, _INFEASIBLE if compiled is None else compiled)
    return compiled


def _compile_all_to_all(
    schedule: Schedule, m: int, local_of: Tuple[int, ...]
) -> Optional[CompiledSchedule]:
    n_rows = schedule.n
    # pos[r]: chunk id -> slot, for the blocks rank r currently holds
    pos: List[Dict[int, int]] = [
        {local_of[r] * m + t: t for t in range(m)} for r in range(n_rows)
    ]
    tables = []
    for i, rnd in enumerate(schedule.rounds):
        perm, send_ids, recv_ids, reduce = round_tables(
            rnd, n_rows, ctx=_ctx(schedule, i)
        )
        if reduce:
            return None  # all-to-all never reduces; metadata says otherwise
        k = send_ids.shape[1]
        send_slots = np.zeros((n_rows, k), dtype=np.int32)
        recv_slots = np.zeros((n_rows, k), dtype=np.int32)
        # gather phase: every send leaves its slot (frees it for this
        # round's receive — the executor gathers payloads before scattering)
        for t in rnd.transfers:
            for j, c in enumerate(t.chunks):
                slot = pos[t.src].pop(c, None)
                if slot is None:
                    return None  # sender does not hold this chunk
                send_slots[t.src, j] = slot
        # scatter phase: receives land on free slots, ascending order
        for t in rnd.transfers:
            held = set(pos[t.dst].values())
            free = [s for s in range(m) if s not in held]
            if len(t.chunks) > len(free):
                return None  # more live blocks than slots
            for j, c in enumerate(t.chunks):
                if c in pos[t.dst]:
                    return None  # duplicated live block
                pos[t.dst][c] = free[j]
                recv_slots[t.dst, j] = free[j]
        tables.append((perm, send_slots, recv_slots, False))

    final_slots = np.zeros((n_rows, m), dtype=np.int32)
    for r in range(n_rows):
        for o in range(m):
            slot = pos[r].get(o * m + local_of[r])
            if slot is None:
                return None  # post-condition unmet: block (o -> r) missing
            final_slots[r, o] = slot
    return CompiledSchedule(
        fingerprint=schedule.fingerprint(),
        collective=schedule.collective,
        algorithm=schedule.algorithm,
        n=n_rows,
        num_rounds=schedule.num_rounds,
        groups=_fold_groups(tables),
        final_slots=_freeze(final_slots),
    )


# ----------------------------------------------------- donation aliasing


def expected_eager_result_shape(
    collective: str, global_shape: Tuple[int, ...]
) -> Tuple[int, ...]:
    """Result shape of the eager path for a ``(axis_size, *local)`` operand.

    Purely structural — no tracing (the eager path's 0-retrace guarantee
    must survive the check).  Row ``r`` of the result is rank ``r``'s local
    output, so the leading axis is preserved and only the first local dim
    scales: reduce-scatter splits it ``n`` ways, all-gather concatenates
    ``n`` shards, all-reduce and all-to-all preserve it.
    """
    global_shape = tuple(int(d) for d in global_shape)
    n = global_shape[0]
    if collective in ("all_reduce", "all_to_all"):
        return global_shape
    if collective == "reduce_scatter":
        if len(global_shape) < 2 or n <= 0 or global_shape[1] % n:
            raise ScheduleExecutionError(
                f"reduce_scatter: local leading dim of {global_shape} not "
                f"divisible by axis size {n}"
            )
        return (n, global_shape[1] // n) + global_shape[2:]
    if collective == "all_gather":
        if len(global_shape) < 2:
            raise ScheduleExecutionError(
                f"all_gather: operand {global_shape} has no local dims"
            )
        return (n, global_shape[1] * n) + global_shape[2:]
    raise ScheduleExecutionError(f"unknown collective {collective!r}")


def donation_compatible(collective: str, global_shape: Tuple[int, ...]) -> bool:
    """May the eager executable donate operand 0 to XLA?

    Donation aliases the result buffer onto the input buffer, which is
    only sound when their whole-array footprints coincide — the same
    :class:`~repro.analysis.pallas_model.Box` model the kernel lint uses
    for ``input_output_aliases``, applied at the executable boundary.
    """
    from repro.analysis.pallas_model import whole_array_box  # lazy: no cycle

    try:
        out_shape = expected_eager_result_shape(collective, global_shape)
    except ScheduleExecutionError:
        return False
    return whole_array_box(tuple(global_shape)) == whole_array_box(out_shape)


# --------------------------------------------------------------- execution


_LANE = 128  # TPU vector lane width


def _lane_view(chunks):
    """``(k, m)`` chunk buffer → ``(k, m // _LANE, _LANE)`` where ``m`` allows.

    For flat chunk buffers: an all-reduce operand that is a scalar or 1-D,
    or runs ``ring_ef8`` or on a split communicator (one of two or more
    dimensions otherwise keeps its own layout,
    ``repro.api.backends._chunked_layout``).  A flat buffer split into
    ``k`` chunks is a 2-D array whose second-minor dimension is the small
    chunk count; the TPU compiler tiles that layout badly, and its compile
    time grows with ``m`` (about 25 s at 200 MB per chunk buffer on v5e).
    The 3-D view keeps every element in its chunk and position, so
    results stay bit-identical.
    """
    if chunks.ndim == 2 and chunks.shape[1] > _LANE and chunks.shape[1] % _LANE == 0:
        return chunks.reshape(chunks.shape[0], chunks.shape[1] // _LANE, _LANE)
    return chunks


_SCOPE = threading.local()  # the collective scope open on this thread's trace


@contextlib.contextmanager
def collective_scope(collective: str, algorithm: str):
    """Name the device ops traced inside ``pccl_<collective>_<algorithm>``.

    A ``jax.named_scope``: it sets HLO ``op_name`` metadata and nothing
    else, so it costs nothing at run time.  Opened again inside itself (the
    backend around the engine) it adds no second level.
    """
    import jax

    name = re.sub(r"\W", "_", f"pccl_{collective}_{algorithm}")
    outer = getattr(_SCOPE, "name", None)
    if outer == name:
        yield
        return
    _SCOPE.name = name
    try:
        with jax.named_scope(name):
            yield
    finally:
        _SCOPE.name = outer


def execute_compiled(
    chunks, compiled: CompiledSchedule, axis_name: str, *, me=None, operands=False
):
    """Run a compiled schedule on local chunk buffers inside ``shard_map``.

    ``chunks`` is one buffer, or a list or tuple of buffers that all run
    ``compiled``; the result has the same form.  With ``operands``, the
    buffers are the collective's operands, whose leading axes split into
    the schedule's ``n`` chunks (:func:`split_chunks`): the loop carries
    one of two or more dimensions in its own shape and views it as
    ``(n, rows / n, …)`` inside each round, a bitcast on the TPU, whose
    tiles span the two minor dimensions.  The carry then keeps the layout
    the operand's producer gave it; carried as chunks, it would take the
    default layout and need a copy to enter the loop.  A 1-D operand is
    split and runs as a flat chunk buffer.  Several buffers share one
    round loop: each round group runs once, and its body gathers, permutes
    and adds or sets every buffer, largest first.  An
    ``optimization_barrier`` holds each ``ppermute`` until the previous
    buffer's has landed, so one transfer is in flight at a time and the
    previous buffer's add runs under it.  (Left free, the TPU scheduler
    issues many transfers at once and blocks on a small one's done queued
    behind large ones.)  Each buffer still sees the same chunk ids,
    permutation and add order as alone.

    Bit-identical to the per-round reference interpreter: same gathers,
    same permutation per round, same scatter-add/store order.  ``me``
    defaults to ``lax.axis_index(axis_name)``; grouped callers that index
    their buffers with a *group-local* rank still pass nothing here — the
    tables are always row-indexed by the global axis index.  Flat chunk
    buffers run in their :func:`_lane_view`.  The ops of round group ``i``
    are named ``pccl_<collective>_<algorithm>/group<i>``.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    many = isinstance(chunks, (list, tuple))
    bufs = tuple(chunks) if many else (chunks,)
    if len(bufs) > 1:
        note_shared_loop(len(bufs))
    if me is None:
        me = lax.axis_index(axis_name)
    shapes = [b.shape for b in bufs]
    views = [None] * len(bufs)  # a buffer's chunks inside a round, if carried whole
    if operands:
        split = [split_chunks(b, compiled.n) for b in bufs]
        views = [c.shape if b.ndim >= 2 else None for b, c in zip(bufs, split)]
        bufs = tuple(b if b.ndim >= 2 else c for b, c in zip(bufs, split))

    # largest first: each transfer then lasts at least as long as the next
    # buffer's gather, and the previous buffer's add hides under it unless
    # that buffer is several times larger
    order = sorted(range(len(bufs)), key=lambda j: -bufs[j].size)

    def apply_round(carry, send, recv, grp):
        def land(buf, got):
            return buf.at[recv].add(got) if grp.reduce else buf.at[recv].set(got)

        bufs = [b if v is None else b.reshape(v) for b, v in zip(carry, views)]
        out, prev, got = list(bufs), None, None
        for j in order:
            payload = jnp.take(bufs[j], send, axis=0)
            if prev is not None:
                # one transfer in flight: this one starts when the last is
                # done, and the last one's add runs under it
                got, payload = lax.optimization_barrier((got, payload))
                out[prev] = land(bufs[prev], got)
            got, prev = lax.ppermute(payload, axis_name, grp.perm), j
        out[prev] = land(bufs[prev], got)
        return tuple(o.reshape(c.shape) for o, c in zip(out, carry))

    with collective_scope(compiled.collective, compiled.algorithm):
        bufs = tuple(b if v else _lane_view(b) for b, v in zip(bufs, views))
        for i, grp in enumerate(compiled.groups):
            with jax.named_scope(f"group{i}"):
                send = jnp.take(jnp.asarray(grp.send_ids), me, axis=1)  # (rounds, k)
                recv = jnp.take(jnp.asarray(grp.recv_ids), me, axis=1)
                if grp.rounds == 1:
                    bufs = apply_round(bufs, send[0], recv[0], grp)
                else:

                    def body(carry, sr, _grp=grp):
                        return apply_round(carry, sr[0], sr[1], _grp), None

                    bufs, _ = lax.scan(body, bufs, (send, recv))
        out = [b.reshape(shape) for b, shape in zip(bufs, shapes)]
        return type(chunks)(out) if many else out[0]


def split_chunks(x, n: int):
    """``x`` as ``n`` chunks along its leading axis: ``(n, rows / n, …)``."""
    if x.shape[0] % n:
        raise ScheduleExecutionError(
            f"leading dim {x.shape[0]} not divisible by {n} ranks"
        )
    return x.reshape((n, x.shape[0] // n) + x.shape[1:])


def execute_all_to_all_compact(blocks, compiled: CompiledSchedule, axis_name: str, me):
    """Slot-compiled all-to-all: run the rounds, then gather origin-major.

    ``blocks`` is the (m, blk, …) dest-major local buffer; the return is
    (m, blk, …) origin-major.  Shared by the full-axis and grouped paths
    so the slot-gather epilogue exists exactly once.
    """
    import jax.numpy as jnp

    out = execute_compiled(blocks, compiled, axis_name, me=me)
    sel = jnp.take(jnp.asarray(compiled.final_slots), me, axis=0)  # (m,)
    return jnp.take(out, sel, axis=0)


# ------------------------------------------------------- caches & counters


class _LruCache:
    """Lock-guarded bounded LRU with hit/miss/eviction accounting."""

    def __init__(self, max_entries: int) -> None:
        self._store: "OrderedDict[Any, Any]" = OrderedDict()
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key):
        with self._lock:
            val = self._store.get(key)
            if val is not None:
                self.hits += 1
                self._store.move_to_end(key)
            else:
                self.misses += 1
            return val

    def put(self, key, value) -> None:
        with self._lock:
            self._store[key] = value
            self._store.move_to_end(key)
            while len(self._store) > self.max_entries:
                self._store.popitem(last=False)
                self.evictions += 1

    def clear(self) -> None:
        with self._lock:
            self._store.clear()
            self.hits = self.misses = self.evictions = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._store)


_INFEASIBLE = object()  # cached "slot compile checked and rejected" sentinel

_COMPILED = _LruCache(max_entries=256)  # fingerprint → CompiledSchedule
EXECUTABLES = _LruCache(max_entries=128)  # exec key → jitted callable

_TRACE_LOCK = threading.Lock()
_TRACES = 0
_SHARED_LOOP_BUFFERS = 0
_LAYOUT_KEPT_BUFFERS = 0

# Dispatch counters, filled by repro.comm.fusion: dispatches that streamed
# producer tiles into collective rounds vs. took the sequential fallback.
# What communication a dispatch hides is device time, which only a
# profiler trace measures; these count dispatches.
_OVERLAP_LOCK = threading.Lock()
_FUSED_DISPATCHES = 0
_FALLBACK_DISPATCHES = 0


def note_trace() -> None:
    """Record one trace through the engine (Python body of a jitted path)."""
    global _TRACES
    with _TRACE_LOCK:
        _TRACES += 1


def note_shared_loop(buffers: int) -> None:
    """Record ``buffers`` buffers traced through one shared round loop."""
    global _SHARED_LOOP_BUFFERS
    with _TRACE_LOCK:
        _SHARED_LOOP_BUFFERS += buffers


def note_layout_kept(buffers: int) -> None:
    """Record ``buffers`` all-reduce operands chunked in their own layout."""
    global _LAYOUT_KEPT_BUFFERS
    with _TRACE_LOCK:
        _LAYOUT_KEPT_BUFFERS += buffers


def note_fused_dispatch() -> None:
    """Record one fused (comm-under-compute) dispatch."""
    global _FUSED_DISPATCHES
    with _OVERLAP_LOCK:
        _FUSED_DISPATCHES += 1


def note_fallback_dispatch() -> None:
    """Record one dispatch where fusion was requested but fell back."""
    global _FALLBACK_DISPATCHES
    with _OVERLAP_LOCK:
        _FALLBACK_DISPATCHES += 1


@dataclass(frozen=True)
class ExecStats:
    """Process-wide execution-engine counters (see ``exec_stats()``)."""

    executable_hits: int
    executable_misses: int
    executable_size: int
    compiled_hits: int
    compiled_misses: int
    compiled_size: int
    traces: int
    fused_dispatches: int = 0
    fallback_dispatches: int = 0
    shared_loop_buffers: int = 0
    layout_kept_buffers: int = 0


def exec_stats() -> ExecStats:
    """Snapshot of the engine's process-wide caches and trace counter.

    * ``executable_*`` — the jitted-executable cache the eager interp path
      fills (key: schedule fingerprint, global shape, dtype, axis name,
      group fingerprint).
    * ``compiled_*`` — the schedule→stacked-tables compile cache.
    * ``traces`` — how many times a Python trace actually ran; a warm
      steady state stops incrementing it.
    * ``shared_loop_buffers`` — buffers traced through a round loop shared
      with at least one other buffer (:func:`execute_compiled` given
      several); counted at trace time, like ``traces``.
    * ``layout_kept_buffers`` — all-reduce operands that entered the round
      loop in their own shape, chunked along the leading axis, instead of
      flattened (``repro.api.backends._chunked_layout``); at trace time.
    * ``fused_*``/``fallback_*`` — dispatch counters from
      ``repro.comm.fusion`` (see :func:`note_fused_dispatch`).
    """
    with _TRACE_LOCK:
        traces, shared, kept = _TRACES, _SHARED_LOOP_BUFFERS, _LAYOUT_KEPT_BUFFERS
    with _OVERLAP_LOCK:
        fused, fallback = _FUSED_DISPATCHES, _FALLBACK_DISPATCHES
    return ExecStats(
        executable_hits=EXECUTABLES.hits,
        executable_misses=EXECUTABLES.misses,
        executable_size=len(EXECUTABLES),
        compiled_hits=_COMPILED.hits,
        compiled_misses=_COMPILED.misses,
        compiled_size=len(_COMPILED),
        traces=traces,
        fused_dispatches=fused,
        fallback_dispatches=fallback,
        shared_loop_buffers=shared,
        layout_kept_buffers=kept,
    )


def clear_exec_caches() -> None:
    """Drop compiled tables + executables and zero all counters (tests).

    Also clears the ``PCCL_VERIFY=1`` per-dispatch kernel-analysis memo
    (``repro.analysis.kernel_lint._VERIFIED``) so tests that toggle the env
    var cannot see stale verdicts — but only when that module is already
    loaded: importing it here would pull JAX into planning-/sim-only
    processes that this module deliberately keeps JAX-free.
    """
    global _TRACES, _SHARED_LOOP_BUFFERS, _LAYOUT_KEPT_BUFFERS
    global _FUSED_DISPATCHES, _FALLBACK_DISPATCHES
    _COMPILED.clear()
    EXECUTABLES.clear()
    with _TRACE_LOCK:
        _TRACES = _SHARED_LOOP_BUFFERS = _LAYOUT_KEPT_BUFFERS = 0
    with _OVERLAP_LOCK:
        _FUSED_DISPATCHES = _FALLBACK_DISPATCHES = 0
    lint = sys.modules.get("repro.analysis.kernel_lint")
    if lint is not None:
        lint.clear_verified_cache()
