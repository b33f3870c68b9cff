"""Executable PCCL collectives: schedules → ``jax.lax.ppermute`` rounds.

This is the TPU-native realization of PCCL's "one circuit set per round"
(DESIGN.md §2): every :class:`~repro.core.schedules.Round` of a schedule is a
permutation (each rank ≤1 Tx, ≤1 Rx — the paper's per-tile transmitter
constraint), so it lowers to exactly one ``ppermute`` whose permutation *is*
the circuit set PCCL would program on the photonic fabric.

``execute_schedule`` is the hot path: it hands the schedule to the compiled
execution engine (:mod:`repro.comm.exec_engine`), which derives all static
per-round tables once (memoized process-wide by ``Schedule.fingerprint()``)
and folds runs of rounds sharing a permutation into a single ``lax.scan`` —
same chunk metadata, same add order, bit-identical outputs, O(round-groups)
trace size.  ``execute_schedule_reference`` keeps the original per-round
interpreter as the engine's equivalence oracle (tests).

``all_to_all`` uses the engine's slot-addressed compile: local state is one
``(n, blk)`` buffer — O(n·blk) memory — whenever the chunk metadata admits
one live block per slot (every generated all-to-all schedule does; asserted
statically at compile time).  ``all_to_all_dense`` keeps the original
origin×target O(n²·blk) state as the fallback and cross-check path.

Requirements on the schedule (all generators in ``core.schedules`` satisfy
them; asserted at trace time):
* every round is a permutation in which **every** rank sends, and
* within a round all ranks send the same number of chunks.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.schedules import Round, Schedule

from .errors import ScheduleExecutionError
from .exec_engine import (
    compile_all_to_all,
    compile_schedule,
    execute_all_to_all_compact,
    execute_compiled,
    round_tables,
    split_chunks,
)


def _round_tables(
    rnd: Round, n: int, *, ctx: str = ""
) -> Tuple[List[Tuple[int, int]], np.ndarray, np.ndarray, bool]:
    """Static per-round tables (see :func:`repro.comm.exec_engine.round_tables`)."""
    return round_tables(rnd, n, ctx=ctx)


def execute_schedule(chunks, schedule: Schedule, axis_name: str):
    """Run a schedule's rounds on a local chunk buffer inside ``shard_map``.

    Args:
      chunks: (n_chunks, *chunk_shape) local buffer; chunk ids as in the
        schedule (RS/AG: n_chunks == n; AllToAll: n_chunks == n with id
        src*n+dst mapped to local block dst — see callers).  A list of
        such buffers shares one round loop and gives a list back.
      schedule: permutation-round schedule from ``repro.core.schedules``.
      axis_name: mesh axis of size ``schedule.n``.

    Returns the updated local chunk buffer.  Compiles the schedule once
    (process-wide memo) and runs the fused engine — bit-identical to
    :func:`execute_schedule_reference`.
    """
    return execute_compiled(chunks, compile_schedule(schedule), axis_name)


def execute_schedule_reference(
    chunks: jax.Array, schedule: Schedule, axis_name: str
) -> jax.Array:
    """Pre-engine per-round interpreter — the engine's bit-identity oracle.

    Re-derives static tables per round per trace and emits one ppermute +
    scatter pair per round with no fusion.  Kept for equivalence tests;
    use ``execute_schedule``.
    """
    n = schedule.n
    me = lax.axis_index(axis_name)
    for i, rnd in enumerate(schedule.rounds):
        ctx = f"{schedule.collective}/{schedule.algorithm} round {i}/{schedule.num_rounds}: "
        perm, send_ids, recv_ids, reduce = round_tables(rnd, n, ctx=ctx)
        my_send = jnp.take(jnp.asarray(send_ids), me, axis=0)       # (k,)
        my_recv = jnp.take(jnp.asarray(recv_ids), me, axis=0)       # (k,)
        payload = jnp.take(chunks, my_send, axis=0)                 # (k, …)
        got = lax.ppermute(payload, axis_name, perm)
        if reduce:
            chunks = chunks.at[my_recv].add(got)
        else:
            chunks = chunks.at[my_recv].set(got)
    return chunks


# --------------------------------------------------------------------------
# Collective wrappers (call inside shard_map over `axis_name`).
# --------------------------------------------------------------------------


def reduce_scatter(x: jax.Array, schedule: Schedule, axis_name: str) -> jax.Array:
    """x: full per-rank buffer (each rank holds its own addend).
    Returns this rank's fully reduced chunk (1/n of the buffer)."""
    n = schedule.n
    chunks = split_chunks(x, n)
    chunks = execute_schedule(chunks, schedule, axis_name)
    me = lax.axis_index(axis_name)
    return jnp.take(chunks, me, axis=0)


def all_gather(x: jax.Array, schedule: Schedule, axis_name: str) -> jax.Array:
    """x: this rank's shard. Returns the concatenated full buffer."""
    n = schedule.n
    me = lax.axis_index(axis_name)
    chunks = jnp.zeros((n,) + x.shape, x.dtype).at[me].set(x)
    chunks = execute_schedule(chunks, schedule, axis_name)
    return chunks.reshape((n * x.shape[0],) + x.shape[1:])


def all_reduce(x, schedule: Schedule, axis_name: str):
    """x: full per-rank buffer. Returns sum over ranks, replicated.
    The schedule must be an all_reduce composition (RS rounds + AG rounds).
    ``x`` may be a list of buffers: they share one round loop, each with
    the result it would get alone, and a list comes back.  A buffer of two
    or more dimensions is carried through the loop in its own shape
    (``execute_compiled(..., operands=True)``)."""
    return execute_compiled(x, compile_schedule(schedule), axis_name, operands=True)


def all_to_all(x: jax.Array, schedule: Schedule, axis_name: str) -> jax.Array:
    """x: (n*blk, …) where block j is this rank's payload for rank j.
    Returns (n*blk, …) where block j is the payload received from rank j.

    Chunk ids in all_to_all schedules are ``src*n + dst``.  The engine's
    slot-addressed compile keeps local state at one (n, blk, …) buffer —
    O(n·blk) memory — assigning every in-flight block a live slot from the
    static chunk metadata; schedules whose metadata cannot be
    slot-addressed fall back to :func:`all_to_all_dense`.
    """
    n = schedule.n
    compact = compile_all_to_all(schedule, n, tuple(range(n)))
    if compact is None:
        return all_to_all_dense(x, schedule, axis_name)
    blocks = split_chunks(x, n)                       # (n, blk, …) dest-major
    me = lax.axis_index(axis_name)
    return execute_all_to_all_compact(blocks, compact, axis_name, me).reshape(x.shape)


def run_reference(
    collective: str, x: jax.Array, schedule: Schedule, axis_name: str
) -> jax.Array:
    """Whole-collective pre-engine interpreter — the bit-identity oracle.

    The original wrappers verbatim over :func:`execute_schedule_reference`
    (dense all-to-all state included); shared by the equivalence tests so
    the oracle exists exactly once.
    """
    n = schedule.n
    me = lax.axis_index(axis_name)
    if collective == "reduce_scatter":
        chunks = split_chunks(x, n)
        chunks = execute_schedule_reference(chunks, schedule, axis_name)
        return jnp.take(chunks, me, axis=0)
    if collective == "all_gather":
        chunks = jnp.zeros((n,) + x.shape, x.dtype).at[me].set(x)
        chunks = execute_schedule_reference(chunks, schedule, axis_name)
        return chunks.reshape((n * x.shape[0],) + x.shape[1:])
    if collective == "all_reduce":
        chunks = split_chunks(x, n)
        chunks = execute_schedule_reference(chunks, schedule, axis_name)
        return chunks.reshape(x.shape)
    if collective == "all_to_all":
        blocks = split_chunks(x, n)
        state = jnp.zeros((n, n) + blocks.shape[1:], blocks.dtype)
        state = state.at[me].set(blocks)
        flat = state.reshape((n * n,) + blocks.shape[1:])
        flat = execute_schedule_reference(flat, schedule, axis_name)
        state = flat.reshape((n, n) + blocks.shape[1:])
        return jnp.take(state, me, axis=1).reshape(x.shape)
    raise ScheduleExecutionError(f"unknown collective {collective!r}")


def all_to_all_dense(x: jax.Array, schedule: Schedule, axis_name: str) -> jax.Array:
    """Dense-state all-to-all: the pre-engine fallback and cross-check path.

    Keeps a full n×n-addressable buffer indexed by origin — O(n²·blk)
    memory, but exact for *any* schedule semantics (arbitrarily many blocks
    in flight from different origins can coexist at one rank)."""
    n = schedule.n
    blocks = split_chunks(x, n)                       # (n, blk, …) dest-major
    me = lax.axis_index(axis_name)
    # state[o, t] = block from origin o to target t, held locally (zeros if
    # not present). Initially we hold row `me`.
    state = jnp.zeros((n, n) + blocks.shape[1:], blocks.dtype)
    state = state.at[me].set(blocks)
    flat = state.reshape((n * n,) + blocks.shape[1:])
    flat = execute_schedule(flat, schedule, axis_name)
    state = flat.reshape((n, n) + blocks.shape[1:])
    # post-condition: we hold (o -> me) for every origin o
    return jnp.take(state, me, axis=1).reshape(x.shape)
