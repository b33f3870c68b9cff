"""Serving engine: batched prefill + decode with KV cache / recurrent state.

A minimal continuous-batching-shaped engine: requests are admitted into a
fixed-size batch, prefilled together, then decoded step-by-step; finished
sequences free their slots.  The decode step is the model's jitted
``decode_step``.

With ``EngineConfig.tp > 1`` the engine also accounts for the tensor-parallel
activation all-reduces through the PCCL session API (``sim`` backend: the
exact Communicator the training path uses, priced by the planner with no
devices needed) — ``engine.comm_report()`` returns the planned per-token
communication time and algorithm.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import PcclSession
from repro.configs.base import ModelConfig
from repro.core import cost_model as cm
from repro.models import build_model
from repro.models.module import unbox


@dataclass
class Request:
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 16
    generated: List[int] = field(default_factory=list)
    done: bool = False


@dataclass(frozen=True)
class ModelSection:
    """Decoding-policy knobs: how tokens are sampled from the model."""

    greedy: bool = True


@dataclass(frozen=True)
class RuntimeSection:
    """Batching/KV-cache shape: how many sequences share the engine."""

    batch_size: int = 4
    max_len: int = 256

    def __post_init__(self) -> None:
        if self.batch_size < 1:
            raise ValueError(
                f"RuntimeSection.batch_size must be >= 1, got {self.batch_size}"
            )
        if self.max_len < 1:
            raise ValueError(
                f"RuntimeSection.max_len must be >= 1, got {self.max_len}"
            )
        if self.batch_size > self.max_len:
            raise ValueError(
                f"RuntimeSection: batch_size={self.batch_size} exceeds the "
                f"max_len={self.max_len} KV slots one sequence owns — the "
                f"engine cannot admit more sequences than slots"
            )


@dataclass(frozen=True)
class FabricSection:
    """Parallelism layout on the shared photonic fabric."""

    tp: int = 1                 # tensor-parallel degree priced via PCCL
    dp: int = 1                 # data-parallel replicas sharing the fabric
    mesh_n: Optional[int] = None  # fabric domain size; defaults to tp·dp

    def __post_init__(self) -> None:
        if self.tp < 1:
            raise ValueError(f"FabricSection.tp must be >= 1, got {self.tp}")
        if self.dp < 1:
            raise ValueError(f"FabricSection.dp must be >= 1, got {self.dp}")
        if self.mesh_n is not None and self.mesh_n != self.tp * self.dp:
            raise ValueError(
                f"FabricSection: tp*dp = {self.tp}*{self.dp} = "
                f"{self.tp * self.dp} does not cover mesh_n={self.mesh_n} "
                f"fabric ranks — fix tp/dp or drop mesh_n"
            )

    @property
    def n(self) -> int:
        """The fabric domain size every plan spans."""
        return self.mesh_n if self.mesh_n is not None else self.tp * self.dp


class EngineConfig:
    """Sectioned engine configuration with construction-time validation.

    Three frozen sections — :class:`ModelSection` (decoding policy),
    :class:`RuntimeSection` (batching/KV shape), :class:`FabricSection`
    (parallelism layout) — each validating its own invariants so a bad
    config raises an attributable ``ValueError`` at construction instead of
    failing deep inside planning.  The historical flat surface is kept
    intact both ways: flat constructor kwargs
    (``EngineConfig(batch_size=2, tp=4)``) build the sections, and flat
    attributes (``cfg.batch_size`` …) read through to them.  Pass whole
    sections for anything beyond the defaults::

        EngineConfig(runtime=RuntimeSection(8, 4096),
                     fabric=FabricSection(tp=8, dp=4, mesh_n=32))
    """

    def __init__(
        self,
        batch_size: Optional[int] = None,
        max_len: Optional[int] = None,
        greedy: Optional[bool] = None,
        tp: Optional[int] = None,
        dp: Optional[int] = None,
        *,
        model: Optional[ModelSection] = None,
        runtime: Optional[RuntimeSection] = None,
        fabric: Optional[FabricSection] = None,
    ) -> None:
        if runtime is not None and (batch_size is not None or max_len is not None):
            raise ValueError(
                "EngineConfig: pass runtime= or flat batch_size/max_len, not both"
            )
        if model is not None and greedy is not None:
            raise ValueError("EngineConfig: pass model= or flat greedy, not both")
        if fabric is not None and (tp is not None or dp is not None):
            raise ValueError("EngineConfig: pass fabric= or flat tp/dp, not both")
        self.model = model if model is not None else ModelSection(
            greedy=True if greedy is None else greedy
        )
        self.runtime = runtime if runtime is not None else RuntimeSection(
            batch_size=4 if batch_size is None else batch_size,
            max_len=256 if max_len is None else max_len,
        )
        self.fabric = fabric if fabric is not None else FabricSection(
            tp=1 if tp is None else tp, dp=1 if dp is None else dp
        )

    # ------------------------------------------------- flat read-through
    @property
    def greedy(self) -> bool:
        return self.model.greedy

    @property
    def batch_size(self) -> int:
        return self.runtime.batch_size

    @property
    def max_len(self) -> int:
        return self.runtime.max_len

    @property
    def tp(self) -> int:
        return self.fabric.tp

    @property
    def dp(self) -> int:
        return self.fabric.dp

    def __repr__(self) -> str:
        return (
            f"EngineConfig(model={self.model!r}, runtime={self.runtime!r}, "
            f"fabric={self.fabric!r})"
        )

    def __eq__(self, other: object) -> bool:
        if not isinstance(other, EngineConfig):
            return NotImplemented
        return (self.model, self.runtime, self.fabric) == (
            other.model, other.runtime, other.fabric
        )

    def __hash__(self) -> int:
        return hash((self.model, self.runtime, self.fabric))


class ServeEngine:
    def __init__(self, cfg: ModelConfig, engine_cfg: EngineConfig,
                 params: Optional[Any] = None, seed: int = 0,
                 session: Optional[PcclSession] = None):
        self.cfg = cfg
        self.ecfg = engine_cfg
        self._arbiter = None
        self.model = build_model(cfg)
        self.params = params if params is not None else unbox(
            self.model.init(jax.random.PRNGKey(seed))
        )
        # PCCL communication accounting (sim backend: plans, no devices)
        self.pccl = session
        self.comm = None
        if engine_cfg.tp > 1:
            self.pccl = self.pccl or PcclSession(cm.TPU_V5E_PHOTONIC)
            self.comm = self.pccl.communicator("model", engine_cfg.tp, backend="sim")
            self._act = np.zeros((engine_cfg.batch_size, cfg.d_model), np.float32)
        import functools

        self._prefill = jax.jit(
            functools.partial(self.model.prefill, max_len=engine_cfg.max_len)
        )
        self._decode = jax.jit(self.model.decode_step)

    def _charge_tp_step(self, seq_len: int = 1) -> None:
        """Price one model step's TP collectives: two partial-sum activation
        all-reduces per layer (attention out-proj + MLP down-proj).  Decode
        moves a (batch, d_model) activation; prefill moves the full
        (batch, seq_len, d_model) prompt activation."""
        if self.comm is None:
            return
        act = (
            self._act
            if seq_len <= 1
            else np.broadcast_to(self._act, (seq_len, *self._act.shape))
        )
        for _ in range(2 * self.cfg.n_layers):
            self.comm.all_reduce(act)

    def comm_report(self) -> Dict[str, Any]:
        """Planned TP communication accounting for this engine's lifetime.

        ``exec`` carries the execution-engine counters (executable-cache
        hits/misses, traces); zeros under the ``sim`` backend, live numbers
        when an engine is wired to an ``interp`` communicator."""
        if self.comm is None:
            return {"tp": 1, "sim_comm_s": 0.0, "algorithm": "none", "events": 0}
        report = {
            "tp": self.ecfg.tp,
            "sim_comm_s": self.comm.sim_elapsed_s,
            "algorithm": self.comm.chosen_algorithm(
                "all_reduce", self._act.size * 4
            ),
            "events": len(self.comm.backend.events),
            "exec": self.pccl.exec_stats(),
        }
        if self.ecfg.dp > 1:
            report["concurrent"] = self.concurrent_report()
        return report

    def arbiter(self, cfg: Optional[Any] = None) -> Any:
        """The engine's online fabric arbiter (lazily built, then shared).

        Returns a :class:`repro.serve.arbiter.FabricArbiter` bound to this
        engine's session and ``tp × dp`` layout; pass an
        :class:`~repro.serve.arbiter.ArbiterConfig` to rebuild with
        different control-plane policy.
        """
        from repro.serve.arbiter import FabricArbiter

        if self._arbiter is None or cfg is not None:
            self.pccl = self.pccl or PcclSession(cm.TPU_V5E_PHOTONIC)
            self._arbiter = FabricArbiter(
                self.pccl, tp=self.ecfg.tp, dp=self.ecfg.dp,
                d_model=self.cfg.d_model, cfg=cfg,
            )
        return self._arbiter

    def concurrent_report(self) -> Dict[str, Any]:
        """Joint fabric pricing for a continuous-batching step with ``dp``
        replicas on one photonic fabric: the prefill TP all-reduces (full
        ``(batch, max_len, d_model)`` prompt activation, within each
        replica's TP group) run *concurrently* with the decode-side DP
        all-gather (per-token activations exchanged across replicas).  The
        arbiter overlaps the two axes with per-link contention pricing;
        ``speedup`` is the planned gain over pricing each collective as if
        it owned the fabric (sequential baseline).  Pricing goes through
        :meth:`arbiter`, the same control plane that runs the online
        admission/preemption loop (see ``repro.serve.arbiter``).
        """
        tp, dp = self.ecfg.tp, self.ecfg.dp
        if tp < 2 or dp < 2:
            return {"tp": tp, "dp": dp, "speedup": 1.0, "serialized": False}
        prefill_bytes = 4.0 * self.ecfg.batch_size * self.ecfg.max_len * self.cfg.d_model
        decode_bytes = 4.0 * self.ecfg.batch_size * self.cfg.d_model
        cp = self.arbiter().price_joint(prefill_bytes, decode_bytes)
        return {
            "tp": tp,
            "dp": dp,
            "joint_s": cp.cost,
            "sequential_s": cp.sequential_cost,
            "speedup": cp.speedup,
            "serialized": cp.serialized,
            "algorithms": cp.algorithms,
        }

    def _extra_inputs(self, B: int) -> Dict[str, jax.Array]:
        out = {}
        if self.cfg.vlm:
            out["img_embeds"] = jnp.zeros(
                (B, self.cfg.vlm.n_img_tokens, self.cfg.d_model), jnp.float32
            )
        if self.cfg.enc_dec:
            out["enc_frames"] = jnp.zeros(
                (B, self.cfg.enc_dec.enc_seq, self.cfg.d_model), jnp.float32
            )
        return out

    def generate(self, requests: List[Request]) -> List[Request]:
        """Serve a batch of requests to completion (prefill + decode loop)."""
        B = self.ecfg.batch_size
        assert len(requests) <= B
        S = max(len(r.prompt) for r in requests)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(requests):
            toks[i, S - len(r.prompt):] = r.prompt  # left-pad
        batch = {"tokens": jnp.asarray(toks), **self._extra_inputs(B)}
        logits, state = self._prefill(self.params, batch)
        self._charge_tp_step(seq_len=S)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
        for i, r in enumerate(requests):
            r.generated.append(int(nxt[i, 0]))

        max_new = max(r.max_new_tokens for r in requests)
        for t in range(max_new - 1):
            logits, state = self._decode(self.params, state, nxt)
            self._charge_tp_step()
            nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]
            for i, r in enumerate(requests):
                if len(r.generated) < r.max_new_tokens:
                    r.generated.append(int(nxt[i, 0]))
        for r in requests:
            r.done = True
        return requests
