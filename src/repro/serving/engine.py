"""Diffusion serving engine — a thin facade over the continuous-batching
scheduler (``repro.serving.scheduler``, SERVING.md).

Requests carry a ``task`` tag; the engine keeps ONE
:class:`~repro.core.osdt.CalibrationStore` (task → calibrated threshold
table — the paper's observation O2 says the confidence signature is a
*task-level* property) and ONE compiled decode program. Mixed-task batches
are the normal case: each slot's table is gathered per row at runtime.

``submit()`` is the synchronous compatibility surface: enqueue, drain, and
return responses in uid order. Callers that want boundary-granularity
control (``Scheduler.slice_step``: admit, one slice, retire) should drive
the scheduler directly.

Throughput accounting (``EngineStats``): NFE (model forwards — the
hardware-independent driver), *delivered* tokens (post-EOS truncation; a
request that stops early is not credited ``max_new_tokens``), and
per-request wall = its own queue wait + the walls of the slices it was
actually decoded in. Under the paged KV layout (``DecodeConfig.cache_layout=
"paged"``, SERVING.md "Paged KV") the stats additionally surface
page-pool occupancy: ``page_capacity``, ``pages_peak`` / ``page_util``,
``pages_shared``, ``pages_freed``.

The scheduler runs the STEP-SLICED decode loop (SERVING.md "Async
admission"): requests admit into freed slots mid-generation, EOS
retirement reclaims pages at slice boundaries, and the latency split is
slice-granular — ``Response.ttfb_s`` (submit → first decoded block) plus
``queue_s``/``decode_s`` measured at the boundaries the row actually
crossed.

With ``EngineConfig.data_parallel`` / ``model_parallel`` > 1 the
scheduler runs SPMD over a ``("data", "model")`` device mesh
(SERVING.md "Sharded serving"): slots partition into per-data-shard
groups, the decode carry and paged pool carry NamedShardings, and
weights route through the TP "serve" specs. ``DiffusionEngine.mesh``
exposes the mesh (``None`` for the 1x1 single-device runtime).
"""
from __future__ import annotations

from typing import Dict, List, Optional

from repro.config.base import DecodeConfig, EngineConfig, ModelConfig
from repro.core.osdt import CalibrationStore, TaskView
from repro.data import tokenizer as tok
from repro.serving.scheduler import (EngineStats, Request, Response,
                                     Scheduler)

__all__ = ["DiffusionEngine", "EngineStats", "Request", "Response",
           "TaskView"]


class DiffusionEngine:
    def __init__(self, params, cfg: ModelConfig, dcfg: DecodeConfig, *,
                 ecfg: Optional[EngineConfig] = None,
                 store: Optional[CalibrationStore] = None,
                 mask_id: int = tok.MASK_ID, eos_id: int = tok.EOS_ID):
        """``dcfg`` carries the decode program's choices (attention path,
        cache layout, weight dtype — pass ``attn_impl="kernel"`` when
        serving on TPU, KERNELS.md); ``ecfg`` the scheduler knobs (batch
        geometry, cache mode, EOS early exit, calibration persistence —
        see ``EngineConfig``; defaults when absent)."""
        ecfg = ecfg if ecfg is not None else EngineConfig()
        self.params = params
        self.cfg = cfg
        self.dcfg = dcfg
        self.ecfg = ecfg
        self.scheduler = Scheduler(params, cfg, dcfg, ecfg=ecfg,
                                   store=store, mask_id=mask_id,
                                   eos_id=eos_id)

    # -- compat / convenience surface -----------------------------------
    @property
    def store(self) -> CalibrationStore:
        return self.scheduler.store

    @property
    def stats(self) -> EngineStats:
        return self.scheduler.stats

    @property
    def obs(self):
        """The scheduler's :class:`repro.obs.Observability` bundle
        (tracer, metrics registry, drift monitor)."""
        return self.scheduler.obs

    @property
    def mesh(self):
        """The scheduler's serving mesh (``jax.sharding.Mesh``), or
        ``None`` when data_parallel == model_parallel == 1."""
        return self.scheduler.mesh

    @property
    def sessions(self) -> Dict[str, TaskView]:
        """task → read-only calibration view, for every task ever admitted."""
        return {t: TaskView(self.store, t)
                for t in self.scheduler.seen_tasks}

    def submit(self, requests: List[Request]) -> List[Response]:
        """Synchronous drain: enqueue, run to completion, uid order."""
        self.scheduler.submit(requests)
        out = self.scheduler.run()
        out.sort(key=lambda r: r.uid)
        return out
