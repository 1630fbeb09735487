"""Slot-based continuous-batching scheduler for diffusion decoding.

The decode program is compiled ONCE per engine (fixed ``[batch_size,
prompt_len]`` carry shapes); everything that varies per request rides as
runtime state in the decode carry — the per-slot threshold table
``[B, nb, steps_cap]`` gathered from the
:class:`~repro.core.osdt.CalibrationStore`, the per-slot ``live`` mask,
the per-slot block cursor, and the EOS id. That is what lets a
*mixed-task* request stream share one executable: OSDT's table is a
task-level artifact, and here every row of a batch may belong to a
different task.

Lifecycle (SERVING.md), one ``slice_step()`` per slice boundary:

  QUEUED --admit--> ACTIVE --slice x N--> RETIRED (response emitted)

At each boundary queued requests are admitted into FREE slots — also
while other rows are mid-generation, each with its own block cursor —
and prefilled by one batched admission program over the admitted rows;
then ONE compiled slice program decodes ``slice_len`` blocks of every
live row; rows whose cursor ran out or that EOS-retired are retired and
their pages reclaimed immediately. Free slots ride along with
``live=False`` and cost no denoising steps.

Each uncalibrated task's first admitted request — whatever slot it lands
in — becomes that task's one-shot calibration (paper Algorithm 1): its
row's confidence recording is ingested at retirement, and several new
tasks may calibrate at once. Extra requests of a not-yet-calibrated task
ride along on the static table.

With ``DecodeConfig.cache_layout == "paged"`` the scheduler is the PAGE
OWNER (SERVING.md "Paged KV"): it holds the device page pool and a host
:class:`~repro.models.cache.PageAllocator`. Admission COW-forks each
request off the shared system-prompt parent (``PageAllocator.fork``:
refcount-map the shared pages read-only, allocate private pages only for
the logical range the row writes), retirement releases the fork, and a
request is admissible as soon as enough *pages* — not a whole dense
slot — are free. Free slots map no pages at all.
``EngineConfig.shared_prefix`` is prefilled ONCE into refcounted pages at
engine construction; every slot's page table then maps those pages
read-only (copy-on-write boundaries are page-aligned, so decode writes
never touch them).

With ``EngineConfig.prefix_cache`` the scheduler additionally owns a
:class:`~repro.models.cache.RadixPrefixCache` next to the allocator
(SERVING.md "Radix prefix cache"): admission walks the tree for the
longest page-aligned match on the row's ``shared_prefix + prefix``
stream, ``share()``s the matched pages into the row's page table, and
prefills only the novel remainder through a per-row composed forward
(``prefix_len``); retirement promotes the row's immutable prompt pages
back into the tree so the cache warms itself from live traffic, and an
LRU over tree-only nodes evicts under page pressure BEFORE admission
load-shedding.

With ``EngineConfig.spec_decode`` the scheduler also owns the DRAFT
lifecycle (SERVING.md "Speculative drafting"): the slice program is the
``variant="draft"`` executable, a :class:`~repro.spec.drafter.Drafter`
turns each calibrated task's stored profile into the ``draft_mask`` of
the rows admitted at a boundary, accepted blocks' pages merge back into
the row's committed KV, rejected blocks decode through the stepped loop,
and ``EngineStats`` gains the acceptance counters.
"""
from __future__ import annotations

import os
import time
from collections import deque
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import DecodeConfig, EngineConfig, ModelConfig
from repro.core.calibrate import CalibrationProfile
from repro.core.decoder import (DecodeCarry, admit_carry_rows, bucket_rows,
                                init_decode_carry, make_admit_fn,
                                make_slice_fn, retire_carry_rows)
from repro.core.osdt import CalibrationStore
from repro.data import tokenizer as tok
from repro.launch.mesh import make_serving_mesh
from repro.models import model as M
from repro.models.cache import RadixPrefixCache, ShardedPageAllocator
from repro.sharding.ctx import place_serving_params
from repro.models.quantize import (WEIGHT_DTYPES, decode_weight_bytes,
                                   is_quantized, quantize_decode_params)
from repro.obs import Observability
from repro.obs.metrics import MetricsRegistry
from repro.spec.drafter import Drafter


@dataclass
class Request:
    uid: int
    task: str
    prompt: str
    # cacheable prompt prefix (tenant system prompt, few-shot template,
    # resubmitted history): under EngineConfig.prefix_cache the radix
    # tree deduplicates its KV pages across requests. The decoded row is
    # always ``shared_prefix + prefix + prompt`` — engines WITHOUT the
    # cache lay the row out identically and simply prefill it whole, so
    # oracle comparisons stay token-identical.
    prefix: str = ""


@dataclass
class Response:
    uid: int
    task: str
    text: str
    nfe: int          # denoising forwards THIS row needed (its seq_steps)
    wall_s: float     # queue wait + decode wall THIS row was decoded in
    queue_s: float = 0.0
    decode_s: float = 0.0
    tokens_out: int = 0   # tokens delivered after EOS truncation
    tokens_dropped: int = 0  # generated but cut at EOS / never unmasked
    blocks_drafted: int = 0   # spec decode: blocks drafted for this row
    blocks_accepted: int = 0  # ... and how many survived verification
    # submit -> the row's FIRST decoded block is available, measured at
    # the first slice boundary the row participated in. Always
    # ``block_s[0]``.
    ttfb_s: float = 0.0
    # submit -> each decoded block, as the host observes it: the end of
    # the wait for the slice that completed the block (blocks completed
    # by one slice share its time)
    block_s: Tuple[float, ...] = ()
    # radix prefix cache (0 with prefix_cache off): tree pages this
    # row's admission reused, and the prompt tokens whose prefill those
    # pages replaced
    prefix_hit_pages: int = 0
    prefill_tokens_saved: int = 0
    # the delivered token ids ``text`` decodes (EOS-truncated, masks
    # dropped); ids outside the byte tokenizer's range have no text
    token_ids: Tuple[int, ...] = ()


@dataclass
class RequestState:
    req: Request
    t_submit: float
    t_admit: float = 0.0
    slot: int = -1


@dataclass
class Slot:
    """One row of the decode batch. ``state``: free | active.
    ``pages``: private pool pages this slot's request owns (paged layout);
    freed — and shared-prefix references dropped — at retirement. The
    slot accumulates its request's latency split (``decode_s`` over the
    slices it was live in, ``block_t``: the host time each block
    completed) and remembers which task it is calibrating, if any."""
    index: int
    state: str = "free"
    rs: Optional[RequestState] = None
    pages: Optional[List[int]] = None
    decode_s: float = 0.0
    block_t: List[float] = field(default_factory=list)
    calib_task: str = ""
    was_mid: bool = False  # admitted while the batch was mid-generation
    # radix prefix cache: tree pages share()d into this row (freed at
    # retirement — their KV belongs to the tree), the token length they
    # cover (the row's composed-prefill offset), and how many of them
    # pre-dated this request's own seeding (the actual reuse)
    prefix_pages: Optional[List[int]] = None
    prefix_len: int = 0
    prefix_hit_pages: int = 0

    def admit(self, rs: RequestState,
              pages: Optional[List[int]] = None) -> None:
        self.rs = rs
        self.pages = pages
        self.state = "active"
        self.decode_s = 0.0
        self.block_t = []
        self.calib_task = ""
        self.was_mid = False
        self.prefix_pages = None
        self.prefix_len = 0
        self.prefix_hit_pages = 0
        rs.slot = self.index

    def retire(self) -> None:
        self.rs = None
        self.pages = None
        self.state = "free"
        self.calib_task = ""
        self.prefix_pages = None
        self.prefix_len = 0
        self.prefix_hit_pages = 0


# EngineStats field spec: name -> (python type, help text). Every field
# exports as a Prometheus GAUGE, not a counter — the failed-slice requeue
# path backs admissions out with ``-=``, which a counter contract forbids.
_STATS_FIELDS: Dict[str, tuple] = {
    "requests": (int, "requests admitted"),
    "tokens": (int, "delivered tokens (post-EOS truncation)"),
    "tokens_dropped": (int, "generated-but-truncated tokens"),
    "nfe": (int, "model forwards, prefills included"),
    "wall_s": (float, "sum of slice dispatch walls"),
    "queue_s": (float, "sum of per-request queue waits"),
    "seq_steps": (int, "sum of per-row live denoising steps"),
    # nfe x the resident decode footprint — int8 engines stream ~1/4
    # the f32 bytes per forward
    "weight_bytes_streamed": (int, "decode-weight bytes read"),
    # paged layout occupancy (all 0 under the dense layout)
    "page_capacity": (int, "total pool pages"),
    "pages_peak": (int, "max pages simultaneously allocated"),
    "pages_shared": (int, "pages pinned by the shared prefix"),
    "pages_freed": (int, "private-page frees at retirement"),
    # speculative drafting (all 0 with spec_decode off)
    "blocks_drafted": (int, "row-blocks flagged by the signature"),
    "blocks_accepted": (int, "drafted blocks surviving verification"),
    "draft_batches": (int, "boundaries that handed the slice a plan"),
    "slices": (int, "compiled slice dispatches"),
    # the async-admission payoff: admitted while cursor > 0 rows present
    "mid_admits": (int, "requests admitted mid-generation"),
    "ttfb_s": (float, "sum of per-request time-to-first-block"),
    # radix prefix cache (all 0 with prefix_cache off)
    "prefix_hits": (int, "admissions that reused >= 1 tree node"),
    "prefix_misses": (int, "non-empty-prefix admissions reusing none"),
    "prefix_inserts": (int, "tree nodes adopted (seeds + promotions)"),
    "prefix_evictions": (int, "LRU nodes reclaimed under page pressure"),
    "prefix_hit_pages": (int, "tree pages served at admission"),
    "prefill_tokens_saved": (int, "prompt tokens those pages replaced"),
    # admission + seeding + the one-time shared prefill; the radix
    # cache's headline reduction (a full-hit skips its forward outright)
    "prefill_nfe": (int, "prefill forwards"),
    # rows the admission forwards computed, bucket pad rows included
    "prefill_rows": (int, "admission prefill rows"),
}


class EngineStats:
    """Engine counters — a typed VIEW over a ``MetricsRegistry``.

    Field access reads/writes ``engine_<name>`` gauges in the backing
    registry, so the scheduler's ledger IS the exported metric — one
    source of truth, no snapshot copying, and ``obs.prometheus()`` /
    ``snapshot()`` expose exactly what the stats report prints. The
    attribute surface (every ``_STATS_FIELDS`` name plus the derived
    properties) is unchanged from the former dataclass; reads come back
    in the field's declared python type. SERVING.md "Stats glossary"
    documents the semantics.
    """

    PREFIX = "engine_"

    def __init__(self, registry=None):
        reg = registry if registry is not None else MetricsRegistry()
        gauges = {}
        for name, (_, help) in _STATS_FIELDS.items():
            g = reg.gauge(self.PREFIX + name, help)
            g.set(0.0)
            gauges[name] = g
        object.__setattr__(self, "registry", reg)
        object.__setattr__(self, "_g", gauges)

    def __getattr__(self, name):  # fields only; properties hit the class
        f = _STATS_FIELDS.get(name)
        if f is None:
            raise AttributeError(name)
        return f[0](object.__getattribute__(self, "_g")[name].get())

    def __setattr__(self, name, value):
        g = object.__getattribute__(self, "_g").get(name)
        if g is None:
            raise AttributeError(f"unknown engine stat {name!r}")
        g.set(float(value))

    def as_dict(self) -> Dict[str, float]:
        return {name: getattr(self, name) for name in _STATS_FIELDS}

    def __eq__(self, other):
        if not isinstance(other, EngineStats):
            return NotImplemented
        return self.as_dict() == other.as_dict()

    def __repr__(self):
        inner = ", ".join(f"{k}={v}" for k, v in self.as_dict().items())
        return f"EngineStats({inner})"

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / self.wall_s if self.wall_s else 0.0

    @property
    def tokens_per_nfe(self) -> float:
        return self.tokens / self.nfe if self.nfe else 0.0

    @property
    def page_util(self) -> float:
        return self.pages_peak / self.page_capacity \
            if self.page_capacity else 0.0

    @property
    def draft_accept_rate(self) -> float:
        return self.blocks_accepted / self.blocks_drafted \
            if self.blocks_drafted else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        n = self.prefix_hits + self.prefix_misses
        return self.prefix_hits / n if n else 0.0


@lru_cache(maxsize=None)
def _seed_prefill_prog(cfg: ModelConfig, max_len: int, ps: int,
                       end: int, composed: bool):
    """Compiled B=1 donor prefill for one seed-segment shape. Module-
    level so every engine in the process shares one program per
    (config, boundary-length) pair — an eager ``M.prefill`` re-traces
    its scan every call, which costs more than the forward itself and
    would stall the slice loop on every cold tenant."""
    if composed:
        def fn(params, tokens, kp, vp, pt, prefix_len, wpt):
            cache = {"attn": {
                "kp": kp, "vp": vp, "pt": pt,
                "pos": jnp.full((max_len,), -1, jnp.int32),
                "length": jnp.zeros((), jnp.int32)}}
            _, c = M.prefill(params, cfg, tokens, max_len=max_len,
                             mode="full", cache=cache, page_size=ps,
                             prefix_len=prefix_len,
                             write_page_table=wpt)
            return c["attn"]["kp"], c["attn"]["vp"]
    else:
        def fn(params, tokens, kp, vp, pt):
            cache = {"attn": {
                "kp": kp, "vp": vp, "pt": pt,
                "pos": jnp.full((max_len,), -1, jnp.int32),
                "length": jnp.zeros((), jnp.int32)}}
            _, c = M.prefill(params, cfg, tokens, max_len=max_len,
                             mode="full", cache=cache, page_size=ps)
            return c["attn"]["kp"], c["attn"]["vp"]
    return jax.jit(fn)


@lru_cache(maxsize=None)
def _seed_prefill_batched_prog(cfg: ModelConfig, max_len: int, ps: int,
                               S: int, bucket: int):
    """Compiled MULTI-segment donor prefill: ``bucket`` (power-of-two)
    pending seed segments, right-padded to a common length ``S``, in ONE
    composed forward. Per row: ``prefix_len`` marks the already-seeded
    chain (its pages compose in, its writes are dropped), ``valid_len``
    masks the row's pad keys out of the bidirectional attention, and the
    write page table maps only the row's fresh ``[start, end)`` pages —
    so one dispatch seeds several tenants' segments where the B=1 donor
    path (``_seed_prefill_prog``, still used for lone segments) would
    have cost one forward each."""
    def fn(params, tokens, kp, vp, spt, prefix_len, valid_len, wpt):
        cache = {"attn": {
            "kp": kp, "vp": vp, "pt": spt,
            "pos": jnp.full((max_len,), -1, jnp.int32),
            "length": jnp.zeros((), jnp.int32)}}
        _, c = M.prefill(params, cfg, tokens, max_len=max_len,
                         mode="full", cache=cache, page_size=ps,
                         prefix_len=prefix_len, write_page_table=wpt,
                         valid_len=valid_len)
        return c["attn"]["kp"], c["attn"]["vp"]
    return jax.jit(fn)


class Scheduler:
    """Request queue + slot pool + one compiled slice program.

    ``slice_step()`` runs one slice boundary: it admits queued requests
    into free slots, dispatches one ``slice_len``-block slice over the
    carry, retires finished rows, and returns their responses. ``run()``
    drains the queue by ``slice_step()`` alone.
    """

    def __init__(self, params, cfg: ModelConfig, dcfg: DecodeConfig, *,
                 ecfg: Optional[EngineConfig] = None,
                 store: Optional[CalibrationStore] = None,
                 obs: Optional[Observability] = None,
                 mask_id: int = tok.MASK_ID, eos_id: int = tok.EOS_ID):
        self.params = params
        self.cfg = cfg
        self.dcfg = dcfg
        self.ecfg = ecfg if ecfg is not None else EngineConfig()
        self.slice_len = int(self.ecfg.slice_len)
        if self.slice_len < 1:
            raise ValueError(
                f"EngineConfig.slice_len must be >= 1 (blocks per "
                f"compiled slice), got {self.slice_len}")
        mode = self.ecfg.resolved_cache_mode()
        # weight streaming dtype: quantize ONCE at load (before the page
        # pool's shared prefill — every forward thereafter streams the
        # int8 tiles), and price each forward's weight traffic for the
        # ``weight_bytes_streamed`` stat
        self.weight_dtype = dcfg.weight_dtype or "bf16"
        assert self.weight_dtype in WEIGHT_DTYPES, self.weight_dtype
        if self.weight_dtype == "int8" and not is_quantized(self.params):
            self.params = quantize_decode_params(params, cfg)
        self._decode_w_bytes = decode_weight_bytes(self.params, cfg)
        if store is not None:
            # an explicitly passed store wins over any on-disk npz (which
            # the next calibration's save() will then overwrite)
            self.store = store
        elif self.ecfg.store_path and os.path.exists(
                CalibrationStore.npz_path(self.ecfg.store_path)):
            self.store = CalibrationStore.load(self.ecfg.store_path, dcfg)
        else:
            self.store = CalibrationStore(dcfg)
        self.mask_id = int(mask_id)
        self.eos_id = int(eos_id)
        self._mask_arr = jnp.asarray(mask_id, jnp.int32)
        self.queue: Deque[RequestState] = deque()
        self.slots = [Slot(i) for i in range(self.ecfg.batch_size)]
        # observability bundle (SERVING.md "Observability"): the stats
        # ledger is a view over its registry, so EngineStats and the
        # Prometheus/JSON exports share one source of truth. Tracing and
        # drift telemetry stay off unless EngineConfig opts in.
        self.obs = obs if obs is not None else Observability.from_config(
            self.ecfg, store=self.store)
        self.stats = EngineStats(self.obs.registry)
        self._h_queue = self.obs.registry.histogram(
            "queue_wait_seconds", "submit -> admission wait per request")
        self._h_dispatch = self.obs.registry.histogram(
            "dispatch_seconds", "compiled decode dispatch wall")
        self.seen_tasks: Dict[str, int] = {}  # task -> requests admitted

        self.paged = dcfg.cache_layout == "paged" and mode != "none"
        self.prefix_cache = bool(self.ecfg.prefix_cache)
        if self.prefix_cache:
            # the radix tree shares PAGES between rows
            assert self.paged, "prefix_cache needs the paged KV layout"
        self.prefix_tree: Optional[RadixPrefixCache] = None
        self._prefix_memo: Dict[str, Tuple[List[int], int]] = {}
        # uid -> tree pages matched BEFORE this boundary's batched
        # seeding: _prefix_claim reports these as the request's true
        # reuse (its own boundary's seeds are cost, not hits)
        self._preseed_hits: Dict[int, int] = {}

        # mesh-sharded SPMD serving (SERVING.md "Sharded serving"): the
        # slot pool partitions into per-data-shard groups and weights
        # place through the "serve" TP specs. All device arrays get
        # their NamedShardings at carry construction; the compiled
        # slice/admit programs specialize on them, so the program
        # factories (and their cross-engine memo keys) stay mesh-free.
        self.dp = max(1, int(self.ecfg.data_parallel))
        self.mp = max(1, int(self.ecfg.model_parallel))
        self.mesh = make_serving_mesh(data=self.dp, model=self.mp)
        self.slots_per_shard = self.ecfg.batch_size // self.dp \
            if self.ecfg.batch_size % self.dp == 0 else self.ecfg.batch_size
        if self.mesh is not None:
            assert self.ecfg.batch_size % self.dp == 0, \
                f"batch_size {self.ecfg.batch_size} must divide into " \
                f"data={self.dp} slot shards"
            assert not self.prefix_cache or self.dp == 1, \
                "radix prefix cache is single-shard (tree pages live " \
                "on one data shard); use model_parallel only"
            pallas = [name for name, on in (
                ("attn_impl=kernel", dcfg.attn_impl == "kernel"),
                ("step_fusion=fused", dcfg.step_fusion == "fused"),
                ("weight_dtype=int8", self.weight_dtype == "int8")) if on]
            if pallas and jax.default_backend() == "tpu":
                # the TPU compiler cannot partition a Mosaic kernel over
                # a mesh; that takes a shard_map per kernel
                raise ValueError(
                    f"sharded serving on TPU runs the XLA paths only; "
                    f"{', '.join(pallas)} would put Pallas kernels in a "
                    f"mesh-sharded program")
            self.params = place_serving_params(self.params, cfg,
                                               self.mesh)
        # the shared system prompt is prepended to every row's prompt
        # under BOTH layouts (same tokens in, comparable runs); the page
        # rounding applies regardless so the prompts match — only the
        # paged layout additionally dedups its KV into shared pages.
        # Under prefix_cache the static machinery stays OFF: the shared
        # prefix becomes the pre-seeded first radix node instead
        # (SERVING.md migration note), folded into every row's prefix
        # stream by _row_prefix_ids.
        self.shared_len = 0           # shared-prefix tokens (page multiple)
        self._shared_ids: List[int] = []
        self._shared_pages: List[int] = []
        if self.ecfg.shared_prefix and not self.prefix_cache:
            ps = dcfg.page_size
            ids = tok.encode(self.ecfg.shared_prefix, bos=True)
            # round DOWN to a page multiple (and keep at least one page
            # of per-row prompt — the cap itself must also round down,
            # or a prompt_len that is not a page multiple yields a
            # non-aligned shared length) so decode writes never touch a
            # shared page — copy-on-write with the copy elided by
            # alignment
            cap = (max(self.ecfg.prompt_len - ps, 0) // ps) * ps
            self.shared_len = min((len(ids) // ps) * ps, cap)
            self._shared_ids = ids[:self.shared_len]
        if self.paged:
            self._init_page_pool(mode)
        self.spec = bool(self.ecfg.spec_decode)
        self.drafter = Drafter(self.store, dcfg,
                               max_steps=self.ecfg.draft_max_steps) \
            if self.spec else None

        # step-sliced decode loop (SERVING.md "Async admission")
        self._carry = None
        self._nfe_seen = 0
        self._calibrating: Dict[str, int] = {}  # task -> calibration slot
        kw = dict(cache_mode=mode,
                  cache_layout="paged" if self.paged else "dense",
                  shared_prefix_len=self.shared_len if self.paged else 0)
        self._slice_fn = make_slice_fn(
            cfg, dcfg, slice_len=self.slice_len,
            variant="draft" if self.spec else "step",
            weight_dtype=self.weight_dtype, **kw)
        self._admit_fn = make_admit_fn(cfg, dcfg, **kw) \
            if mode != "none" else None

    @property
    def carry(self) -> Optional[DecodeCarry]:
        """The live decode carry between slices (``None`` when idle).
        Read it between ``slice_step`` calls only: the next slice may
        donate its buffers."""
        return self._carry

    def _count_nfe(self, n: int) -> None:
        """Every counted forward streams the decode weight set once —
        ``weight_bytes_streamed`` is the engine's HBM weight-traffic
        ledger (int8 engines read ~1/4 the f32 bytes per forward)."""
        self.stats.nfe += n
        self.stats.weight_bytes_streamed += n * self._decode_w_bytes

    # -- page pool (paged layout; SERVING.md "Paged KV") ----------------
    def _init_page_pool(self, mode: str) -> None:
        cfg, dcfg, ecfg = self.cfg, self.dcfg, self.ecfg
        assert cfg.has_attention and cfg.family != "hybrid", \
            "paged KV needs a pure-attention family"
        ps = dcfg.page_size
        B, P = ecfg.batch_size, ecfg.prompt_len
        self.max_len = P + dcfg.max_new_tokens + \
            (dcfg.block_size if mode == "dual" else 0)
        self.n_log = dcfg.pages_per_seq(self.max_len)
        n_shared = self.shared_len // ps
        self.private_per_slot = self.n_log - n_shared
        # each data shard keeps its OWN copy of the shared-prefix pages
        # (a row only ever gathers pages resident on its shard), so the
        # auto-sized pool carries dp shared runs plus B private runs
        num_pages = ecfg.num_pages or \
            (self.dp * n_shared + B * self.private_per_slot)
        assert num_pages >= n_shared + self.private_per_slot, \
            f"pool of {num_pages} pages cannot fit one request"
        assert num_pages % self.dp == 0, \
            f"pool of {num_pages} pages must divide into data={self.dp} " \
            f"shards"
        self.allocator = ShardedPageAllocator(num_pages, self.dp)
        L, Kh = cfg.num_layers, cfg.num_kv_heads
        D = cfg.resolved_head_dim
        dtype = M.param_dtype(cfg)
        self._pool_k = jnp.zeros((L, num_pages, ps, Kh, D), dtype)
        self._pool_v = jnp.zeros((L, num_pages, ps, Kh, D), dtype)
        self.stats.page_capacity = num_pages
        self._shared_pages_by_shard: List[List[int]] = \
            [[] for _ in range(self.dp)]
        if self.shared_len:
            # prefill the shared prefix ONCE PER SHARD; the scheduler
            # keeps a permanent reference so retirement never reclaims
            # the pages. dp=1 runs the identical single forward it
            # always did.
            shared = jnp.asarray(self._shared_ids, jnp.int32)[None]
            for shard in range(self.dp):
                pages = self.allocator.alloc(n_shared, shard)
                self._shared_pages_by_shard[shard] = pages
                spt = np.full((1, self.n_log), -1, np.int32)
                spt[0, :n_shared] = pages
                cache = {"attn": {
                    "kp": self._pool_k, "vp": self._pool_v,
                    "pt": jnp.asarray(spt),
                    "pos": jnp.full((self.max_len,), -1, jnp.int32),
                    "length": jnp.zeros((), jnp.int32)}}
                _, cache = M.prefill(self.params, cfg, shared,
                                     max_len=self.max_len, mode="full",
                                     cache=cache, page_size=ps)
                self._pool_k = cache["attn"]["kp"]
                self._pool_v = cache["attn"]["vp"]
                self._count_nfe(1)  # the one-time shared-prefix forward
                self.stats.prefill_nfe += 1
            self._shared_pages = self._shared_pages_by_shard[0]
        if self.prefix_cache:
            # the tree owns prefix pages WITHIN this pool; a rebuilt
            # pool (donated-carry failure) gets a fresh empty tree —
            # the old pages died with the old pool
            self.prefix_tree = RadixPrefixCache(
                self.allocator, ps,
                max_pages=self.ecfg.prefix_cache_pages)
        self.stats.pages_shared = sum(
            len(p) for p in self._shared_pages_by_shard)
        self.stats.pages_peak = self.allocator.in_use

    # -- shard topology (SERVING.md "Sharded serving") ------------------
    def shard_of_slot(self, index: int) -> int:
        """The data shard owning slot ``index``: slots partition into
        ``dp`` contiguous groups of ``batch_size // dp`` — a request is
        admitted into ONE slot, so it never straddles shards."""
        return index // self.slots_per_shard

    def _shared_for(self, slot: Slot) -> List[int]:
        """The shared-prefix page run of the slot's own shard."""
        return self._shared_pages_by_shard[self.shard_of_slot(slot.index)]

    # -- queue ----------------------------------------------------------
    def submit(self, requests: List[Request],
               at: Optional[float] = None) -> None:
        """Enqueue requests. ``at`` overrides the submit timestamp (a
        ``time.perf_counter()`` value) — arrival-process simulators
        submit between decode dispatches but want queue waits measured
        from the ARRIVAL time, not from when the driver thread got
        around to the call."""
        now = time.perf_counter() if at is None else at
        tr = self.obs.tracer
        for r in requests:
            self.queue.append(RequestState(r, now))
            if tr:
                tr.abegin("request", r.uid, t=now, task=r.task)
                tr.abegin("queued", r.uid, t=now)

    def pending(self) -> int:
        return len(self.queue)

    # -- step-sliced decode (SERVING.md "Async admission") --------------
    def _start_carry(self) -> None:
        """Build a fresh all-free carry. Paged: the pool arrays move INTO
        the carry (they may be donated into the compiled slice program on
        TPU — the scheduler must not keep aliases while a carry is live;
        ``_teardown_carry`` recovers them)."""
        B, P = self.ecfg.batch_size, self.ecfg.prompt_len
        kw = {}
        if self.paged:
            kw = dict(pool_k=self._pool_k, pool_v=self._pool_v,
                      page_table=np.full((B, self.n_log), -1, np.int32))
            self._pool_k = self._pool_v = None
        self._carry = init_decode_carry(
            self.cfg, self.dcfg, batch=B, prompt_len=P,
            mask_id=self.mask_id,
            cache_mode=self.ecfg.resolved_cache_mode(),
            cache_layout="paged" if self.paged else "dense",
            shared_prefix_len=self.shared_len if self.paged else 0,
            mesh=self.mesh, **kw)
        self._nfe_seen = 0

    def _teardown_carry(self) -> None:
        if self._carry is None:
            return
        if self.paged:
            kv = self._carry.cache["attn"]
            if kv["kp"].is_deleted():
                # the carry was donated into a dispatch that then failed
                # at execution time (TPU): its buffers — pool included —
                # are gone. Rebuild the pool and re-prefill the shared
                # prefix instead of masking the original error with a
                # deleted-buffer access (and never recovering the pool).
                self._carry = None
                self._init_page_pool(self.ecfg.resolved_cache_mode())
                return
            self._pool_k, self._pool_v = kv["kp"], kv["vp"]
        self._carry = None

    def _prompt_row(self, rs: RequestState) -> List[int]:
        P = self.ecfg.prompt_len
        if self.prefix_cache or rs.req.prefix:
            # prefix-layout row: the cacheable stream left-anchored up
            # to its page-rounded boundary, the remainder right-aligned
            # in the rest (pads in the middle). Engines WITHOUT the
            # radix cache build the identical row for a prefix-carrying
            # request and prefill it whole — that is what keeps the
            # cache-on/off and paged/dense comparisons token-identical.
            return self._row_tokens(rs.req)
        ids = tok.encode(rs.req.prompt, bos=True)
        ids = ids[-(P - self.shared_len):]
        return self._shared_ids + tok.pad_left(ids, P - self.shared_len)

    # -- radix prefix cache (SERVING.md "Radix prefix cache") -----------
    def _row_tokens(self, req: Request) -> List[int]:
        """The request's full [prompt_len] row in the prefix layout:
        cacheable stream left-anchored up to its page-rounded boundary,
        remainder right-aligned (pads in the middle)."""
        P = self.ecfg.prompt_len
        pfx, _ = self._row_prefix_ids(req)
        L = len(pfx)
        full = tok.encode(self.ecfg.shared_prefix + req.prefix
                          + req.prompt, bos=True)
        rest = full[L:][-(P - L):]
        return list(pfx) + tok.pad_left(rest, P - L)

    def _row_prefix_ids(self, req: Request) -> Tuple[List[int], int]:
        """The request's cacheable token stream and its shared-template
        boundary: ``(ids, m0)`` where ``ids`` is the page-rounded (and
        capped — at least one page of the row must stay per-request)
        encoding of ``shared_prefix + req.prefix`` and ``ids[:m0]`` is
        the page-rounded shared template alone. Tree nodes are seeded
        exactly at these two boundaries, so every tenant chains through
        ONE cross-tenant template node. The byte tokenizer concatenates
        (``encode(a + b) == encode(a) + bytes(b)``), which is what makes
        the boundaries stable under memoization by tenant prefix."""
        hit = self._prefix_memo.get(req.prefix)
        if hit is not None:
            return hit
        ps, P = self.dcfg.page_size, self.ecfg.prompt_len
        cap = (max(P - ps, 0) // ps) * ps
        shared = tok.encode(self.ecfg.shared_prefix, bos=True) \
            if self.ecfg.shared_prefix else []
        ids = tok.encode(self.ecfg.shared_prefix + req.prefix, bos=True)
        L = min((len(ids) // ps) * ps, cap)
        m0 = min((len(shared) // ps) * ps, cap, L)
        out = (ids[:L], m0)
        self._prefix_memo[req.prefix] = out
        return out

    def _evict_pages(self, need: int) -> None:
        """LRU-evict tree-only nodes until ``need`` pages plus the
        configured watermark headroom are free. Ordered BEFORE the
        load-shedding break in page-gated admission: a request only
        waits once live rows and the watermark genuinely exhaust the
        pool, never because cold cache entries sit on it."""
        if not self.prefix_cache:
            return
        head = int(self.ecfg.prefix_cache_watermark
                   * self.stats.page_capacity)
        want = need + head - self.allocator.available
        if want > 0:
            n, freed = self.prefix_tree.evict(want)
            self.stats.prefix_evictions += n
            if n and self.obs.tracer:
                self.obs.tracer.instant("evict", tid=0, nodes=n,
                                        pages=freed)

    def _live_kv(self) -> dict:
        """The pool the seed forward reads/writes: the live carry's (the
        arrays move INTO the carry) or the scheduler's parked ones."""
        if self._carry is not None:
            return self._carry.cache["attn"]
        return {"kp": self._pool_k, "vp": self._pool_v}

    def _put_kv(self, kp, vp) -> None:
        if self._carry is not None:
            kv = dict(self._carry.cache["attn"], kp=kp, vp=vp)
            self._carry = self._carry._replace(
                cache=dict(self._carry.cache, attn=kv))
        else:
            self._pool_k, self._pool_v = kp, vp

    def _seed_segment(self, ids: List[int], start: int, end: int,
                      chain_pages: List[int]) -> List[int]:
        """One B=1 donor forward over ``ids[:end]``, composed against
        the already-seeded chain covering ``[0, start)``; writes ONLY
        the fresh pages for ``[start, end)`` and returns them (refcount
        1, destined for the tree via ``insert``'s ownership transfer).
        Seeding at node boundaries is what keeps warm hits bit-exact:
        a row composing this node sees exactly the K/V this forward
        wrote, which is exactly what ITS OWN admission would have
        computed for those positions."""
        ps = self.dcfg.page_size
        pages = self.allocator.alloc((end - start) // ps)
        try:
            kv = self._live_kv()
            spt = np.full((1, self.n_log), -1, np.int32)
            spt[0, :start // ps] = chain_pages
            spt[0, start // ps: end // ps] = pages
            tokens = jnp.asarray(ids[:end], jnp.int32)[None]
            prog = _seed_prefill_prog(self.cfg, self.max_len, ps, end,
                                      bool(start))
            if start:
                wpt = spt.copy()
                wpt[0, :start // ps] = -1  # chain pages stay immutable
                kp, vp = prog(self.params, tokens, kv["kp"], kv["vp"],
                              jnp.asarray(spt),
                              jnp.asarray([start], jnp.int32),
                              jnp.asarray(wpt))
            else:
                kp, vp = prog(self.params, tokens, kv["kp"], kv["vp"],
                              jnp.asarray(spt))
            self._put_kv(kp, vp)
            self._count_nfe(1)
            self.stats.prefill_nfe += 1
            return pages
        except BaseException:
            self.allocator.free(pages)
            raise

    def _batch_seed_pending(self, n_slots: int) -> int:
        """Seed the radix segments the next ``n_slots`` queued requests
        are missing, batching concurrent segments into ONE padded donor
        forward per dependency round (SERVING.md "Radix prefix cache",
        batched seeding). A row's chain has at most two boundaries
        (template ``m0``, full prefix ``L``), so two rounds cover every
        plan: round 0 seeds each row's first missing segment, round 1
        the segments that chain on round 0's. Segments are deduplicated
        by ``(tokens, start)`` — a burst of same-tenant cold requests
        seeds its template once. Page pressure aborts quietly: the
        per-request claim re-seeds (and sheds load) exactly as before.
        Returns the number of segments seeded."""
        if n_slots <= 0 or not self.queue:
            return 0
        ps = self.dcfg.page_size
        plans = []
        owned: set = set()  # segments already attributed this boundary
        for rs in list(self.queue)[:n_slots]:
            pfx_ids, m0 = self._row_prefix_ids(rs.req)
            row = self._row_tokens(rs.req)
            matched, mpages, _ = self.prefix_tree.match(row)
            # the request's true reuse: pages already in the tree plus
            # segments a QUEUE-EARLIER request is about to seed (the
            # sequential claim would have found those resident too);
            # segments first needed by THIS request are its own cost
            hits, pos = len(mpages), matched
            for b in (m0, len(pfx_ids)):
                if pos < b:
                    key = (tuple(row[:b]), pos)
                    if key in owned:
                        hits += (b - pos) // ps
                    else:
                        owned.add(key)
                    pos = b
            self._preseed_hits[rs.req.uid] = hits
            plans.append((row, m0, len(pfx_ids)))
        seeded = 0
        for _round in range(2):
            segs: Dict[tuple, tuple] = {}
            for row, m0, L in plans:
                matched, mpages, _ = self.prefix_tree.match(row)
                if matched >= L:
                    continue
                end = m0 if matched < m0 else L
                if end <= matched:
                    continue
                segs.setdefault((tuple(row[:end]), matched),
                                (row, matched, end, list(mpages)))
            if not segs:
                break
            try:
                self._seed_segments(list(segs.values()))
            except MemoryError:
                break
            seeded += len(segs)
        return seeded

    def _seed_segments(self, segs: List[tuple]) -> None:
        """Seed a round of independent segments and insert each into the
        tree. A LONE segment takes the exact-length B=1 donor program —
        bit-identical to the pre-batching path, so single-tenant traffic
        never changes. Two or more pad to the round's longest segment in
        a power-of-two row bucket and run ONE composed forward
        (``valid_len`` keeps pad keys out of the bidirectional
        attention; each row writes only its own fresh pages)."""
        ps = self.dcfg.page_size
        if len(segs) == 1:
            row, start, end, chain = segs[0]
            self._evict_pages((end - start) // ps)
            pages = self._seed_segment(row, start, end, chain)
            if self.prefix_tree.insert(row, start, pages):
                self.stats.prefix_inserts += 1
            else:
                self.allocator.free(pages)
            return
        self._evict_pages(sum((end - start) // ps
                              for _, start, end, _ in segs))
        fresh: List[List[int]] = []
        try:
            for _, start, end, _ in segs:
                fresh.append(self.allocator.alloc((end - start) // ps))
        except MemoryError:
            for pages in fresh:
                self.allocator.free(pages)
            raise
        n = len(segs)
        bucket = 1 << (n - 1).bit_length()
        S = max(end for _, _, end, _ in segs)
        tokens = np.full((bucket, S), self.mask_id, np.int32)
        plen = np.zeros((bucket,), np.int32)
        vlen = np.zeros((bucket,), np.int32)
        spt = np.full((bucket, self.n_log), -1, np.int32)
        wpt = np.full((bucket, self.n_log), -1, np.int32)
        for i, ((row, start, end, chain), pages) in enumerate(
                zip(segs, fresh)):
            tokens[i, :end] = row[:end]
            plen[i], vlen[i] = start, end
            spt[i, :start // ps] = chain
            spt[i, start // ps: end // ps] = pages
            wpt[i, start // ps: end // ps] = pages
        try:
            kv = self._live_kv()
            prog = _seed_prefill_batched_prog(self.cfg, self.max_len, ps,
                                              S, bucket)
            kp, vp = prog(self.params, jnp.asarray(tokens),
                          kv["kp"], kv["vp"], jnp.asarray(spt),
                          jnp.asarray(plen), jnp.asarray(vlen),
                          jnp.asarray(wpt))
            self._put_kv(kp, vp)
            self._count_nfe(1)
            self.stats.prefill_nfe += 1
        except BaseException:
            for pages in fresh:
                self.allocator.free(pages)
            raise
        for (row, start, _, _), pages in zip(segs, fresh):
            if self.prefix_tree.insert(row, start, pages):
                self.stats.prefix_inserts += 1
            else:
                self.allocator.free(pages)

    def _prefix_claim(self, req: Request
                      ) -> Optional[Tuple[int, List[int], List[int], int]]:
        """Walk the tree for ``req``'s prefix (seeding missing segments
        on demand), then claim this row's pages: ``share()`` the chain
        and allocate the private remainder. Returns ``(prefix_len,
        chain_pages, private_pages, hit_pages)`` — ``hit_pages`` counts
        only pages that PRE-dated this call's seeding (true reuse) — or
        ``None`` under page pressure eviction could not relieve (the
        caller sheds load; seeds already adopted stay in the tree, so
        the retry only needs the private pages)."""
        pfx_ids, m0 = self._row_prefix_ids(req)
        L = len(pfx_ids)
        # walk the FULL row, not just the prefix stream: retirement
        # promotes prompt pages at boundaries past L, and matching them
        # is what makes an identical resubmission near-zero-prefill
        row = self._row_tokens(req)
        matched, mpages, _ = self.prefix_tree.match(row)
        # pages this request's own boundary seeded (via the batched
        # pre-pass) are cost, not reuse — report the pre-seed depth
        hit_pages = self._preseed_hits.pop(req.uid, len(mpages))
        if matched < L:
            with self.obs.tracer.span("sched.seed") as sp:
                seeded = 0
                try:
                    for b in (m0, L):
                        if matched < b:
                            self._evict_pages((b - matched)
                                              // self.dcfg.page_size)
                            new = self._seed_segment(row, matched, b,
                                                     mpages)
                            seeded += 1
                            if self.prefix_tree.insert(row, matched, new):
                                self.stats.prefix_inserts += 1
                            else:  # cannot happen single-threaded (the
                                # walk just missed); keep the ledger
                                # honest anyway
                                self.allocator.free(new)
                            matched, mpages, _ = self.prefix_tree.match(row)
                except MemoryError:
                    return None
                finally:
                    sp.set_metadata(segments=seeded)
        need = self.n_log - len(mpages)
        self._evict_pages(need)
        if self.allocator.available < need:
            return None
        self.allocator.share(mpages)
        try:
            private = self.allocator.alloc(need)
        except MemoryError:
            self.allocator.free(mpages)
            return None
        return matched, list(mpages), private, hit_pages

    def _admit_sliced(self) -> List[Slot]:
        """Pop admissible requests into free slots (FIFO; paged admission
        gates on PAGE availability) and write their rows into the carry.
        Returns the slots admitted at this boundary; their admission
        prefill is ``_admit_prefill``'s."""
        tr = self.obs.tracer
        free = [s for s in self.slots if s.state == "free"]
        admitted: List[Slot] = []
        now = time.perf_counter()
        mid_gen = self._carry is not None and \
            any(s.state == "active" for s in self.slots)
        if self.prefix_cache and free and self.queue:
            # satellite: seed every missing radix segment the next
            # admissions will need in batched donor forwards BEFORE the
            # per-request claims walk the tree (each then finds its
            # chain resident)
            with tr.span("sched.seed") as sp:
                sp.set_metadata(segments=self._batch_seed_pending(len(free)))
        for slot in free:
            if not self.queue:
                break
            shard = self.shard_of_slot(slot.index)
            claim = None
            if self.prefix_cache:
                # peek — the claim itself evicts LRU tree nodes before
                # giving up, and a shed request must stay at the head
                claim = self._prefix_claim(self.queue[0].req)
                if claim is None:
                    break  # page pressure even after eviction
            elif self.paged and \
                    self.allocator.available_in(shard) \
                    < self.private_per_slot:
                # THIS shard's pool is short — another shard's free slot
                # may still admit the head (a request never straddles
                # shards, so per-shard pressure only skips that shard)
                continue
            rs = self.queue.popleft()
            rs.t_admit = now
            pages = None
            if self.prefix_cache:
                pfx_len, chain, pages, hit_pages = claim
            elif self.paged:
                _, pages = self.allocator.fork(self._shared_for(slot),
                                               self.private_per_slot,
                                               shard)
            slot.admit(rs, pages)
            if self.prefix_cache:
                slot.prefix_pages = chain
                slot.prefix_len = pfx_len
                slot.prefix_hit_pages = hit_pages
                if hit_pages:
                    self.stats.prefix_hits += 1
                elif pfx_len:
                    self.stats.prefix_misses += 1
                self.stats.prefix_hit_pages += hit_pages
                self.stats.prefill_tokens_saved += \
                    hit_pages * self.dcfg.page_size
            slot.was_mid = mid_gen
            t = rs.req.task
            self.seen_tasks[t] = self.seen_tasks.get(t, 0) + 1
            if not self.store.calibrated(t) and t not in self._calibrating:
                self._calibrating[t] = slot.index
                slot.calib_task = t
            if tr:
                tr.aend("queued", rs.req.uid)
                tr.begin("serve", tid=self.obs.slot_track(slot.index),
                         t=now, uid=rs.req.uid, task=t, mid=mid_gen,
                         prefix_len=slot.prefix_len)
            admitted.append(slot)
        if not admitted:
            return admitted
        if self._carry is None:
            self._start_carry()
        self.stats.requests += len(admitted)
        if mid_gen:
            self.stats.mid_admits += len(admitted)
        rows = [s.index for s in admitted]
        prompts = np.asarray([self._prompt_row(s.rs) for s in admitted],
                             np.int32)
        tables = self.store.tables_for([s.rs.req.task for s in admitted])
        page_rows = None
        if self.paged:
            page_rows = np.full((len(admitted), self.n_log), -1, np.int32)
            if self.prefix_cache:
                for i, s in enumerate(admitted):
                    row_pages = list(s.prefix_pages) + list(s.pages)
                    page_rows[i, :len(row_pages)] = row_pages
            else:
                n_shared = self.shared_len // self.dcfg.page_size
                for i, s in enumerate(admitted):
                    page_rows[i, :n_shared] = self._shared_for(s)
                    page_rows[i, n_shared:] = s.pages
            self.stats.pages_peak = max(self.stats.pages_peak,
                                        self.allocator.in_use)
        self._carry = admit_carry_rows(self._carry, rows, prompts,
                                       np.asarray(tables), self.mask_id,
                                       page_rows=page_rows,
                                       mark_prompt_pos=self.prefix_cache)
        return admitted

    def _admit_prefill(self, admitted: List[Slot]) -> None:
        """Dispatch the one batched admission prefill over the rows
        ``_admit_sliced`` just wrote (nothing to dispatch without a
        cache, or when every prompt position is already resident)."""
        if not admitted or self._admit_fn is None:
            return
        tr = self.obs.tracer
        if self.prefix_cache:
            P = self.ecfg.prompt_len
            if all(s.prefix_len == P for s in admitted):
                # zero-prefill admission: every prompt position of every
                # admitted row is already resident in tree pages
                # (admit_carry_rows marked pos/length) — the composed
                # forward would compute nothing fresh
                if tr:
                    tr.instant("zero_prefill_admit", tid=0,
                               rows=len(admitted))
                return
        rows = bucket_rows([s.index for s in admitted],
                           self.ecfg.batch_size)
        with tr.span("sched.admit_prefill", rows=len(admitted),
                     bucket=len(rows)):
            if self.prefix_cache:
                pfx = np.zeros((len(rows),), np.int32)
                pfx[:len(admitted)] = [s.prefix_len for s in admitted]
                self._carry = self._admit_fn(self.params, self._carry,
                                             jnp.asarray(rows),
                                             jnp.asarray(pfx))
            else:
                self._carry = self._admit_fn(self.params, self._carry,
                                             jnp.asarray(rows))
        self.stats.prefill_nfe += 1
        self.stats.prefill_rows += len(rows)

    def _retire_sliced(self) -> List[Response]:
        """Emit responses for rows whose cursor ran out or that
        EOS-retired, reclaim their pages immediately (the next
        ``_admit_sliced`` can hand them out), and ingest any finished
        calibration row."""
        tr = self.obs.tracer
        carry = self._carry
        nb = self.dcfg.num_blocks
        drift = self.obs.drift
        with tr.span("sched.pull"):
            cursor = np.asarray(carry.cursor)
            live = np.asarray(carry.live)
            done = [s for s in self.slots if s.state == "active"
                    and (cursor[s.index] >= nb or not live[s.index])]
            if not done:
                return []
            tokens = np.asarray(carry.resp)
            seq_steps = np.asarray(carry.seq_steps)
            drafted = np.asarray(carry.blocks_drafted)
            accepted = np.asarray(carry.blocks_accepted)
            if drift is not None or any(s.calib_task for s in done):
                # the batch recording ONCE, not one full pull per row
                conf_rec = np.asarray(carry.conf)
                val_rec = np.asarray(carry.conf_valid)
            if drift is not None:
                thr = np.asarray(carry.thr_steps)
                msum = np.asarray(carry.margin_sum)
                mn = np.asarray(carry.margin_n)
        out: List[Response] = []
        for slot in done:
            j, rs = slot.index, slot.rs
            if slot.calib_task:
                with tr.span("sched.ingest", task=slot.calib_task):
                    self.store.ingest(slot.calib_task, CalibrationProfile(
                        conf=conf_rec[j], valid=val_rec[j],
                        steps=seq_steps[j]))
                    if self.drafter is not None:
                        self.drafter.invalidate(slot.calib_task)
                    self._calibrating.pop(slot.calib_task, None)
                    if self.ecfg.store_path:
                        self.store.save(self.ecfg.store_path)
            row = tokens[j].tolist()
            if self.eos_id in row:
                row = row[:row.index(self.eos_id)]
            row = [t for t in row if t != self.mask_id]
            queue_s = rs.t_admit - rs.t_submit
            steps = int(seq_steps[j].sum())
            block_s = tuple(t - rs.t_submit for t in slot.block_t)
            ttfb_s = block_s[0] if block_s else 0.0
            out.append(Response(
                rs.req.uid, rs.req.task, tok.decode(row),
                nfe=steps, wall_s=queue_s + slot.decode_s,
                queue_s=queue_s, decode_s=slot.decode_s,
                tokens_out=len(row),
                tokens_dropped=tokens.shape[1] - len(row),
                blocks_drafted=int(drafted[j]),
                blocks_accepted=int(accepted[j]), ttfb_s=ttfb_s,
                block_s=block_s, prefix_hit_pages=slot.prefix_hit_pages,
                prefill_tokens_saved=slot.prefix_hit_pages
                * self.dcfg.page_size, token_ids=tuple(row)))
            self.stats.tokens += len(row)
            self.stats.tokens_dropped += tokens.shape[1] - len(row)
            self.stats.queue_s += queue_s
            self.stats.ttfb_s += ttfb_s
            self.stats.seq_steps += steps
            self._h_queue.observe(queue_s)
            if drift is not None:
                drift.observe(rs.req.task,
                              CalibrationProfile(conf=conf_rec[j],
                                                 valid=val_rec[j],
                                                 steps=seq_steps[j]),
                              thr_steps=thr[j], seq_steps=seq_steps[j],
                              margin_sum=msum[j], margin_n=mn[j])
            if tr:
                tr.end("serve", tid=self.obs.slot_track(j),
                       tokens=len(row), nfe=steps)
                tr.aend("request", rs.req.uid)
            # per-row draft counters reset at (re)admission and
            # accumulate over the row's lifetime: bank them here
            self.stats.blocks_drafted += int(drafted[j])
            self.stats.blocks_accepted += int(accepted[j])
        with tr.span("sched.release") as sp:
            freed = 0
            for slot in done:
                if self.paged and slot.pages is not None:
                    freed += self._release_pages(slot)
                slot.retire()
            self._carry = retire_carry_rows(carry, [s.index for s in done],
                                            nb)
            sp.set_metadata(pages=freed)
        return out

    def _release_pages(self, slot: Slot) -> int:
        """Return a retiring slot's pages to the pool (promoting its
        prompt pages into the radix tree first); the count freed."""
        pages = slot.pages
        if self.prefix_cache:
            # promote the row's now-immutable prompt pages into the tree
            # by refcount TRANSFER (no copy): the next identical
            # submission becomes a near-zero-prefill full hit. Only
            # whole pages strictly inside the prompt qualify — the page
            # straddling prompt/generation was decode-written and stays
            # private.
            ps = self.dcfg.page_size
            n_promo = (self.ecfg.prompt_len - slot.prefix_len) // ps
            promo = pages[:n_promo]
            if promo and self.prefix_tree.insert(
                    self._prompt_row(slot.rs), slot.prefix_len, promo):
                self.stats.prefix_inserts += 1
                if self.obs.tracer:
                    self.obs.tracer.instant("promote", tid=0,
                                            uid=slot.rs.req.uid,
                                            pages=len(promo))
                pages = pages[n_promo:]
                n, _ = self.prefix_tree.trim()
                self.stats.prefix_evictions += n
            self.allocator.free(pages)
            self.allocator.free(slot.prefix_pages or [])
        else:
            self.allocator.free(pages)
            self.allocator.free(self._shared_for(slot))
        self.stats.pages_freed += len(pages)
        return len(pages)

    def slice_step(self) -> List[Response]:
        """One slice boundary: admit into free slots, dispatch ONE
        compiled ``slice_len``-block slice, retire finished rows, and
        return their responses. A no-op (returning ``[]``) when nothing
        is queued or active. Each phase is a ``sched.*`` span
        (SERVING.md "Observability")."""
        tr = self.obs.tracer
        with tr.span("sched.boundary", slice=int(self.stats.slices)) as bd:
            with tr.span("sched.admit") as sp:
                admitted = self._admit_sliced()
                sp.set_metadata(rows=len(admitted))
            try:
                self._admit_prefill(admitted)
            except BaseException:
                self._requeue_active("admit_failed")
                raise
            active = [s for s in self.slots if s.state == "active"]
            bd.set_metadata(live=len(active), admitted=len(admitted))
            if not active:
                self._teardown_carry()
                return []
            out = self._slice_and_retire(admitted, active)
            bd.set_metadata(retired=len(out))
            return out

    def _slice_and_retire(self, admitted: List[Slot],
                          active: List[Slot]) -> List[Response]:
        """The boundary after admission: plan drafts, dispatch and wait
        for the slice, stamp completed blocks, retire finished rows."""
        tr = self.obs.tracer
        draft_mask = None
        if self.spec and admitted:
            # slice-boundary draft (re-)planning: ONLY the rows admitted
            # at this boundary get a plan — rows mid-decode already
            # drafted at their own admission
            with tr.span("sched.plan") as sp:
                fresh = {s.index for s in admitted}
                plan = [s.rs.req.task if s.index in fresh
                        and s.rs is not None else None for s in self.slots]
                dm = self.drafter.plan_remaining(
                    plan, np.asarray(self._carry.cursor))
                sp.set_metadata(draft_blocks=int(dm.sum()))
                if dm.any():
                    draft_mask = jnp.asarray(dm)
                    self.stats.draft_batches += 1
        try:
            t0 = time.perf_counter()
            with tr.span("sched.slice", live=len(active)):
                self._carry = self._slice_fn(
                    self.params, self._carry, self._mask_arr,
                    self.eos_id if self.ecfg.eos_early_exit else None,
                    draft_mask)
            with tr.span("sched.wait"):
                cursor = np.asarray(self._carry.cursor)  # blocks until ready
                t_end = time.perf_counter()
                nfe_now = int(np.asarray(self._carry.nfe))
        except BaseException:
            self._requeue_active("slice_failed")
            raise
        wall = t_end - t0
        self.stats.wall_s += wall
        self.stats.slices += 1
        nfe_delta = nfe_now - self._nfe_seen
        self._count_nfe(nfe_delta)
        self._nfe_seen = nfe_now
        self._h_dispatch.observe(wall, kind="slice")
        if tr and self.paged:
            tr.counter("pages_in_use", self.allocator.in_use)
        for slot in active:
            slot.decode_s += wall
            # every block this slice completed becomes observable now
            n = int(cursor[slot.index]) - len(slot.block_t)
            slot.block_t.extend([t_end] * n)
        with tr.span("sched.retire") as sp:
            out = self._retire_sliced()
            sp.set_metadata(rows=len(out))
        if not self.queue and \
                not any(s.state == "active" for s in self.slots):
            self._teardown_carry()
        return out

    def _requeue_active(self, event: str) -> None:
        """Back out every active row after a boundary program raised (the
        admission prefill or the slice): the carry holding their state is
        torn down, so none of them may be swallowed or leak its pages.
        Requeue FIFO (by submit time) and reclaim. The retried admission
        re-counts the request and may re-claim its calibration row, so
        back out both here."""
        tr = self.obs.tracer
        if tr:
            tr.instant(event, tid=0)
        active = [s for s in self.slots if s.state == "active"]
        for slot in sorted(active, key=lambda s: s.rs.t_submit,
                           reverse=True):
            if tr:
                tr.end("serve", tid=self.obs.slot_track(slot.index),
                       requeued=True)
                tr.abegin("queued", slot.rs.req.uid)
            self.queue.appendleft(slot.rs)
            self.stats.requests -= 1
            if slot.was_mid:
                self.stats.mid_admits -= 1
            if slot.calib_task:
                self._calibrating.pop(slot.calib_task, None)
            if self.prefix_cache:
                # re-admission re-counts the lookup (possibly with a
                # deeper match — seeds survive the failure)
                if slot.prefix_hit_pages:
                    self.stats.prefix_hits -= 1
                elif slot.prefix_len:
                    self.stats.prefix_misses -= 1
                self.stats.prefix_hit_pages -= slot.prefix_hit_pages
                self.stats.prefill_tokens_saved -= \
                    slot.prefix_hit_pages * self.dcfg.page_size
            if self.paged and slot.pages is not None:
                self.allocator.free(slot.pages)
                if self.prefix_cache:
                    self.allocator.free(slot.prefix_pages or [])
                else:
                    self.allocator.free(self._shared_for(slot))
            slot.retire()
        self._teardown_carry()

    def run(self) -> List[Response]:
        """Drain the queue: slice boundaries until nothing is queued or
        active, or nothing queued is admissible (pool too small)."""
        out: List[Response] = []
        while self.queue or any(s.state == "active" for s in self.slots):
            got = self.slice_step()
            out.extend(got)
            if not got and not any(s.state == "active" for s in self.slots):
                break
        return out
