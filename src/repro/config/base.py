"""Configuration dataclasses for models, input shapes and meshes.

Every architecture in ``repro.configs`` instantiates :class:`ModelConfig`.
Configs are plain frozen dataclasses so they hash, compare, and serialize
cleanly (used as cache keys by the dry-run driver).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional


@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyperparameters.

    ``family`` selects the backbone assembly:
      - ``dense``  : pre-norm GQA transformer (llama-style)
      - ``moe``    : dense attention + mixture-of-experts MLP
      - ``ssm``    : Mamba2 / SSD, attention-free
      - ``hybrid`` : Mamba2 backbone with shared attention blocks (Zamba2)
      - ``vlm``    : dense transformer consuming vision-frontend embeddings
      - ``audio``  : dense transformer over codec-token embeddings
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25

    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_width: int = 4
    # hybrid: one shared attention block applied every `attn_every` layers
    attn_every: int = 0

    # --- attention options ---
    qkv_bias: bool = False
    rope_theta: float = 1.0e4
    sliding_window: int = 0  # >0: sliding-window decode variant available

    # --- misc ---
    norm_eps: float = 1.0e-5
    tie_embeddings: bool = False
    frontend: str = "none"  # none | vision | audio
    frontend_dim: int = 0   # embedding dim produced by the (stub) frontend
    supports_mdlm: bool = True  # OSDT / diffusion decoding applicable?
    mask_token_id: int = 0      # assigned at tokenizer build; 0 ok for dry-run
    dtype: str = "bfloat16"
    citation: str = ""

    # ------------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim > 0:
            return self.head_dim
        return self.d_model // self.num_heads if self.num_heads else 0

    @property
    def d_inner(self) -> int:
        """Mamba2 inner width."""
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def has_attention(self) -> bool:
        return self.family != "ssm"

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Total parameters (embedding + backbone + head), exact for our defs."""
        d, h = self.d_model, self.resolved_head_dim
        n = 0
        n += self.vocab_size * d  # embed
        if not self.tie_embeddings:
            n += self.vocab_size * d  # unembed
        per_layer = 0
        if self.family in ("dense", "moe", "vlm", "audio"):
            q = d * self.num_heads * h
            kv = 2 * d * self.num_kv_heads * h
            o = self.num_heads * h * d
            attn = q + kv + o
            if self.qkv_bias:
                attn += (self.num_heads + 2 * self.num_kv_heads) * h
            if self.is_moe:
                mlp = self.num_experts * 3 * d * self.d_ff + d * self.num_experts
            else:
                mlp = 3 * d * self.d_ff
            per_layer = attn + mlp + 2 * d  # two RMSNorm scales
        elif self.family in ("ssm", "hybrid"):
            di, s = self.d_inner, self.ssm_state
            nh = self.ssm_heads
            in_proj = d * (2 * di + 2 * s + nh)  # z, x, B, C, dt
            out_proj = di * d
            conv = self.conv_width * (di + 2 * s)
            per_layer = in_proj + out_proj + conv + nh * 2 + di + d  # A,D,norm
            if self.family == "hybrid":
                # shared attention block params counted once (weight sharing)
                q = d * self.num_heads * h
                kv = 2 * d * self.num_kv_heads * h
                o = self.num_heads * h * d
                mlp = 3 * d * self.d_ff
                n += q + kv + o + mlp + 2 * d
        n += per_layer * self.num_layers
        n += d  # final norm
        return n

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed experts)."""
        if not self.is_moe:
            return self.param_count()
        d = self.d_model
        dense_mlp_all = self.num_experts * 3 * d * self.d_ff * self.num_layers
        dense_mlp_active = self.experts_per_token * 3 * d * self.d_ff * self.num_layers
        return self.param_count() - dense_mlp_all + dense_mlp_active

    # ------------------------------------------------------------------
    def reduced(self, *, num_layers: int = 2, max_d_model: int = 256,
                max_experts: int = 4, vocab_size: int = 512) -> "ModelConfig":
        """A tiny same-family variant for CPU smoke tests."""
        d = min(self.d_model, max_d_model)
        hd = 32
        heads = max(1, d // 64)
        # keep GQA ratio ~ the original
        ratio = max(1, self.num_heads // max(1, self.num_kv_heads))
        kv = max(1, heads // ratio)
        heads = kv * ratio
        experts = min(self.num_experts, max_experts) if self.is_moe else 0
        topk = min(self.experts_per_token, experts) if experts else 0
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            num_layers=num_layers,
            d_model=d,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=hd,
            d_ff=min(self.d_ff, 4 * d) if self.d_ff else 0,
            vocab_size=vocab_size,
            num_experts=experts,
            experts_per_token=topk,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=32 if self.ssm_state else self.ssm_head_dim,
            attn_every=2 if self.attn_every else 0,
            frontend_dim=d if self.frontend != "none" else 0,
            dtype="float32",
        )


@dataclass(frozen=True)
class ShapeConfig:
    """An input-shape workload. ``kind`` picks which step gets lowered."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


@dataclass(frozen=True)
class DecodeConfig:
    """Diffusion / AR decoding parameters (the paper's §3-§4 knobs)."""

    max_new_tokens: int = 128
    block_size: int = 32
    steps_per_block: int = 0      # fixed-step baseline: 0 -> block_size (1 tok/step)
    policy: str = "static"        # fixed | static | factor | osdt
    # Fast-dLLM static threshold
    threshold: float = 0.9
    # factor variant: tau_s = threshold * factor**s
    factor: float = 0.95
    # OSDT hyperparameters (paper §4.1)
    mode: str = "block"           # block | step-block
    metric: str = "q1"            # mean | q1 | median | q3 | min-whisker
    cap: float = 0.9              # kappa
    slack: float = 0.1            # epsilon
    max_steps_per_block: int = 0  # 0 -> block_size (worst case 1 tok/step)
    # attention path for the cached block/decode steps (KERNELS.md):
    #   auto   — dense/flash by score size (XLA)
    #   dense  — force masked dense attention
    #   flash  — length-aware chunked attention (kv scan stops at the
    #            cache's valid extent)
    #   kernel — fused Pallas block-attention kernel on TPU, the length-
    #            aware flash path elsewhere
    attn_impl: str = "auto"
    # KV-cache layout (SERVING.md "Paged KV"):
    #   dense — every batch row owns a [T, Kh, D] buffer slice (the oracle)
    #   paged — rows map logical pages onto a global page pool through
    #           per-slot int32 page tables; dead rows pin zero pages and a
    #           shared system-prompt prefix is stored once (refcounted)
    cache_layout: str = "dense"
    page_size: int = 16           # cache slots per page (kernel wants >= 8)
    # denoising-step epilogue (KERNELS.md "fused step"):
    #   unfused — head matmul, confidence pass, threshold select as three
    #             separate dispatches (3 HBM passes over the logits)
    #   fused   — ops.fused_step streams lm-head logit tiles through the
    #             confidence accumulators + threshold compare in ONE
    #             kernel on TPU (bit-identical jnp chain elsewhere);
    #             threshold rule only (quota == 0)
    step_fusion: str = "unfused"
    # decode-path weight streaming (KERNELS.md "Quantized matmuls"):
    #   bf16 — weights stream in their stored dtype (the bit-identity
    #          oracle; the name covers f32-stored params too)
    #   int8 — the decode program expects params quantized ONCE at load
    #          by models.quantize.quantize_decode_params (the serving
    #          scheduler does so at construction): QKV/O, MLP and
    #          lm-head tiles stream as symmetric per-output-channel int8
    #          and dequantize in-register before each contraction (half
    #          the weight HBM bytes of bf16; NOT bit-identical — the
    #          accuracy contract is the token-match gate, KERNELS.md)
    weight_dtype: str = "bf16"

    @property
    def num_blocks(self) -> int:
        assert self.max_new_tokens % self.block_size == 0
        return self.max_new_tokens // self.block_size

    @property
    def steps_cap(self) -> int:
        return self.max_steps_per_block or self.block_size

    def pages_per_seq(self, max_len: int) -> int:
        """Logical pages covering a ``max_len``-slot cache row."""
        return -(-max_len // self.page_size)


@dataclass(frozen=True)
class EngineConfig:
    """Serving-engine / scheduler knobs (SERVING.md).

    The scheduler keeps a fixed-shape ``[batch_size, prompt_len]`` decode
    carry and advances it one compiled slice at a time; between slices
    it retires finished rows and admits queued requests into the freed
    slots. Everything per-request (threshold table, liveness, block
    cursor, EOS exit) is carry state, not program shape. The attention
    path and the weight dtype are ``DecodeConfig``'s.
    """

    batch_size: int = 4
    prompt_len: int = 64
    cache_mode: str = "prefix"    # prefix | dual | none (decoder variants)
    # retire rows at the first completed block containing EOS; dead slots
    # and retired rows stop forcing denoising steps
    eos_early_exit: bool = True
    # npz path for CalibrationStore persistence ("" disables): loaded at
    # engine construction when no store is passed explicitly, saved after
    # every new calibration
    store_path: str = ""
    # paged layout (DecodeConfig.cache_layout == "paged"):
    # total pool pages; 0 -> auto-size (shared pages + batch_size rows)
    num_pages: int = 0
    # common system prompt prepended to every request's prompt; with the
    # paged layout its KV pages are prefilled ONCE and refcount-mapped
    # into every slot (the effective shared length rounds down to a page
    # multiple so decode writes never touch a shared page)
    shared_prefix: str = ""
    # speculative block drafting (SERVING.md "Speculative drafting"):
    # decode through the variant="draft" program — blocks the task's
    # calibrated signature predicts clear in <= draft_max_steps steps are
    # drafted in one forward and verified in a second; accepted blocks
    # skip their denoising steps. Off by default: the stepped path stays
    # bit-identical to a spec_decode-free engine.
    spec_decode: bool = False
    draft_max_steps: int = 1
    # blocks decoded per compiled slice (SERVING.md "Async admission"),
    # >= 1: the scheduler returns to the host after every slice, where
    # EOS rows retire (pages reclaimed immediately) and queued requests
    # are admitted into freed slots MID-GENERATION with their own block
    # cursor, threshold table, and (spec_decode) draft mask
    slice_len: int = 1
    # radix-tree prefix cache (SERVING.md "Radix prefix cache"): page-
    # aligned multi-tenant prefix reuse. Admission walks a radix tree of
    # immutable prefix pages for the longest match on the row's
    # ``shared_prefix + Request.prefix`` stream, share()s the matched
    # pages and prefills only the novel remainder; retirement promotes
    # the row's now-immutable prompt pages back into the tree. Requires
    # the paged layout.
    # ``shared_prefix`` stops being a statically prefilled run and
    # becomes the pre-seeded first tree node instead.
    prefix_cache: bool = False
    # page budget the tree may pin (LRU-trimmed past it); 0 -> bounded
    # by the pool only (eviction happens on demand under page pressure)
    prefix_cache_pages: int = 0
    # eviction watermark: fraction of the pool kept free *beyond* the
    # pages an admission immediately needs — eviction at admission frees
    # down to (need + watermark * capacity) before load-shedding kicks in
    prefix_cache_watermark: float = 0.0
    # mesh-sharded SPMD serving (SERVING.md "Sharded serving"): shard
    # the decode batch over a ("data", "model") device mesh.
    # data_parallel partitions the slot pool into per-shard groups (a
    # request never straddles shards; batch_size and — paged — the page
    # pool must divide evenly); model_parallel runs tensor-parallel
    # decode through repro.sharding.rules' "serve" specs (dims that
    # don't divide the axis replicate — the divisibility fallback).
    # 1/1 keeps the single-device runtime bit-exactly. Slice boundaries
    # are the host-side exchange points, and only int32 metadata
    # (retired slots, freed/shared page ids, calibration ingests)
    # crosses them.
    data_parallel: int = 1
    model_parallel: int = 1
    # observability (SERVING.md "Observability") — all off by default;
    # the disabled engine's decode output and EngineStats are
    # bit-identical to a build without the subsystem:
    # record request-lifecycle / dispatch spans in a ring buffer
    # (Tracer), exported as Chrome/Perfetto trace_event JSON
    trace: bool = False
    trace_capacity: int = 1 << 16
    # score every retired row's confidence trajectory against the
    # task's stored CalibrationProfile (obs.drift.DriftMonitor); a task
    # whose windowed mean cosine drops below drift_threshold trips the
    # staleness flag — the re-calibration trigger for the future
    # online-refinement loop
    drift_telemetry: bool = False
    drift_threshold: float = 0.95
    drift_window: int = 32

    def resolved_cache_mode(self) -> str:
        assert self.cache_mode in ("prefix", "dual", "none"), self.cache_mode
        return self.cache_mode


# Canonical assigned input shapes -------------------------------------------
INPUT_SHAPES = {
    "train_4k": ShapeConfig("train_4k", seq_len=4096, global_batch=256, kind="train"),
    "prefill_32k": ShapeConfig("prefill_32k", seq_len=32768, global_batch=32, kind="prefill"),
    "decode_32k": ShapeConfig("decode_32k", seq_len=32768, global_batch=128, kind="decode"),
    "long_500k": ShapeConfig("long_500k", seq_len=524288, global_batch=1, kind="decode"),
}
