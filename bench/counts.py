"""Operations and bytes the cell's work needs, from shapes alone.

The counting follows ``repro/roofline/analytic.py`` (copied, not
imported: the yardstick does not move with the program): a dense
layer's matmuls cost 2 FLOPs per weight per token, attention costs
4 * q_tokens * kv_tokens * heads * head_dim (scores and the weighted
sum), and the head 2 * d_model * vocab per token.

:class:`Context` turns the harness's record of the traced window (which
rows each slice decoded, at which block, and which were admitted) into
the totals the per-layer readers divide by device time.
"""
from __future__ import annotations

import math

BF16 = 2


def layer_weights(cfg: dict) -> int:
    d, H, Kh, D, F = (cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"],
                      cfg["head_dim"], cfg["d_ff"])
    return d * H * D + 2 * d * Kh * D + H * D * d + 3 * d * F


def forward_flops(cfg: dict, q_tokens: int, kv_tokens: int,
                  head: bool) -> float:
    """One forward of ``q_tokens`` queries that attend ``kv_tokens`` keys
    each (their own included), through every layer, with or without the
    lm head."""
    L, H, D = cfg["num_layers"], cfg["num_heads"], cfg["head_dim"]
    fl = L * (2.0 * q_tokens * layer_weights(cfg)
              + 4.0 * q_tokens * kv_tokens * H * D)
    if head:
        fl += 2.0 * q_tokens * cfg["d_model"] * cfg["vocab_size"]
    return fl


def paged_attn_call(cfg: dict, q_len: int, extents: list, batch: int,
                    page_size: int) -> tuple:
    """(FLOPs, bytes) of one layer's paged block-attention call: each of
    ``batch`` rows attends its ``q_len`` fresh queries to its own fresh
    keys and to its cached extent (``extents``, one per row that reads
    the cache; the other rows read no page)."""
    H, Kh, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    fl = 4.0 * q_len * H * D * (batch * q_len + sum(extents))
    pages = sum(math.ceil(e / page_size) for e in extents)
    by = (pages * page_size * Kh * D * 2 * BF16        # cached K and V
          + batch * q_len * Kh * D * 2 * BF16          # fresh K and V
          + 2 * batch * q_len * H * D * BF16)          # q in, out
    return fl, by


def fused_step_call(cfg: dict, rows: int) -> tuple:
    """(FLOPs, bytes) of one fused lm-head + confidence + threshold call
    over ``rows`` hidden rows: the head is read once."""
    d, V = cfg["d_model"], cfg["vocab_size"]
    return 2.0 * rows * d * V, d * V * BF16 + rows * d * BF16 + rows * 20


def bound_s(fl: float, by: float, pk: dict) -> tuple:
    """(seconds at the roofline, which bound binds)."""
    tf, tb = fl / pk["bf16_flops"], by / pk["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")


class Context:
    """What the per-layer readers read: the run, the trace reduction, the
    peaks, and the cell's needed work over the traced window."""

    def __init__(self, run, red, pk: dict):
        self.run, self.red, self.pk = run, red, pk
        self.cfg, self.mix = run.cell.cfg, run.mix
        self.notes: list = []
        t_end = getattr(run, "t_trace_end", run.t1)
        self.slices = [s for s in run.slices
                       if s["t0"] >= run.t0 and s["t1"] <= t_end]
        self.window_s = red.window_s if red is not None else t_end - run.t0
        self._tally()

    def _tally(self) -> None:
        cfg, mix, run = self.cfg, self.mix, self.run
        P, bs, B, ps = (mix.prompt_len, mix.block_size, mix.batch_size,
                        mix.page_size)
        Sp = mix.doc_tokens
        L = cfg["num_layers"]
        fused_on = cfg.get("serve", {}).get("step_fusion", "fused") == "fused"
        self.needed = 0.0
        self.forwards = 0          # slice-program forwards (steps, commits)
        attn = [0.0, 0.0, 0.0]     # FLOPs, bytes, seconds at the roofline
        fused = [0.0, 0.0, 0.0]
        binds = {"attn": {}, "fused": {}}

        def add(acc, key, fl, by, n):
            t, which = bound_s(fl, by, self.pk)
            acc[0] += n * fl
            acc[1] += n * by
            acc[2] += n * t
            binds[key][which] = binds[key].get(which, 0) + n

        for s in self.slices:
            rows = s["rows"]
            steps = [mix.steps(run.reqs[u].task)[b] for u, b in rows]
            ext = [P + b * bs for _, b in rows]
            for k, e in zip(steps, ext):
                self.needed += k * forward_flops(cfg, bs, e + bs, True)
                self.needed += forward_flops(cfg, bs, e + bs, False)
            n_fwd = max(steps) + 1
            self.forwards += n_fwd
            fl, by = paged_attn_call(cfg, bs, ext, B, ps)
            add(attn, "attn", fl, by, n_fwd * L)
            if fused_on:
                fl, by = fused_step_call(cfg, B * bs)
                add(fused, "fused", fl, by, max(steps))
            if s["admitted"]:
                own = P - Sp
                self.needed += len(s["admitted"]) * forward_flops(
                    cfg, own, P, False)
                if Sp:
                    fl, by = paged_attn_call(
                        cfg, own, [Sp] * len(s["admitted"]), B, ps)
                    add(attn, "attn", fl, by, L)
        self.attn, self.fused, self.binds = attn, fused, binds
        self.notes.append(
            f"counts: {len(self.slices)} slices in the traced window, "
            f"{self.forwards} slice forwards, needed {self.needed:.4g} "
            f"FLOP; paged attention {attn[0]:.4g} FLOP {attn[1]:.4g} B "
            f"(calls bound by {binds['attn']}); fused step {fused[0]:.4g} "
            f"FLOP {fused[1]:.4g} B (calls bound by {binds['fused']})")

    def roofline(self, kernel: str):
        """Share (%) of the kernel's device time that its roofline bound
        takes, or None when the trace shows no time for it."""
        if self.red is None:
            return None
        t = self.red.kernel_s.get(kernel, 0.0)
        need = {"paged_block_attention": self.attn,
                "fused_step": self.fused}[kernel][2]
        if t <= 0 or need <= 0:
            return None
        return 100.0 * need / t

    def program_share(self, program: str):
        if self.red is None or self.red.window_s <= 0:
            return None
        t = self.red.program_s.get(program)
        if t is None:
            return None
        return 100.0 * t / self.red.window_s
