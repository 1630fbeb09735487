"""Seeded traffic from a mix's data file (``bench/traffic/<name>.json``).

One general generator reads every mix. A mix names its loop (``closed``:
a fixed number of requests outstanding; ``open``: Poisson arrivals at a
fixed rate), the engine geometry its requests need, the prompt-size
distribution, an optional shared document, and its tasks: each with a
share of the mix and a steps-per-block profile.

Every seed gets the same sizes, tasks and inter-arrival gaps in the same
order: they are the distribution's quantiles at evenly spaced levels over
a cycle of ``cycle`` requests, each cycle shuffled by a stream that does
not depend on the seed. Runs of different seeds therefore hold the same
work and meet the same bursts; with some 40 requests in a window, a p90
that followed a seed-drawn arrival order would swing by about 20% from
seed to seed. The seed draws the prompts' bytes (32..126, one token each
under the system's byte tokenizer), the shared document and the weights.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

# byte tokenizer layout of the system under test: ids 0-255 are bytes
PAD_ID, BOS_ID, EOS_ID, MASK_ID = 256, 257, 258, 259


@dataclass
class Req:
    uid: int
    task: str
    prompt: str
    due: float = 0.0     # seconds after the generator's start (open loop)


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` sizes at evenly spaced quantile levels of ``spec``."""
    lv = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in lv])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + lv * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


class Mix:
    """A traffic mix as its data file states it."""

    def __init__(self, d: dict, name: str):
        self.name = name
        self.spec = d
        self.loop = d["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"{name}: loop must be closed or open")
        self.batch_size = int(d["batch_size"])
        self.prompt_len = int(d["prompt_len"])
        self.max_new_tokens = int(d["max_new_tokens"])
        self.block_size = int(d["block_size"])
        self.page_size = int(d["page_size"])
        self.num_blocks = self.max_new_tokens // self.block_size
        self.cycle = int(d["cycle"])
        self.prompt_bytes = d["prompt_bytes"]
        self.doc_tokens = int(d.get("shared_doc_tokens", 0))
        self.rate = float(d.get("rate_per_s", 0.0))
        self.outstanding = int(d.get("outstanding", 0))
        self.tasks = {t: (int(v["share"]), [int(k) for k in v["steps"]])
                      for t, v in d["tasks"].items()}
        for t, (_, steps) in self.tasks.items():
            if len(steps) != self.num_blocks or not all(
                    1 <= k <= self.block_size for k in steps):
                raise ValueError(f"{name}: task {t} needs {self.num_blocks} "
                                 f"steps per block in 1..{self.block_size}")
        if self.cycle % sum(s for s, _ in self.tasks.values()):
            raise ValueError(f"{name}: cycle must hold whole task periods")
        if self.loop == "open" and self.rate <= 0:
            raise ValueError(f"{name}: an open loop needs rate_per_s")

    @classmethod
    def load(cls, path) -> "Mix":
        path = Path(path)
        return cls(json.loads(path.read_text()), path.stem)

    def steps(self, task: str) -> List[int]:
        return self.tasks[task][1]

    def tables(self) -> dict:
        """Per task, the ``[num_blocks, steps_cap]`` threshold table that
        makes block b take exactly ``steps[b]`` denoising steps whatever
        the weights: no confidence exceeds 2, so steps before the last
        unmask only the single most confident position (the decoder's
        fallback), and every confidence exceeds -1, so the last step
        unmasks the rest."""
        out = {}
        for t, (_, steps) in self.tasks.items():
            tab = np.full((self.num_blocks, self.block_size), 2.0, np.float32)
            for b, k in enumerate(steps):
                tab[b, k - 1:] = -1.0
            out[t] = tab
        return out

    def shared_doc(self, seed: int) -> str:
        """The shared document: ``doc_tokens`` tokens once the system's
        BOS is put before it."""
        if not self.doc_tokens:
            return ""
        rng = np.random.default_rng([int(seed), 1])
        return _text(rng, self.doc_tokens - 1)

    def requests(self, seed: int, stream: int = 0) -> Iterator[Req]:
        """Requests in order of uid, without end. Open loops carry due
        times (seconds from the start of arrivals). Each ``stream`` has
        its own order and numbers its uids from ``stream * 10**6`` (the
        warm-up draws from stream 1, the window from stream 0)."""
        order = np.random.default_rng([2, int(stream)])
        rng = np.random.default_rng([int(seed), 2, int(stream)])
        sizes = _quantiles(self.prompt_bytes, self.cycle)
        period = []
        for t, (share, _) in self.tasks.items():
            period += [t] * share
        tasks = period * (self.cycle // len(period))
        gaps = None
        if self.loop == "open":
            lv = (np.arange(self.cycle) + 0.5) / self.cycle
            gaps = -np.log1p(-lv)
            gaps *= 1.0 / (self.rate * gaps.mean())
        uid, due = int(stream) * 10**6, 0.0
        while True:
            s = order.permutation(sizes)
            t = order.permutation(tasks)
            g = order.permutation(gaps) if gaps is not None else None
            for i in range(self.cycle):
                if g is not None:
                    due += float(g[i])
                yield Req(uid, str(t[i]), _text(rng, int(s[i])), due)
                uid += 1


def _text(rng, n: int) -> str:
    return bytes(rng.integers(32, 127, n, dtype=np.uint8)).decode()


def prompt_ids(prompt: str, prompt_len: int, doc: str = "",
               page_size: int = 16) -> tuple:
    """The token row the system builds from a request: ``(row, shared)``.

    The byte tokenizer puts BOS before the text. A shared document is
    cut to whole pages (at most ``prompt_len - page_size`` tokens) and
    laid first; the request's own ids follow, left-padded with PAD to
    fill the row. ``shared`` is the document's length in tokens.
    """
    shared: list = []
    if doc:
        d = [BOS_ID] + list(doc.encode())
        cap = (max(prompt_len - page_size, 0) // page_size) * page_size
        shared = d[:min((len(d) // page_size) * page_size, cap)]
    own = [BOS_ID] + list(prompt.encode())
    room = prompt_len - len(shared)
    own = own[-room:]
    return np.asarray(shared + [PAD_ID] * (room - len(own)) + own,
                      np.int32), len(shared)


def mean_steps(mix: Mix) -> float:
    """Steps per block averaged over the mix's tasks and blocks."""
    tot = sum(s * sum(k) for s, k in mix.tasks.values())
    return tot / (sum(s for s, _ in mix.tasks.values()) * mix.num_blocks)


def describe(mix: Mix) -> str:
    kind = (f"open loop, Poisson {mix.rate:g}/s" if mix.loop == "open"
            else f"closed loop, {mix.outstanding} outstanding")
    doc = f", shared document {mix.doc_tokens} tokens" if mix.doc_tokens \
        else ""
    return (f"{mix.name}: {kind}; batch {mix.batch_size}, prompt "
            f"{mix.prompt_len}, {mix.num_blocks} blocks of {mix.block_size}"
            f"{doc}; tasks " + ", ".join(
                f"{t} x{s} steps/block {k}" for t, (s, k) in
                mix.tasks.items()) + f"; mean {mean_steps(mix):.2f} steps/"
            f"block")
