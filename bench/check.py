"""The comparison that decides ``correct``.

After the window closes, a sample of the requests the timed path
finished, drawn from the seed, is replayed through the plain reference
of the configuration (``bench/references/<name>.py``) at float32. For
each sampled request the harness kept what the timed path produced for
its row: the response tokens, and the decoder's per-step record of which
positions were still masked and their confidences. That record fixes the
order in which the program unmasked the block, so the reference can be
evaluated at exactly the step states the program saw (teacher forcing).

Numbers read (``bench/limits/<cell>`` names the ones compared, each with
its limit):
  token_gap       widest gap by which a served token's reference logit
                  lies below the reference's best logit, at the step
                  where it was unmasked (greedy tokens: 0 up to rounding)
  token_gap_mean  that gap averaged over every served token compared
  conf_dlog       widest |log conf| gap between the confidence the
                  program recorded for a masked position and the
                  reference's (the epilogue's output)
  conf_mse        the mean of that gap squared over every recorded
                  masked position
  order_gap       widest log-confidence gap between the reference's most
                  confident masked position and the one the program's
                  argmax fallback unmasked
  step_mismatch   blocks whose step count, or whose one-position-per-step
                  fallback pattern, departs from the task's table (exact,
                  over every finished request)
  served_mismatch responses whose delivered tokens differ from the row
                  the slice program produced, or that hold a mask (exact,
                  over every finished request)
  unanswered      requests due in the window that never got a response
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from traffic import EOS_ID, MASK_ID, Mix, Req, prompt_ids



@dataclass
class Record:
    """What the timed path produced for one request's row."""
    resp: np.ndarray       # [N] int32 response tokens
    conf: np.ndarray       # [nb, sc, bs] float32 recorded confidences
    valid: np.ndarray      # [nb, sc, bs] bool: position masked at step s
    steps: np.ndarray      # [nb] int32 denoising steps per block
    served: tuple          # token ids the response delivered


def load_reference(root: Path, name: str):
    path = root / "references" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_limits(root: Path, cell: str) -> dict:
    return json.loads((root / "limits" / f"{cell}.json").read_text())


def unmask_step(valid: np.ndarray) -> np.ndarray:
    """[nb, bs]: the step at which each position was unmasked — the last
    step the decoder recorded it as masked (-1: never recorded)."""
    sc = valid.shape[1]
    idx = np.arange(sc)[None, :, None]
    return np.where(valid, idx, -1).max(axis=1)


def _inputs(mix: Mix, req: Req, rec: Record, doc: str):
    row, shared = prompt_ids(req.prompt, mix.prompt_len, doc, mix.page_size)
    bs, nb, P = mix.block_size, mix.num_blocks, mix.prompt_len
    u = unmask_step(rec.valid)
    states, starts, served, where = [], [], [], []
    for b in range(nb):
        blk = rec.resp[b * bs:(b + 1) * bs]
        for s in range(int(rec.steps[b])):
            states.append(np.where(u[b] < s, blk, MASK_ID))
            starts.append(P + b * bs)
            served.append(blk)
            where.append((b, s))
    own = row[shared:]
    seg = np.concatenate([np.zeros(len(own), np.int32),
                          1 + np.repeat(np.arange(nb, dtype=np.int32), bs)])
    return row[:shared], {
        "tokens": np.concatenate([own, rec.resp]).astype(np.int32),
        "seg": seg, "states": np.asarray(states, np.int32),
        "starts": np.asarray(starts, np.int32),
        "served": np.asarray(served, np.int32)}, where, u


def structural(mix: Mix, req: Req, rec: Record) -> tuple:
    """(step_mismatch, served_mismatch) of one request, exact."""
    steps = mix.steps(req.task)
    u = unmask_step(rec.valid)
    bad = 0
    for b, k in enumerate(steps):
        per_step = np.bincount(u[b][u[b] >= 0], minlength=mix.block_size)
        if (int(rec.steps[b]) != k or (u[b] < 0).any()
                or (per_step[:k - 1] != 1).any() or per_step[k:].any()):
            bad += 1
    row = [int(t) for t in rec.resp]
    if EOS_ID in row:
        row = row[:row.index(EOS_ID)]
    row = tuple(t for t in row if t != MASK_ID)
    return bad, int(row != tuple(rec.served) or MASK_ID in rec.resp)


def _numbers(sample, wheres, us, ref_stats, logconf_of, pick_of):
    """The numbers, given per request the reference's statistics, the
    compared log-confidences ``logconf_of(k, b, s)`` and the compared
    choice of unmasked positions ``pick_of(k, b, s)``."""
    token_gap = conf_dlog = order_gap = gap_sum = 0.0
    tokens, sq, n_conf = 0, 0.0, 0
    for k, ((req, rec), (mx, lse, sv, _), where, u) in enumerate(
            zip(sample, ref_stats, wheres, us)):
        logconf = mx - lse                        # [S, bs]
        for i, (b, s) in enumerate(where):
            now = u[b] == s                       # unmasked at this step
            masked = rec.valid[b, s]
            tokens += int(now.sum())
            gap = (mx[i] - sv[i])[now]
            token_gap = max(token_gap, float(gap.max()))
            gap_sum += float(gap.sum())
            d = np.abs(logconf_of(k, i, b, s)[masked] - logconf[i][masked])
            conf_dlog = max(conf_dlog, float(d.max()))
            sq += float((d * d).sum())
            n_conf += d.size
            if s < int(rec.steps[b]) - 1:         # an argmax fallback step
                best = logconf[i][masked].max()
                order_gap = max(order_gap, float(
                    best - logconf[i][pick_of(k, i, b, s)].max()))
    return {"token_gap": token_gap, "token_gap_mean": gap_sum / max(tokens, 1),
            "conf_dlog": conf_dlog, "conf_mse": sq / max(n_conf, 1),
            "order_gap": order_gap, "tokens": tokens}


def _prepare(mix, doc, sample):
    pre, inputs, wheres, us = None, [], [], []
    for req, rec in sample:
        pre, inp, where, u = _inputs(mix, req, rec, doc)
        inputs.append(inp)
        wheres.append(where)
        us.append(u)
    return pre, inputs, wheres, us


def compare(ref_mod, cfg: dict, seed: int, mix: Mix, doc: str,
            sample: list) -> dict:
    """The program's numbers on the sampled requests ``[(req, rec)]``,
    against the float32 reference."""
    pre, inputs, wheres, us = _prepare(mix, doc, sample)
    stats = ref_mod.Reference(cfg, seed, MASK_ID).stats(pre, inputs)
    return _numbers(
        sample, wheres, us, stats,
        lambda k, i, b, s: np.log(np.where(sample[k][1].valid[b, s],
                                           sample[k][1].conf[b, s], 1.0)),
        lambda k, i, b, s: us[k][b] == s)


def control(ref_mod, cfg: dict, seed: int, mix: Mix, doc: str,
            sample: list) -> dict:
    """The control's numbers: the reference computed in int8, put in the
    program's place at the same step states (the prompts and the
    program's served tokens). At each position the token the int8
    computation puts first is read against the float32 reference, and
    so are its confidences; at each fallback step, the position it
    would unmask."""
    pre, inputs, wheres, us = _prepare(mix, doc, sample)
    low = ref_mod.Reference(cfg, seed, MASK_ID, quant=True).stats(pre, inputs)
    for inp, (_, _, _, am) in zip(inputs, low):
        inp["served"] = np.asarray(am, np.int32)
    stats = ref_mod.Reference(cfg, seed, MASK_ID).stats(pre, inputs)

    def pick(k, i, b, s):
        lc = low[k][0][i] - low[k][1][i]
        masked = sample[k][1].valid[b, s]
        return np.arange(len(lc)) == int(np.argmax(np.where(masked, lc,
                                                            -np.inf)))
    return _numbers(sample, wheres, us, stats,
                    lambda k, i, b, s: low[k][0][i] - low[k][1][i], pick)


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]) for each number the cell's
    limits name, in their order."""
    rows, ok = [], True
    for k in limits:
        v, lim = numbers[k], limits[k]
        rows.append((k, v, lim))
        if lim is None or not v <= lim:
            ok = False
    return ok, rows
