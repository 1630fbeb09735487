"""The comparison that decides ``correct``, shown to fail where it must.

Runs the harness on the CPU at a size a test run holds (``tiny.py``),
skipping only its look for a chip:
  * sound runs, closed and open loop, come out correct;
  * the control, the reference computed in int8 (the precision below
    the configuration's bfloat16) in the program's place, comes out not
    correct;
  * the timed path broken underneath comes out not correct, once for
    each fault a one-chip serving cell can have: a slice that returns its
    state unchanged, half of the batch left out, a token altered where it
    is produced, the argmax fallback unmasking the least confident
    position, and responses handed to the wrong requests
    (``faults.py``). (The exchange between chips does not exist on one
    chip.)

  JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

The limits of the tiny cell were set from its own readings, as the real
cells' were from theirs (``tiny.py``).
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import tiny  # noqa: E402

import faults  # noqa: E402  (tiny puts bench/ on the path)
import run  # noqa: E402

SECONDS = 1.5


def _run(kind="closed", seed=101, hook=None, control=""):
    return run.execute(tiny.cell(kind), seed, SECONDS, False,
                       control=control, engine_hook=hook)


@pytest.mark.parametrize("kind,seed", [("closed", 101), ("closed", 102),
                                       ("open", 103)])
def test_sound_run_is_correct(kind, seed):
    r = _run(kind, seed)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("seed", [201, 202, 203])
def test_int8_control_is_not_correct(seed):
    r = _run(seed=seed, control="ref-int8")
    assert not r["correct"], r["checks"]


def _faulty(name, monkeypatch):
    # on the chip the slice program takes the carry's buffers; so here
    from repro.core import decoder
    monkeypatch.setattr(decoder, "_donate_default", lambda: True)
    with faults.planted(name) as hook:
        return _run(hook=hook)


def test_state_unchanged_is_not_correct(monkeypatch):
    r = _faulty("state_unchanged", monkeypatch)
    assert not r["correct"], r["checks"]


def test_half_batch_left_out_is_not_correct(monkeypatch):
    r = _faulty("half_batch", monkeypatch)
    assert not r["correct"], r["checks"]


def test_token_altered_is_not_correct(monkeypatch):
    r = _faulty("token_altered", monkeypatch)
    assert not r["correct"], r["checks"]
    assert r["checks"]["token_gap"]["value"] > \
        r["checks"]["token_gap"]["limit"]


def test_wrong_position_is_not_correct(monkeypatch):
    r = _faulty("wrong_position", monkeypatch)
    assert not r["correct"], r["checks"]
    assert r["checks"]["order_gap"]["value"] > \
        r["checks"]["order_gap"]["limit"]


def test_responses_to_wrong_requests_is_not_correct(monkeypatch):
    r = _faulty("responses_swapped", monkeypatch)
    assert not r["correct"], r["checks"]


def test_sound_run_after_a_planted_fault_is_correct(monkeypatch):
    assert not _faulty("wrong_position", monkeypatch)["correct"]
    r = _run(seed=104)
    assert r["correct"], r["checks"]
