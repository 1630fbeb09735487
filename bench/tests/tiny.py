"""A cell at a size the CPU runs in seconds, for the harness's tests."""
from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

CFG = {"name": "tiny", "family": "dense", "num_layers": 4, "d_model": 256,
       "num_heads": 8, "num_kv_heads": 2, "head_dim": 32, "d_ff": 512,
       "vocab_size": 1024, "rope_theta": 10000.0, "norm_eps": 1e-5,
       "tie_embeddings": False, "dtype": "bfloat16",
       "reference": "llama_dense", "published": {"num_layers": 2}}

TASKS = {"a": {"share": 1, "steps": [3, 2]}, "b": {"share": 1, "steps": [5, 4]}}

CLOSED = {"loop": "closed", "outstanding": 8, "batch_size": 4,
          "prompt_len": 64, "max_new_tokens": 64, "block_size": 32,
          "page_size": 16, "cycle": 8,
          "prompt_bytes": {"dist": "lognormal", "median": 30, "sigma": 0.5,
                           "min": 8, "max": 63}, "tasks": TASKS}

OPEN = {"loop": "open", "rate_per_s": 4.0, "batch_size": 4,
        "prompt_len": 96, "max_new_tokens": 64, "block_size": 32,
        "page_size": 16, "cycle": 8, "shared_doc_tokens": 48,
        "prompt_bytes": {"dist": "uniform", "min": 8, "max": 40},
        "tasks": TASKS}

# Set as the real cells' limits are, from readings at this size (closed
# loop, 1.5 s windows, 2 sampled requests): the largest of 12 sound seeds
# (1001-1012; which requests a window finishes, and so the sample, moves
# with the host's speed: two readings are given where two sets differed),
# and the smallest of 4 seeds of the int8 control (2001-2004) or of the
# fault the number is there to catch (3001-3004, ``faults.py``)
#   token_gap   0.01146, 0.02378 .. control 0.04328, token_altered 4.351
#   conf_mse    0.000120, 0.0000804 .. control 0.000691
#   order_gap   0.01829 .. wrong_position 0.4781
LIMITS = {"sample": 2, "limits": {
    "token_gap": 0.1, "conf_mse": 0.0003, "order_gap": 0.08,
    "step_mismatch": 0, "served_mismatch": 0, "unanswered": 0}}


def cell(kind: str = "closed"):
    import run
    from traffic import Mix
    mix = Mix(CLOSED if kind == "closed" else OPEN, f"tiny-{kind}")
    return run.Cell(f"tiny-{kind}", CFG, mix, [], LIMITS)
