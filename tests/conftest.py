import os

# Tests must see the real (single) CPU device — the fake-device override is
# for the dry-run driver (repro/launch/dryrun.py) and the mesh-serving CI
# leg, which opts in explicitly with REPRO_MESH_TESTS=1 (ci.yml).
assert os.environ.get("REPRO_MESH_TESTS") == "1" or \
    "xla_force_host_platform_device_count" not in \
    os.environ.get("XLA_FLAGS", ""), \
    "do not run tests with the dry-run XLA_FLAGS set " \
    "(set REPRO_MESH_TESTS=1 for the fake-device mesh leg)"

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return jax.random.key(0)


def _generate_reference(sched, requests):
    """What the monolithic oracle ``make_generate_fn`` decodes for
    ``requests`` laid into ``sched``'s first slots as ONE batch: the rows
    the scheduler builds (``_prompt_row``), its per-task tables and draft
    plan, its page pool with the shared prefix prefilled, every other
    slot a mask-only dead row. ``sched`` must be idle (no live carry) and
    is left untouched. Returns ``(by_uid, result)``: uid -> ``(text, nfe,
    blocks_drafted, blocks_accepted)`` truncated at EOS as the scheduler
    delivers them, and the raw ``GenerateResult``."""
    from repro.core.decoder import make_generate_fn
    from repro.data import tokenizer as tok
    from repro.serving.scheduler import RequestState

    assert sched.carry is None, "the oracle reads the parked page pool"
    B, P = sched.ecfg.batch_size, sched.ecfg.prompt_len
    rows = [sched._prompt_row(RequestState(r, 0.0)) for r in requests]
    rows += [[sched.mask_id] * P] * (B - len(requests))
    tasks = [r.task for r in requests] + ["__dead__"] * (B - len(requests))
    live = np.arange(B) < len(requests)
    gen = make_generate_fn(
        sched.cfg, sched.dcfg, cache_mode=sched.ecfg.resolved_cache_mode(),
        cache_layout="paged" if sched.paged else "dense",
        shared_prefix_len=sched.shared_len if sched.paged else 0,
        variant="draft" if sched.spec else "step",
        weight_dtype=sched.weight_dtype)
    args = [sched.params, jnp.asarray(np.asarray(rows, np.int32)),
            jnp.asarray(sched.store.tables_for(tasks)),
            jnp.asarray(sched.mask_id, jnp.int32), jnp.asarray(live),
            sched.eos_id if sched.ecfg.eos_early_exit else None]
    if sched.paged:
        shared = list(sched._shared_pages)
        spare = [p for p in range(sched.allocator.num_pages)
                 if p not in set(shared)]
        per = sched.private_per_slot
        pt = np.full((B, sched.n_log), -1, np.int32)
        for i in range(len(requests)):
            pt[i, :len(shared)] = shared
            pt[i, len(shared):] = spare[i * per:(i + 1) * per]
        args += [sched._pool_k, sched._pool_v, jnp.asarray(pt)]
    kw = {}
    if sched.spec:
        kw["draft_mask"] = jnp.asarray(sched.drafter.mask_for(tasks))
    res = gen(*args, **kw)
    tokens = np.asarray(res.tokens)
    seq_steps = np.asarray(res.seq_steps)
    drafted = np.asarray(res.blocks_drafted)
    accepted = np.asarray(res.blocks_accepted)
    by_uid = {}
    for j, r in enumerate(requests):
        row = tokens[j].tolist()
        if sched.eos_id in row:
            row = row[:row.index(sched.eos_id)]
        row = [t for t in row if t != sched.mask_id]
        by_uid[r.uid] = (tok.decode(row), int(seq_steps[j].sum()),
                         int(drafted[j]), int(accepted[j]))
    return by_uid, res


@pytest.fixture(scope="session")
def generate_reference():
    """The monolithic ``make_generate_fn`` oracle over a scheduler's own
    rows, tables and pool (``_generate_reference``)."""
    return _generate_reference
