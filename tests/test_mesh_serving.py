"""Mesh-sharded SPMD serving (SERVING.md "Sharded serving").

The contracts enforced here:

* **Sharded page ledgers** — ``ShardedPageAllocator`` partitions one
  global page id space into per-shard free lists: allocation never
  crosses a shard, exhaustion is per-shard (MemoryError even while
  another shard has pages), freed pages return to their OWNER shard, and
  ``num_shards=1`` is behaviorally identical to the base allocator.
* **Carry specs** — ``rules.carry_specs`` puts every batch-major
  ``DecodeCarry`` leaf's leading dim on ``data`` (page pool on its pages
  dim, KV head/head_dim on ``model``) iff the dim divides the axis, and
  replicates scalars — decided spec-only against a FakeMesh.
* **Padded-prefill masking** — ``prefill(valid_len=...)`` makes a padded
  row's real positions blind to its pad tail: two batched forwards
  differing only beyond ``valid_len`` write bitwise-identical KV pages
  (the bidirectional-MDLM property the batched radix seed relies on).
* **Decode identity** (subprocess, 8 fake CPU devices) — a data=2
  mesh-sharded carry decodes bitwise-identically to the single-device
  sliced runtime across layouts x epilogue fusion x slice_len, and a
  model=2 tensor-parallel carry is token-identical.
* **Shard-aware scheduler** (subprocess) — dp=2 serves the same
  responses as dp=1, a request's pages never straddle shards, per-shard
  ledgers conserve across mid-loop retirement, and a failed slice
  restores every shard's free list.
"""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.models.cache import PageAllocator, ShardedPageAllocator

pytestmark = pytest.mark.mesh


# ---------------------------------------------------------------------------
# ShardedPageAllocator: per-shard ledgers over one global id space
# ---------------------------------------------------------------------------

def test_single_shard_matches_base_allocator():
    a, b = PageAllocator(8), ShardedPageAllocator(8, num_shards=1)
    assert a.alloc(3) == b.alloc(3)           # same order: 0, 1, 2
    assert a.available == b.available == 5
    a.free([1]), b.free([1])
    assert a.alloc(2) == b.alloc(2)           # 1 comes back first
    assert a.in_use == b.in_use


def test_alloc_stays_in_shard():
    a = ShardedPageAllocator(8, num_shards=2)  # shard 0: 0-3, shard 1: 4-7
    p0, p1 = a.alloc(2, shard=0), a.alloc(2, shard=1)
    assert all(a.shard_of(p) == 0 for p in p0) and p0 == [0, 1]
    assert all(a.shard_of(p) == 1 for p in p1) and p1 == [4, 5]
    assert a.available_in(0) == a.available_in(1) == 2
    assert a.available == 4 and a.in_use == 4


def test_shard_exhaustion_is_per_shard():
    a = ShardedPageAllocator(8, num_shards=2)
    a.alloc(4, shard=0)
    with pytest.raises(MemoryError):
        a.alloc(1, shard=0)                   # shard 1 still has 4 free
    assert a.available_in(1) == 4
    assert a.alloc(1, shard=1) == [4]


def test_free_returns_to_owner_shard():
    a = ShardedPageAllocator(8, num_shards=2)
    p0, p1 = a.alloc(4, shard=0), a.alloc(4, shard=1)
    a.free(p1[:2] + p0[:2])                   # interleaved owners
    assert a.available_in(0) == 2 and a.available_in(1) == 2
    assert all(a.shard_of(p) == 0 for p in a.alloc(2, shard=0))
    assert all(a.shard_of(p) == 1 for p in a.alloc(2, shard=1))


def test_fork_shares_parent_and_allocs_private_in_shard():
    a = ShardedPageAllocator(8, num_shards=2)
    shared = a.alloc(1, shard=1)
    held, private = a.fork(shared, 2, shard=1)
    assert held == shared                     # refcount bump, same page
    assert all(a.shard_of(p) == 1 for p in held + private)
    a.free(held + private)                    # drops ref + frees private
    assert a.in_use == 1                      # parent survives its fork
    a.free(shared)
    assert a.available_in(1) == 4 and a.in_use == 0


def test_invalid_free_is_rejected_before_mutation():
    a = ShardedPageAllocator(8, num_shards=2)
    pages = a.alloc(2, shard=0)
    with pytest.raises(ValueError):
        a.free(pages + [7])                   # 7 was never allocated
    assert a.in_use == 2                      # validate-first: no change
    a.free(pages)
    assert a.in_use == 0


# ---------------------------------------------------------------------------
# carry_specs: spec-only decisions against a FakeMesh
# ---------------------------------------------------------------------------

class _FakeMesh:
    def __init__(self, data, model):
        self.axis_names = ("data", "model")
        class devices:  # noqa: N801 — mimics mesh.devices.shape
            shape = (data, model)
        self.devices = devices


def _tiny_carry(layout=""):
    from repro.config.registry import get_config
    from repro.core.decoder import init_decode_carry
    from repro.data import tokenizer as tok
    from repro.config.base import DecodeConfig
    from repro.models import model as M
    import jax.numpy as jnp
    from repro.models.cache import identity_page_table

    cfg = get_config("llada-8b").reduced()
    dcfg = DecodeConfig(max_new_tokens=8, block_size=4, page_size=4)
    kw = {}
    if layout == "paged":
        n_log = dcfg.pages_per_seq(16 + 8)
        shape = (cfg.num_layers, 2 * n_log, 4, cfg.num_kv_heads,
                 cfg.resolved_head_dim)
        dt = M.param_dtype(cfg)
        kw = dict(pool_k=jnp.zeros(shape, dt), pool_v=jnp.zeros(shape, dt),
                  page_table=identity_page_table(2, 16 + 8, 4))
    return init_decode_carry(cfg, dcfg, batch=2, prompt_len=16,
                             mask_id=tok.MASK_ID, cache_layout=layout, **kw)


def test_carry_specs_batch_on_data_scalars_replicated():
    from jax.sharding import PartitionSpec as P
    from repro.sharding import rules
    carry = _tiny_carry()
    specs = rules.carry_specs(carry, _FakeMesh(2, 1))
    assert specs.resp == P("data", None)
    assert specs.table[0] == "data" and specs.cursor == P("data")
    assert specs.nfe == P() and specs.steps_used == P()
    # dense cache [L, B, T, K, D]: batch on data
    k_spec = specs.cache["attn"]["k"]
    assert k_spec[1] == "data" and k_spec[0] is None


def test_carry_specs_divisibility_fallback():
    from jax.sharding import PartitionSpec as P
    from repro.sharding import rules
    carry = _tiny_carry()
    specs = rules.carry_specs(carry, _FakeMesh(4, 1))  # batch=2 % 4 != 0
    assert specs.resp == P(None, None)
    assert specs.cursor == P(None)


def test_carry_specs_paged_pool_and_model_axis():
    from repro.sharding import rules
    carry = _tiny_carry("paged")
    mp = carry.cache["attn"]["kp"].shape[3]  # kv heads in the reduced cfg
    specs = rules.carry_specs(carry, _FakeMesh(2, mp))
    kp = specs.cache["attn"]["kp"]           # [L, pages, ps, K, D]
    assert kp[0] is None and kp[1] == "data" and kp[3] == "model"
    assert specs.cache["attn"]["pt"][0] == "data"
    # indivisible model axis falls back to replicating the head dims
    kp7 = rules.carry_specs(carry, _FakeMesh(2, 7)).cache["attn"]["kp"]
    assert kp7[3] is None and kp7[4] is None


# ---------------------------------------------------------------------------
# prefill valid_len: pad tails are invisible to real positions
# ---------------------------------------------------------------------------

def test_prefill_valid_len_masks_pad_tail():
    """Two padded batched prefills differing ONLY beyond valid_len write
    bitwise-identical KV into the mapped pages (garbage-invariance — the
    property the batched radix seed prefill stands on)."""
    import jax
    import jax.numpy as jnp
    from repro.config.registry import get_config
    from repro.models import model as M

    cfg = get_config("llada-8b").reduced()
    params = M.init_params(jax.random.key(0), cfg)
    ps, S = 4, 8
    n_log = S // ps
    vlen = jnp.asarray([4, 8], jnp.int32)    # row 0 is half pad
    base = jax.random.randint(jax.random.key(1), (2, S), 1, 200)

    def run(garbage_seed):
        junk = jax.random.randint(jax.random.key(garbage_seed), (S,),
                                  200, 250)
        toks = base.at[0, 4:].set(junk[4:])  # row 0's pad tail varies
        dt = M.param_dtype(cfg)
        shape = (cfg.num_layers, 2 * n_log + 1, ps, cfg.num_kv_heads,
                 cfg.resolved_head_dim)
        # row 0 maps one fresh page, its pad page is dropped (-1)
        wpt = jnp.asarray([[0, -1], [1, 2]], jnp.int32)
        cache = {"attn": {
            "kp": jnp.zeros(shape, dt), "vp": jnp.zeros(shape, dt),
            "pt": wpt, "pos": jnp.full((S,), -1, jnp.int32),
            "length": jnp.zeros((), jnp.int32)}}
        _, c = M.prefill(params, cfg, toks, max_len=S, mode="full",
                         cache=cache, page_size=ps, valid_len=vlen)
        return np.asarray(c["attn"]["kp"]), np.asarray(c["attn"]["vp"])

    ka, va = run(2)
    kb, vb = run(3)
    np.testing.assert_array_equal(ka[:, :3], kb[:, :3])
    np.testing.assert_array_equal(va[:, :3], vb[:, :3])
    # and the mask actually bites: without valid_len the junk leaks
    def run_unmasked(garbage_seed):
        junk = jax.random.randint(jax.random.key(garbage_seed), (S,),
                                  200, 250)
        toks = base.at[0, 4:].set(junk[4:])
        _, c = M.prefill(params, cfg, toks, max_len=S, mode="full")
        return np.asarray(c["attn"]["k"])
    assert not np.array_equal(run_unmasked(2)[:, 0, :4],
                              run_unmasked(3)[:, 0, :4])


# ---------------------------------------------------------------------------
# subprocess: real fake-device meshes (8 CPU devices)
# ---------------------------------------------------------------------------

_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.config.base import DecodeConfig, EngineConfig
    from repro.config.registry import get_config
    from repro.core.decoder import (admit_carry_rows, init_decode_carry,
                                    make_admit_fn, make_slice_fn)
    from repro.data import tokenizer as tok
    from repro.models import model as M
    from repro.models.cache import identity_page_table

    cfg = get_config("llada-8b").reduced()
    params = M.init_params(jax.random.key(0), cfg)
    DCFG = DecodeConfig(max_new_tokens=16, block_size=4, policy="osdt",
                        mode="block", metric="q1", cap=0.9, slack=0.1,
                        threshold=0.9, page_size=4)
    PLEN, NB = 16, DCFG.num_blocks
    prompts = np.asarray(jax.random.randint(jax.random.key(3),
                                            (2, PLEN), 1, 256))
    table = np.full((2, NB, DCFG.steps_cap), 0.9, np.float32)

    def pool(dcfg):
        n_log = dcfg.pages_per_seq(PLEN + dcfg.max_new_tokens)
        shape = (cfg.num_layers, 2 * n_log, dcfg.page_size,
                 cfg.num_kv_heads, cfg.resolved_head_dim)
        dt = M.param_dtype(cfg)
        return dict(pool_k=jnp.zeros(shape, dt),
                    pool_v=jnp.zeros(shape, dt),
                    page_table=identity_page_table(
                        2, PLEN + dcfg.max_new_tokens, dcfg.page_size))

    def decode(dcfg, layout, slice_len, mesh, p=None):
        kw = dict(cache_layout=layout) if layout else {}
        pk = pool(dcfg) if layout == "paged" else {}
        carry = init_decode_carry(cfg, dcfg, batch=2, prompt_len=PLEN,
                                  mask_id=tok.MASK_ID, cache_mode="prefix",
                                  mesh=mesh, **kw, **pk)
        carry = admit_carry_rows(
            carry, [0, 1], prompts, table, tok.MASK_ID,
            page_rows=np.asarray(pk["page_table"])
            if layout == "paged" else None)
        adm = make_admit_fn(cfg, dcfg, cache_mode="prefix", **kw)
        carry = adm(p or params, carry, jnp.asarray([0, 1]))
        sf = make_slice_fn(cfg, dcfg, slice_len=slice_len,
                           cache_mode="prefix", **kw)
        mask = jnp.asarray(tok.MASK_ID, jnp.int32)
        while int(np.asarray(carry.cursor).min()) < NB:
            carry = sf(p or params, carry, mask, None, None)
        return (np.asarray(carry.resp), np.asarray(carry.seq_steps),
                int(carry.nfe))
""")

_CHILD_DECODE = _PRELUDE + textwrap.dedent("""
    from repro.launch.mesh import make_serving_mesh
    mesh = make_serving_mesh(data=2, model=1)
    out = {}
    for layout, fusion, sl in [("", "unfused", 1), ("", "fused", NB),
                               ("paged", "unfused", NB),
                               ("paged", "fused", 1)]:
        dcfg = dataclasses.replace(DCFG, step_fusion=fusion)
        base = decode(dcfg, layout, sl, None)
        got = decode(dcfg, layout, sl, mesh)
        out[f"{layout or 'dense'}/{fusion}/sl{sl}"] = dict(
            tokens=bool(np.array_equal(base[0], got[0])),
            steps=bool(np.array_equal(base[1], got[1])),
            nfe=base[2] == got[2])
    # model=2 tensor parallel: token-level identity (reductions reorder)
    from repro.launch.mesh import make_serving_mesh
    from repro.sharding.ctx import place_serving_params
    tp_mesh = make_serving_mesh(data=1, model=2)
    tp_params = place_serving_params(params, cfg, tp_mesh)
    base = decode(DCFG, "", 1, None)
    got = decode(DCFG, "", 1, tp_mesh, p=tp_params)
    out["tp2/tokens"] = bool(np.array_equal(base[0], got[0]))
    print(json.dumps(out))
""")

_CHILD_SCHED = _PRELUDE + textwrap.dedent("""
    from repro.serving.scheduler import Request, Scheduler

    def sched(dp, paged=True):
        dcfg = dataclasses.replace(DCFG, cache_layout="paged") \\
            if paged else DCFG
        return Scheduler(params, cfg, dcfg,
                         ecfg=EngineConfig(batch_size=4, prompt_len=PLEN,
                                           slice_len=1, data_parallel=dp))

    reqs = [Request(i, "alpha", f"alpha question {i}?") for i in range(6)]
    out = {}

    ref = sched(1)
    ref.submit([dataclasses.replace(r) for r in reqs])
    got_ref = {r.uid: r for r in ref.run()}

    s = sched(2)
    assert s.mesh is not None and s.slots_per_shard == 2
    s.submit([dataclasses.replace(r) for r in reqs])
    straddled, responses = False, []
    while s.queue or any(sl.state == "active" for sl in s.slots):
        responses.extend(s.slice_step())
        for sl in s.slots:
            if sl.state == "active" and sl.pages:
                shard = s.shard_of_slot(sl.index)
                if any(s.allocator.shard_of(p) != shard for p in sl.pages):
                    straddled = True
    got = {r.uid: r for r in responses}
    out["identity"] = all(got[u].text == got_ref[u].text and
                          got[u].nfe == got_ref[u].nfe for u in got_ref)
    out["never_straddles"] = not straddled
    out["conserved"] = all(
        s.allocator.available_in(sh) == s.allocator.pages_per_shard
        - len(s._shared_pages_by_shard[sh]) for sh in range(2))

    # failed slice: every shard's ledger is restored for the retry
    f = sched(2)
    real = f._slice_fn
    state = {"n": 0}
    def flaky(*a, **kw):
        state["n"] += 1
        if state["n"] == 1:
            raise RuntimeError("injected")
        return real(*a, **kw)
    f._slice_fn = flaky
    f.submit([dataclasses.replace(r) for r in reqs[:4]])
    try:
        f.slice_step()
    except RuntimeError:
        pass
    out["requeue_restores_ledgers"] = all(
        f.allocator.available_in(sh) == f.allocator.pages_per_shard
        - len(f._shared_pages_by_shard[sh]) for sh in range(2)) \\
        and f.pending() == 4
    served = f.run()
    out["retry_serves_all"] = sorted(r.uid for r in served) == [0, 1, 2, 3]
    print(json.dumps(out))
""")


def _run_child(src):
    out = subprocess.run([sys.executable, "-c", src], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_mesh_decode_identity_subprocess():
    """data=2 sharded decode is bitwise-identical to the single-device
    sliced runtime (layouts x fusion x slice_len); model=2 TP decode is
    token-identical. Subprocess: fake devices must pre-date jax init."""
    res = _run_child(_CHILD_DECODE)
    assert all(all(v.values()) for k, v in res.items()
               if isinstance(v, dict)), res
    assert res["tp2/tokens"], res


@pytest.mark.slow
def test_mesh_scheduler_shards_subprocess():
    """dp=2 scheduler: response identity vs dp=1, per-shard admission
    (a request's pages never straddle shards), per-shard page
    conservation after drain, failed-slice ledger restore + retry."""
    res = _run_child(_CHILD_SCHED)
    assert all(res.values()), res
