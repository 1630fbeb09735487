"""Faults planted in the timed path, to show that ``correct`` catches them.

Each fault is a context manager that yields an engine hook for
``run.execute(..., engine_hook=...)``; the hook breaks the serving engine
underneath the harness once it is built. ``bench/tests/test_correct.py``
runs them at a small size on the CPU, and ``bench/limits.py --fault``
reads them on the chip at a cell's own size.

  state_unchanged    every slice returns its carry unchanged
  half_batch         the odd rows' responses are left as they were
  token_altered      every token a slice commits is replaced by the next id
  wrong_position     the argmax fallback unmasks the least confident masked
                     position instead of the most confident one
  responses_swapped  the first two responses of a boundary trade tokens
"""
from __future__ import annotations

from contextlib import contextmanager


def _wrap_slice(fn):
    """A hook that replaces the slice program by ``fn(r, orig, params,
    carry, *rest)``. On the chip the program takes the carry's buffers
    (donation), so ``fn`` copies what it needs of the carry before the
    call."""
    def hook(r):
        orig = r.sched._slice_fn
        r.sched._slice_fn = lambda params, carry, *rest: fn(
            r, orig, params, carry, *rest)
    return hook


def _unchanged(r, orig, params, carry, *rest):
    return carry


def _half(r, orig, params, carry, *rest):
    import jax.numpy as jnp
    before = jnp.copy(carry.resp)
    out = orig(params, carry, *rest)
    odd = (jnp.arange(before.shape[0]) % 2 == 1)[:, None]
    return out._replace(resp=jnp.where(odd, before, out.resp))


def _altered(r, orig, params, carry, *rest):
    import jax.numpy as jnp
    before = jnp.copy(carry.resp)
    out = orig(params, carry, *rest)
    changed = out.resp != before
    return out._replace(resp=jnp.where(
        changed, (out.resp + 1) % r.cell.cfg["vocab_size"], out.resp))


def _swapped(r):
    orig = r.sched.slice_step

    def swapped():
        out = orig()
        if len(out) >= 2:
            out[0].token_ids, out[1].token_ids = \
                out[1].token_ids, out[0].token_ids
        return out
    r.sched.slice_step = swapped


HOOKS = {"state_unchanged": _wrap_slice(_unchanged),
         "half_batch": _wrap_slice(_half),
         "token_altered": _wrap_slice(_altered),
         "responses_swapped": _swapped}
NAMES = tuple(HOOKS) + ("wrong_position",)


@contextmanager
def planted(name: str):
    """Yield the engine hook of fault ``name``.

    ``wrong_position`` sits inside the compiled slice program, so it is
    planted in the decoder's fallback before the engine traces it, and
    taken out (with JAX's traced programs dropped) when the block ends."""
    if name != "wrong_position":
        yield HOOKS[name]
        return
    import jax

    from repro.core import decoder
    orig = decoder._threshold_fallback

    def least_confident(conf, masked, above, live):
        return orig(-conf, masked, above, live)
    decoder._threshold_fallback = least_confident
    jax.clear_caches()
    try:
        yield None
    finally:
        decoder._threshold_fallback = orig
        jax.clear_caches()
