"""Plain float32 reference of a dense pre-norm transformer (llama layout),
run under the block-causal attention of cached block diffusion.

It imports nothing of the system under test. It knows the system's
parameter layout (the names and shapes of the weights it is handed) and
draws those weights itself from the seed, so the program and the
reference read the same bfloat16 values without the reference taking
anything the program made.

The architecture, as published for LLaDA-8B and DeepSeek LLM 67B:
  x = embed[tokens]
  per layer:  h = rms(x) * ln1;  q, k, v = h Wq, h Wk, h Wv;  RoPE(q, k)
              a = softmax(q k^T / sqrt(D)) v, kv head = q head // (H / Kh)
              x = x + a Wo
              h = rms(x) * ln2;  x = x + (silu(h Wg) * (h Wu)) Wd
  logits = (rms(x) * final_norm) Whead
RoPE rotates interleaved pairs (x[2i], x[2i+1]) by pos * theta^(-2i/D).

Attention is block-causal, the semantics of the prefix KV cache that
the serving path implements: the sequence is cut into segments (the
shared document, the rest of the prompt, then each response block), and
a query sees every key of its own segment and of the segments before it.
A denoising step of block b is the same forward with block b replaced by
its partly masked state, so the reference evaluates each recorded step
state against the keys of the segments before block b plus the state's
own keys. Nothing is cached across calls; the context's keys are
computed once per layer and shared by the step states of that request,
which changes no value because later segments never reach earlier ones.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

# weight leaves: path -> (shape function of the config, init std kind)
_LAYER = {
    ("ln1",): lambda c: (c["d_model"],),
    ("wq",): lambda c: (c["d_model"], c["num_heads"] * c["head_dim"]),
    ("wk",): lambda c: (c["d_model"], c["num_kv_heads"] * c["head_dim"]),
    ("wv",): lambda c: (c["d_model"], c["num_kv_heads"] * c["head_dim"]),
    ("wo",): lambda c: (c["num_heads"] * c["head_dim"], c["d_model"]),
    ("ln2",): lambda c: (c["d_model"],),
    ("mlp", "wi_gate"): lambda c: (c["d_model"], c["d_ff"]),
    ("mlp", "wi_up"): lambda c: (c["d_model"], c["d_ff"]),
    ("mlp", "wo"): lambda c: (c["d_ff"], c["d_model"]),
}
_TOP = {
    ("embed",): lambda c: (c["vocab_size"], c["d_model"]),
    ("final_norm",): lambda c: (c["d_model"],),
    ("head",): lambda c: (c["d_model"], c["vocab_size"]),
}


def _key(seed: int):
    """A PRNG key for any non-negative seed up to 2**63 (two 32-bit
    halves), so large seeds stay distinct."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def _leaf(key, path, shape, cfg, mask_id):
    """One weight leaf, drawn in float32 and rounded to bfloat16: norms
    near 1, projections N(0, 1/fan_in), the embedding N(0, 0.02^2). The
    head's column of the mask token is zero: a trained mask predictor
    never puts the mask token first, and random weights would, now and
    then, which would stall a block on a position that stays masked."""
    name = path[-1]
    if name.startswith("ln") or name == "final_norm":
        w = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    elif name == "embed":
        w = 0.02 * jax.random.normal(key, shape, jnp.float32)
    else:
        w = jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[0])
    w = w.astype(jnp.bfloat16)
    if name == "head":
        w = w.at[:, mask_id].set(0)
    return w


def _leaf_key(base, path):
    return jax.random.fold_in(base, zlib.crc32("/".join(path).encode())
                              & 0x7FFFFFFF)


def _set(tree, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def draw_params(cfg: dict, seed: int, mask_id: int) -> dict:
    """The program's parameter tree, bfloat16, on the default device, in
    one jitted call. Layer weights are stacked on a leading layer axis;
    layer l of leaf ``path`` is drawn from ``fold_in(leaf key, l)``, the
    same key the reference draws that layer from."""
    L = cfg["num_layers"]

    def draw(base):
        out: dict = {}
        for path, shp in _TOP.items():
            _set(out, path, _leaf(_leaf_key(base, path), path, shp(cfg),
                                  cfg, mask_id))
        layers: dict = {}
        for path, shp in _LAYER.items():
            k = _leaf_key(base, ("layers",) + path)
            _set(layers, path, jax.vmap(
                lambda l, k=k, path=path, shp=shp: _leaf(
                    jax.random.fold_in(k, l), path, shp(cfg), cfg,
                    mask_id))(jnp.arange(L)))
        out["layers"] = layers
        return out

    return jax.jit(draw)(_key(seed))


def _draw_top(cfg, seed, mask_id):
    base = _key(seed)
    return {path[-1]: _leaf(_leaf_key(base, path), path, shp(cfg), cfg,
                            mask_id).astype(jnp.float32)
            for path, shp in _TOP.items()}


def _draw_layer(cfg, seed, mask_id, layer):
    base = _key(seed)
    return {"/".join(path): _leaf(
        jax.random.fold_in(_leaf_key(base, ("layers",) + path), layer),
        path, shp(cfg), cfg, mask_id).astype(jnp.float32)
        for path, shp in _LAYER.items()}


# ---------------------------------------------------------------------------
# float32 math (``quant``: every matmul's operands rounded to symmetric
# int8 first, per row of the left operand and per column of the right,
# over the contracted axis: the computation in int8 that serves as the
# comparison's lower-precision control)
# ---------------------------------------------------------------------------

def _fq(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(a, w, quant):
    return _fq(a, -1) @ _fq(w, 0) if quant else a @ w

def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x [..., T, H, D], pos [..., T]: rotate interleaved pairs."""
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = pos.astype(jnp.float32)[..., None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _qkv(w, cfg, x, pos, quant):
    H, Kh, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    h = _rms(x, w["ln1"], cfg["norm_eps"])
    q = _mm(h, w["wq"], quant).reshape(x.shape[:-1] + (H, D))
    k = _mm(h, w["wk"], quant).reshape(x.shape[:-1] + (Kh, D))
    v = _mm(h, w["wv"], quant).reshape(x.shape[:-1] + (Kh, D))
    return (_rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"]),
            v)


def _attend(q, k, v, keep, quant):
    """q [..., Tq, H, D]; k, v [..., Tk, Kh, D]; keep [..., Tq, Tk]."""
    H, Kh, D = q.shape[-2], k.shape[-2], q.shape[-1]
    k = jnp.repeat(k, H // Kh, axis=-2)
    v = jnp.repeat(v, H // Kh, axis=-2)
    if quant:
        q, k, v = _fq(q, -1), _fq(k, -1), _fq(v, -3)
    s = jnp.einsum("...qhd,...khd->...hqk", q, k) / np.sqrt(D)
    s = jnp.where(keep[..., None, :, :], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    if quant:
        p = _fq(p, -1)
    return jnp.einsum("...hqk,...khd->...qhd", p, v)


def _finish(w, cfg, x, a, quant):
    x = x + _mm(a.reshape(x.shape[:-1] + (-1,)), w["wo"], quant)
    h = _rms(x, w["ln2"], cfg["norm_eps"])
    g = _mm(h, w["mlp/wi_gate"], quant)
    u = _mm(h, w["mlp/wi_up"], quant)
    return x + _mm(jax.nn.silu(g) * u, w["mlp/wo"], quant)


def _ctx_layer(w, cfg, x, seg, pk, pv, quant, chunk=512):
    """Block-causal layer over a sequence x [T, M] that follows a prefix
    of keys pk, pv [T0, Kh, D] (possibly empty) which every position
    sees; seg [T] orders the sequence's own segments. Queries go in
    chunks so the score matrix stays small."""
    T0 = pk.shape[0]
    pos = T0 + jnp.arange(x.shape[0])
    q, k, v = _qkv(w, cfg, x, pos, quant)
    kk = jnp.concatenate([pk, k], 0)
    vv = jnp.concatenate([pv, v], 0)
    seg_all = jnp.concatenate([jnp.full((T0,), -1, seg.dtype), seg])
    out = []
    for i in range(0, x.shape[0], chunk):
        keep = seg_all[None, :] <= seg[i:i + chunk, None]
        out.append(_attend(q[i:i + chunk], kk, vv, keep, quant))
    return _finish(w, cfg, x, jnp.concatenate(out, 0), quant), kk, vv


def _state_layer(w, cfg, x, start, ck, cv, quant):
    """Step states x [S, bs, M] of blocks starting at ``start`` [S]: each
    sees the context keys ck, cv [T, Kh, D] before its block and its own
    keys."""
    S, bs = x.shape[:2]
    pos = start[:, None] + jnp.arange(bs)
    q, k, v = _qkv(w, cfg, x, pos, quant)
    T = ck.shape[0]
    kk = jnp.concatenate([jnp.broadcast_to(ck, (S,) + ck.shape), k], 1)
    vv = jnp.concatenate([jnp.broadcast_to(cv, (S,) + cv.shape), v], 1)
    ctx_keep = jnp.arange(T)[None, :] < start[:, None]
    keep = jnp.concatenate([ctx_keep, jnp.ones((S, bs), bool)], 1)
    keep = jnp.broadcast_to(keep[:, None, :], (S, bs, T + bs))
    return _finish(w, cfg, x, _attend(q, kk, vv, keep, quant), quant)


def _head_stats(top, cfg, x, served, quant):
    """Per position: max logit, log-sum-exp, the logit of the served
    token ``served`` and the argmax — all the comparison reads, without
    the [.., V] logits ever leaving the device."""
    logits = _mm(_rms(x, top["final_norm"], cfg["norm_eps"]), top["head"],
                 quant)
    return (logits.max(-1), jax.nn.logsumexp(logits, -1),
            jnp.take_along_axis(logits, served[..., None], -1)[..., 0],
            jnp.argmax(logits, -1))


class Reference:
    """Logit statistics of recorded denoising-step states, layer by layer.

    ``prefix``: token ids [T0] of a prefix every request shares (the
    shared document), which is one bidirectional segment; may be empty.
    ``requests``: dicts with ``tokens`` [T] (the rest of the prompt, then
    the served response), ``seg`` [T] (segment index of each position,
    from 0), ``states`` [S, bs] (token ids of each step state, masks
    included), ``starts`` [S] (absolute position of each state's block)
    and ``served`` [S, bs] (the served token at each state position).
    Returns, per request, ``(max, lse, served_logit, argmax)`` arrays
    [S, bs] at float32 with the highest matmul precision; ``quant``
    computes every matmul in int8 instead (the control).
    """

    def __init__(self, cfg: dict, seed: int, mask_id: int,
                 quant: bool = False):
        self.cfg, self.seed, self.mask_id = cfg, seed, mask_id
        self._ctx = jax.jit(lambda w, x, seg, pk, pv: _ctx_layer(
            w, cfg, x, seg, pk, pv, quant))
        self._state = jax.jit(lambda w, x, st, ck, cv: _state_layer(
            w, cfg, x, st, ck, cv, quant))
        self._draw = jax.jit(lambda l: _draw_layer(cfg, seed, mask_id, l))
        self._top = jax.jit(lambda: _draw_top(cfg, seed, mask_id))
        self._stats = jax.jit(lambda top, x, s: _head_stats(
            top, cfg, x, s, quant))

    def stats(self, prefix, requests, chunk: int = 16):
        cfg = self.cfg
        Kh, D = cfg["num_kv_heads"], cfg["head_dim"]
        with jax.default_matmul_precision("highest"):
            top = self._top()
            emb = top["embed"]
            px = emb[jnp.asarray(prefix, jnp.int32)]
            pseg = jnp.zeros((len(prefix),), jnp.int32)
            xs = [emb[jnp.asarray(r["tokens"])] for r in requests]
            segs = [jnp.asarray(r["seg"], jnp.int32) for r in requests]
            hs, starts, n = [], [], []
            for r in requests:
                S = len(r["states"])
                pad = -S % chunk
                st = np.concatenate([r["states"], r["states"][:1].repeat(
                    pad, 0)]) if pad else r["states"]
                sp = np.concatenate([r["starts"], r["starts"][:1].repeat(
                    pad)]) if pad else r["starts"]
                hs.append(emb[jnp.asarray(st)])
                starts.append(jnp.asarray(sp, jnp.int32))
                n.append(S)
            empty = jnp.zeros((0, Kh, D), jnp.float32)
            for layer in range(cfg["num_layers"]):
                w = self._draw(layer)
                pk = pv = empty
                if len(prefix):
                    px, pk, pv = self._ctx(w, px, pseg, empty, empty)
                for i in range(len(requests)):
                    xs[i], ck, cv = self._ctx(w, xs[i], segs[i], pk, pv)
                    hs[i] = jnp.concatenate([
                        self._state(w, hs[i][j:j + chunk],
                                    starts[i][j:j + chunk], ck, cv)
                        for j in range(0, hs[i].shape[0], chunk)], 0)
                del w
            out = []
            for r, h, S in zip(requests, hs, n):
                served = np.zeros(h.shape[:2], np.int32)
                served[:S] = r["served"]
                out.append(tuple(np.asarray(a)[:S] for a in self._stats(
                    top, h, jnp.asarray(served))))
            return out
