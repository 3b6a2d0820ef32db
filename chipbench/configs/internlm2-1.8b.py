"""Plain float32 reference of InternLM2 (internlm/internlm2-1_8b).

The published block, written out in `jax.numpy` with every matrix product
at `Precision.HIGHEST`; nothing of the system under test is imported.

    x = embed[token]
    per layer:  h = RMSNorm(x) * g1
                q, k, v = h Wq, h Wk, h Wv        (16 query, 8 KV heads of 128)
                q, k = RoPE(q), RoPE(k)           (rotate-half, theta 1e6)
                x = x + softmax(q k^T / sqrt(128), causal) v Wo
                h = RMSNorm(x) * g2
                x = x + (silu(h W1) * h W3) W2
    logits = RMSNorm(x) * g Wout                   (untied head)

What the configuration states beyond the published block, and is
therefore part of the reference:

* weights are the P(16,2) codes the seed drew, decoded exactly
  (`posit_ref.decode`); norm gains are stored as `1 + g` offsets, as the
  weight tree keeps them;
* keys and values are stored as P(8,2) (`posit_ref.round_to_format`).  A
  query reads the stored (rounded) keys and values of every earlier
  prefill chunk and of every decoded position, itself included, and the
  unrounded ones of its own prefill chunk.  `groups` carries that chunking:
  positions of one prefill chunk share a group id >= 0, decoded positions
  have -1.

Departures: none in the mathematics.  Dynamic NTK RoPE scaling acts only
beyond 32768 positions, which no cell reaches, so it is left out.

`act` names the dtype that every activation entering a matrix product is
rounded to (None: float32).  The control of the correctness check runs
this reference with `act="float8_e4m3fn"`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import posit_ref

Q_BLOCK = 512          # query rows per attention block
PAD = 3 * Q_BLOCK      # sequences are padded to a multiple of this, so a
                       # handful of lengths is ever compiled
WEIGHT_FMT = (16, 2)   # P(16,2) weight codes
KV_FMT = (8, 2)        # P(8,2) KV pages
FP8_MAX = 448.0        # largest finite float8_e4m3fn


def _sizes(conf):
    p = conf["published"]
    d = p["hidden_size"]
    h = p["num_attention_heads"]
    return dict(L=p["num_hidden_layers"], D=d, Hq=h,
                Hkv=p["num_key_value_heads"], Dh=d // h,
                F=p["intermediate_size"], V=p["vocab_size"],
                eps=float(p["rms_norm_eps"]), theta=float(p["rope_theta"]))


def _rms(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain)


def _rope(x, pos, theta):
    """x [S, H, Dh], pos [S]: rotate-half RoPE."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                    / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(x, act):
    if act is None:
        return x
    x = jnp.clip(x, -FP8_MAX, FP8_MAX) if "float8" in act else x
    return x.astype(jnp.dtype(act)).astype(jnp.float32)


def _w(codes):
    return posit_ref.decode(codes, *WEIGHT_FMT)


@functools.partial(jax.jit, static_argnames=("sz", "act"))
def _layer(x, layers, l, pos, groups, *, sz, act):
    S = x.shape[0]
    Hq, Hkv, Dh = sz["Hq"], sz["Hkv"], sz["Dh"]
    G = Hq // Hkv
    take = lambda name: layers[name][l]
    h = _act(_rms(x, take("ln1"), sz["eps"]), act)
    q = (h @ _w(take("wq"))).reshape(S, Hq, Dh)
    k = (h @ _w(take("wk"))).reshape(S, Hkv, Dh)
    v = (h @ _w(take("wv"))).reshape(S, Hkv, Dh)
    q = _rope(q, pos, sz["theta"]) / np.sqrt(Dh)
    k = _rope(k, pos, sz["theta"])
    kq = posit_ref.round_to_format(k, *KV_FMT)
    vq = posit_ref.round_to_format(v, *KV_FMT)
    qg = q.reshape(S, Hkv, G, Dh)

    def block(i):
        # queries i*Q_BLOCK .. (i+1)*Q_BLOCK attend keys up to their own
        lo, hi = i * Q_BLOCK, (i + 1) * Q_BLOCK
        qb, pb, gb = qg[lo:hi], pos[lo:hi], groups[lo:hi]
        same = (gb[:, None] == groups[None, :hi]) & (gb[:, None] >= 0)
        causal = pos[None, :hi] <= pb[:, None]
        s_raw = jnp.einsum("qhgd,khd->hgqk", qb, k[:hi])
        s_st = jnp.einsum("qhgd,khd->hgqk", qb, kq[:hi])
        s = jnp.where(same, s_raw, s_st)
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = (jnp.einsum("hgqk,khd->qhgd", jnp.where(same, p, 0.0), v[:hi])
             + jnp.einsum("hgqk,khd->qhgd", jnp.where(same, 0.0, p),
                          vq[:hi]))
        return o.reshape(Q_BLOCK, Hq * Dh)

    attn = jnp.concatenate([block(i) for i in range(S // Q_BLOCK)], 0)
    x = x + _act(attn, act) @ _w(take("wo"))
    h = _act(_rms(x, take("ln2"), sz["eps"]), act)
    m = jax.nn.silu(h @ _w(take("wi_gate"))) * (h @ _w(take("wi_up")))
    return x + _act(m, act) @ _w(take("wo_mlp"))


@functools.partial(jax.jit, static_argnames=("sz", "act"))
def _head(x, final_norm, head, *, sz, act):
    h = _act(_rms(x, final_norm, sz["eps"]), act)
    return h @ _w(head)


def logits(weights, conf, tokens, groups, out_pos, act=None):
    """Reference logits [len(out_pos), V] (float32) of one sequence.

    tokens/groups: [S]; out_pos: the positions whose next-token logits
    are wanted.  The sequence is padded to a multiple of PAD; no real
    position attends the padding."""
    sz = _sizes(conf)
    frozen = tuple(sorted(sz.items()))
    pad = -len(tokens) % PAD
    tokens = np.pad(np.asarray(tokens), (0, pad))
    groups = np.pad(np.asarray(groups), (0, pad), constant_values=-1)
    with jax.default_matmul_precision("highest"):
        tok = jnp.asarray(tokens, jnp.int32)
        x = jnp.take(weights["embed"], tok, axis=0).astype(jnp.float32)
        pos = jnp.arange(tok.shape[0], dtype=jnp.int32)
        grp = jnp.asarray(groups, jnp.int32)
        for l in range(sz["L"]):
            x = _layer(x, weights["layers"], jnp.int32(l), pos, grp,
                       sz=_Frozen(frozen), act=act)
        rows = x[jnp.asarray(out_pos, jnp.int32)]
        out = _head(rows, weights["final_norm"], weights["head"],
                    sz=_Frozen(frozen), act=act)
    return np.asarray(out, np.float32)


class _Frozen(dict):
    """A hashable dict of sizes, so it can be a static jit argument."""

    def __init__(self, items):
        super().__init__(items)
        self._key = tuple(items)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key
