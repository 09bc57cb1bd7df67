"""Plain float32 reference of the dense decoder, written from its equations.

    x   = E[t]
    per layer l:
      z = rms(x) * g1                 rms(x) = x / sqrt(mean(x^2) + eps)
      q = z Wq, k = z Wk, v = z Wv    heads of size hd = head_dim
                                      (d / n_heads where none is given)
      q, k = rope(q, p), rope(k, p)   half rotation, theta = rope_theta
      a = softmax(q k^T / sqrt(hd) + causal mask) v
                                      query head h reads kv head h // (n_heads / n_kv)
      x = x + a Wo
      z = rms(x) * g2
      x = x + (silu(z Wg) * (z Wi)) Wo2
    logits = (rms(x) * gf) U          untied head

No cache, no batching of requests: the sequences of a check are packed into
one row, and a query attends only to earlier positions of its own sequence.
It runs under ``default_matmul_precision("highest")``, one layer's weights
upcast to float32 at a time, so it fits beside the served bf16 weights.

``precision="fp8"`` is the control: every weight matrix and every matmul
input rounded to float8_e4m3 with a scale per output channel (weights) or per
row (activations), the step below the bf16 the configuration states.
Attention scores and probabilities stay in float32.

Imports nothing of the program under test.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x: jax.Array, axis) -> jax.Array:
    """Round to float8_e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x: jax.Array, w: jax.Array, spec: str, fp8: bool) -> jax.Array:
    """x [T, d] times w whose first axis is contracted."""
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum(spec, x, w)


def _rms(x: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x [T, h, hd]: rotate (x1, x2) = halves of the head by pos * f_i."""
    hd = x.shape[-1]
    f = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * f
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attend(q, k, v, pos, seg, chunk: int, span: int):
    """Causal attention inside each packed sequence, over query chunks.
    No sequence is longer than ``span``, so a chunk's queries read only the
    ``span + chunk`` keys that end with the chunk."""
    T, H, hd = q.shape
    G = H // k.shape[1]
    k = jnp.repeat(k, G, axis=1)          # kv head of query head h: h // G
    v = jnp.repeat(v, G, axis=1)
    scale = hd ** -0.5
    K = min(T, span + chunk)

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk)
        pi = jax.lax.dynamic_slice_in_dim(pos, i * chunk, chunk)
        si = jax.lax.dynamic_slice_in_dim(seg, i * chunk, chunk)
        k0 = jnp.clip((i + 1) * chunk - K, 0, T - K)
        kk = jax.lax.dynamic_slice_in_dim(k, k0, K)
        vk = jax.lax.dynamic_slice_in_dim(v, k0, K)
        pk = jax.lax.dynamic_slice_in_dim(pos, k0, K)
        sk = jax.lax.dynamic_slice_in_dim(seg, k0, K)
        s = jnp.einsum("qhd,khd->hqk", qi, kk) * scale
        ok = (sk[None, :] == si[:, None]) & (pk[None, :] <= pi[:, None])
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, vk)

    out = jax.lax.map(one, jnp.arange(T // chunk))
    return out.reshape(T, H, hd)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "fp8",
                                             "chunk", "span"))
def layer(x, layers, l, pos, seg, *, eps: float, theta: float, fp8: bool,
          chunk: int, span: int):
    """One decoder layer over the packed row ``x`` [T, d], reading layer
    ``l`` of the stacked weights and upcasting it to float32 here."""
    w = jax.tree.map(lambda a: a[l].astype(jnp.float32), layers)
    with jax.default_matmul_precision("highest"):
        z = _rms(x, w["ln1"], eps)
        q = _rope(_mm(z, w["attn"]["wq"], "td,dhk->thk", fp8), pos, theta)
        k = _rope(_mm(z, w["attn"]["wk"], "td,dhk->thk", fp8), pos, theta)
        v = _mm(z, w["attn"]["wv"], "td,dhk->thk", fp8)
        a = _attend(q, k, v, pos, seg, chunk, span)
        T, H, hd = a.shape
        x = x + _mm(a.reshape(T, H * hd), w["attn"]["wo"].reshape(H * hd, -1),
                    "te,ed->td", fp8)
        z = _rms(x, w["ln2"], eps)
        h = (jax.nn.silu(_mm(z, w["mlp"]["wg"], "td,df->tf", fp8))
             * _mm(z, w["mlp"]["wi"], "td,df->tf", fp8))
        return x + _mm(h, w["mlp"]["wo"], "tf,fd->td", fp8)


@jax.jit
def embed(emb, tokens):
    return emb[tokens].astype(jnp.float32)


def _vocab_chunk(V: int, most: int = 16384) -> int:
    return max(c for c in range(1, min(V, most) + 1) if V % c == 0)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def head(x, ln_f, unemb, targets, *, eps: float, fp8: bool
         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per row of ``x`` [R, d]: the largest logit, the logit of
    ``targets`` [R] and the argmax, over vocabulary chunks (no [R, V]
    array)."""
    d, V = unemb.shape
    c = _vocab_chunk(V)
    with jax.default_matmul_precision("highest"):
        z = _rms(x, ln_f.astype(jnp.float32), eps)

        def step(carry, inp):
            best, arg, at = carry
            j = inp
            u = jax.lax.dynamic_slice_in_dim(unemb, j * c, c, axis=1)
            lg = _mm(z, u.astype(jnp.float32), "td,dv->tv", fp8)
            m = jnp.max(lg, -1)
            am = jnp.argmax(lg, -1).astype(jnp.int32) + j * c
            local = targets - j * c
            inside = (local >= 0) & (local < c)
            got = jnp.take_along_axis(lg, jnp.clip(local, 0, c - 1)[:, None],
                                      -1)[:, 0]
            at = jnp.where(inside, got, at)
            arg = jnp.where(m > best, am, arg)
            return (jnp.maximum(best, m), arg, at), None

        R = x.shape[0]
        init = (jnp.full((R,), -jnp.inf), jnp.zeros((R,), jnp.int32),
                jnp.full((R,), jnp.nan))
        (best, arg, at), _ = jax.lax.scan(
            step, init, jnp.arange(V // c, dtype=jnp.int32))
    return best, at, arg


def hidden(params, conf: dict, tokens, pos, seg, fp8: bool = False):
    """Final hidden states [T, d] of the packed row, layer by layer."""
    eps, theta = norm_eps(conf), float(conf["rope_theta"])
    T, H = len(tokens), int(conf["num_attention_heads"])
    # the longest sequence of the row, to a power of two: few programs
    span = 512
    while span < int(np.max(np.asarray(pos))) + 1:
        span *= 2
    chunk = 512                  # keep a chunk's scores under 512 MiB
    while chunk > 8 and (H * chunk * min(T, span + chunk) * 4 > 1 << 29
                         or T % chunk):
        chunk //= 2
    x = embed(params["emb"], tokens)
    for l in range(int(conf["num_hidden_layers"])):
        x = layer(x, params["layers"], l, pos, seg, eps=eps, theta=theta,
                  fp8=fp8, chunk=chunk, span=span)
    return x


def logits_at(params, conf: dict, x, targets, fp8: bool = False):
    return head(x, params["ln_f"], params["unemb"], targets,
                eps=norm_eps(conf), fp8=fp8)


def norm_eps(conf: dict) -> float:
    return float(conf.get("rms_norm_eps", conf.get("layer_norm_eps")))
