"""Plain float32 reference of a dense decoder (Qwen1.5 / InternLM2 family).

Pre-norm blocks: RMSNorm, grouped-query attention with rotary embeddings
(rotate-half form) and optional q/k/v biases, RMSNorm, SwiGLU MLP; a final
RMSNorm and an untied output head. Written from the published layer
equations; it imports nothing of the serving program and reads only the
weight tree the benchmark made.

Runs layer by layer with the sequence padded to a multiple of ``BLOCK``
(causal masking keeps padding out of every real position), and attention
in query blocks, so that a 5k-token sequence of a 6144-wide model fits
beside the weights. ``fp8=True`` is the control: every matrix product
takes operands rounded to float8_e4m3 (per-row and per-column absmax
scaling), the precision a cheaper serving path would be tempted to use.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 512  # sequence bucket and query block
F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8_e4m3 with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / F8_MAX)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, fp8, a_axis, b_axis):
    if fp8:
        a, b = _q8(a, a_axis), _q8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs  # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "fp8"))
def _layer(x, blk, eps, theta, fp8):
    """One block over the whole (padded) sequence x [S, d], float32."""
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    s = x.shape[0]
    at = blk["attn"]
    h = _rms(x, f32(blk["norm1"]), eps)
    q = _mm("sd,dhk->shk", h, f32(at["wq"]), fp8, -1, 0)
    k = _mm("sd,dhk->shk", h, f32(at["wk"]), fp8, -1, 0)
    v = _mm("sd,dhk->shk", h, f32(at["wv"]), fp8, -1, 0)
    if "bq" in at:
        q, k, v = q + f32(at["bq"]), k + f32(at["bk"]), v + f32(at["bv"])
    pos = jnp.arange(s)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    nh, kvh, hd = q.shape[1], k.shape[1], q.shape[2]
    group = nh // kvh
    cache = (k, v)  # what a KV cache holds: keys after the rotation
    # query head i reads key/value head i // group
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)

    def attend(qb_start):
        qb = jax.lax.dynamic_slice_in_dim(q, qb_start, BLOCK, axis=0)  # [Bq, H, hd]
        sc = _mm("qhk,shk->hqs", qb, k, fp8, -1, -1) / np.sqrt(hd)
        qpos = qb_start + jnp.arange(BLOCK)
        sc = jnp.where(qpos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return _mm("hqs,shk->qhk", w, v, fp8, -1, 0)

    out = jax.lax.map(attend, jnp.arange(0, s, BLOCK))  # [nb, Bq, H, hd]
    out = out.reshape(s, nh, hd)
    x = x + _mm("shk,hkd->sd", out, f32(at["wo"]), fp8, (-2, -1), (0, 1))
    h = _rms(x, f32(blk["norm2"]), eps)
    ff = blk["ffn"]
    g = _mm("sd,df->sf", h, f32(ff["w_gate"]), fp8, -1, 0)
    u = _mm("sd,df->sf", h, f32(ff["w_up"]), fp8, -1, 0)
    return x + _mm("sf,fd->sd", jax.nn.silu(g) * u, f32(ff["w_down"]), fp8, -1, 0), cache


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, norm, head, eps, fp8):
    h = _rms(x, norm, eps)
    return _mm("sd,dv->sv", h, head.astype(jnp.float32), fp8, -1, 0)


def logits(params, arch, tokens: np.ndarray, first: int, fp8: bool = False,
           kv: bool = False):
    """Float32 logits [len(tokens) - first, V] predicting tokens[first + 1:]
    (row i is the distribution after tokens[: first + i + 1]). With ``kv``
    also each layer's keys (after the rotation) and values of every token,
    as a list of ``(k, v)`` pairs of shape [len(tokens), KV, hd]."""
    n = len(tokens)
    s = -(-n // BLOCK) * BLOCK
    padded = np.zeros(s, np.int32)
    padded[:n] = tokens
    caches = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][jnp.asarray(padded)].astype(jnp.float32)
        for li in range(arch.n_layers):
            blk = jax.tree.map(lambda a, i=li: a[i], params["blocks"])
            x, cache = _layer(x, blk, eps=arch.norm_eps, theta=arch.rope_theta, fp8=fp8)
            if kv:
                caches.append(tuple(np.asarray(c[:n]) for c in cache))
        head = params["embed"].T if arch.tie_embeddings else params["lm_head"]
        out = _head(x[first:n], params["final_norm"], head, eps=arch.norm_eps, fp8=fp8)
    out = np.asarray(out, np.float32)
    return (out, caches) if kv else out
