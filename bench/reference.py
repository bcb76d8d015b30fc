"""Plain float32 reference of the dense decoder configurations.

Written from the published description (pre-norm decoder: RMSNorm,
grouped-query attention with rotary positions on the leading
``rope_fraction`` of each head, optional q/k/v bias, SwiGLU MLP, final
RMSNorm, untied LM head), in ``jax.numpy`` with every matrix product at
``Precision.HIGHEST``: no kernel, no cache, no batching.  It imports
nothing of the program.  It reads the weight tree the benchmark made
(``weights.py``) by its leaf names.

Departure from the published GLM-4: its rotary pairs are interleaved
(dims 2i, 2i+1); here, as in the program, the rotated dims are split
into halves (i, i + rot/2).  With random weights this is the same model
under a fixed permutation of the q and k columns.

The forward runs layer by layer, one sequence at a time, with queries
in blocks, so that it fits beside the weights on one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["reference_rows", "QUERY_BLOCK", "PAD_TO"]

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512
PAD_TO = 512


def _mm(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=HI)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta, fraction):
    """x (T, H, D); rotate the leading ``fraction`` of D."""
    d = x.shape[-1]
    rot = int(d * fraction)
    rot -= rot % 2
    inv = 1.0 / (theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _take(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


@functools.partial(jax.jit, static_argnames=("m",))
def _layer(layers, i, x, *, m):
    """One decoder layer over the whole (padded) sequence x (T, d)."""
    p = _take(layers, i)
    t = x.shape[0]
    hq, hkv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    pos = jnp.arange(t)
    h = _rmsnorm(x, p["ln1"]["scale"], m["norm_eps"])
    a = p["attn"]

    def proj(name, heads):
        y = _mm(h, a[name]["w"])
        if "b" in a[name]:
            y = y + a[name]["b"].astype(jnp.float32)
        return y.reshape(t, heads, hd)

    q = _rope(proj("wq", hq), pos, m["rope_theta"], m["rope_fraction"])
    k = _rope(proj("wk", hkv), pos, m["rope_theta"], m["rope_fraction"])
    v = proj("wv", hkv)
    g = hq // hkv
    qg = q.reshape(t, hkv, g, hd)
    nb = t // QUERY_BLOCK

    def block(j):
        qb = jax.lax.dynamic_slice_in_dim(qg, j * QUERY_BLOCK, QUERY_BLOCK)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HI) * hd ** -0.5
        qpos = j * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", w, v, precision=HI)
        return o.reshape(QUERY_BLOCK, hq * hd)

    o = jax.lax.map(block, jnp.arange(nb)).reshape(t, hq * hd)
    x = x + _mm(o, a["wo"]["w"])
    h2 = _rmsnorm(x, p["ln2"]["scale"], m["norm_eps"])
    mp = p["mlp"]
    u = jax.nn.silu(_mm(h2, mp["gate"]["w"])) * _mm(h2, mp["up"]["w"])
    return x + _mm(u, mp["down"]["w"])


@functools.partial(jax.jit, static_argnames=("m",))
def _head(params, x, rows, *, m):
    h = _rmsnorm(x[rows], params["final_norm"]["scale"], m["norm_eps"])
    return _mm(h, params["head"]["w"])


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def reference_rows(params, model: dict, tokens: np.ndarray,
                   rows: np.ndarray) -> np.ndarray:
    """Float32 logits ``(len(rows), vocab)`` of the reference forward
    over ``tokens`` (one sequence), at positions ``rows``.

    The sequence is padded at its end to a multiple of ``PAD_TO``
    (causal: the padding changes no earlier position), and ``rows`` to
    a multiple of 64, so that a handful of programs cover every length.
    """
    mk = _Frozen({k: model[k] for k in ("n_heads", "n_kv_heads", "head_dim",
                                        "norm_eps", "rope_theta",
                                        "rope_fraction")})
    n = len(tokens)
    tp = -(-n // PAD_TO) * PAD_TO
    toks = np.zeros(tp, np.int32)
    toks[:n] = tokens
    nr = len(rows)
    rp = -(-nr // 64) * 64
    r = np.full(rp, rows[-1], np.int32)
    r[:nr] = rows
    layers = params["dense"]
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"]["table"], jnp.asarray(toks))
        for i in range(model["n_layers"]):
            x = _layer(layers, jnp.int32(i), x, m=mk)
        out = _head(params, x, jnp.asarray(r), m=mk)
    return np.asarray(out[:nr], np.float32)


class _Frozen(dict):
    """A hashable dict of model sizes, for a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
