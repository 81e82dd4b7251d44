"""Plain reference of a dense GQA decoder (Qwen3: qk-norm, tied head).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, with no kernels, cache or batching tricks: every sequence is
run whole, causally, from its first token.  It imports nothing of the
program.  The weights are the benchmark's own (``make_params``, drawn
from the seed) in the layout the program takes; the reference reads
the same bf16 arrays and upcasts one layer at a time inside a scan, so
float32 copies of the whole model never exist at once.

It follows the program's equations where they depart from published
Qwen3, so that the comparison measures precision and not a different
model.  The departures, for a later change to the program:

* the token embedding is multiplied by ``sqrt(hidden_size)`` before the
  first layer (Qwen3 has no such scale);
* norm gains are stored as ``g`` and applied as ``1 + g`` (Qwen3 stores
  the gain itself); the same function, only a different parameter.

``control`` computes the same forward with every matrix multiplication
in float8 (e4m3, one scale per tensor): the lower precision that a
later change might be tempted to serve in.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
E4M3_MAX = 448.0


def _head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def qk_norm(c: dict) -> bool:
    return c.get("model_type") == "qwen3"


def param_shapes(c: dict) -> dict:
    """The program's parameter layout: one scanned group of layers."""
    d, ff = c["hidden_size"], c["intermediate_size"]
    L, hd = c["num_hidden_layers"], _head_dim(c)
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    mixer = {"wq": (L, d, h, hd), "wk": (L, d, kv, hd), "wv": (L, d, kv, hd),
             "wo": (L, h, hd, d)}
    if qk_norm(c):
        mixer["q_norm"] = {"scale": (L, hd)}
        mixer["k_norm"] = {"scale": (L, hd)}
    layer = {"norm_mix": {"scale": (L, d)}, "mixer": mixer,
             "norm_ffn": {"scale": (L, d)},
             "ffn": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                     "w_down": (L, ff, d)}}
    embed = {"table": (c["vocab_size"], d)}
    if not c["tie_word_embeddings"]:
        embed["head"] = (d, c["vocab_size"])
    return {"embed": embed, "groups": ((layer,),),
            "final_norm": {"scale": (d,)}}


def _init_scale(name: str, shape) -> float:
    """1/sqrt(fan-in), the program's own init scale, for every matrix
    but the token embedding, whose rows are drawn a tenth as large.

    With the program's ``sqrt(hidden_size)`` embedding scale and a tied
    head, rows of ``d**-0.5`` make each token's own logit stand about
    ``sqrt(d) / rms(residual)`` noise widths above the rest: a random
    model then repeats its input token with a margin no precision can
    flip, and the check would read nothing.  A tenth of that puts the
    token's own share under one noise width, as in trained models,
    where the layers and not the embedding dominate the residual.
    """
    if "table" in name:
        return 0.1 * shape[-1] ** -0.5
    if "'head'" in name:
        fan = shape[0]
    elif "'wo'" in name:
        fan = shape[1] * shape[2]
    else:
        fan = shape[1]
    return fan ** -0.5


def make_params(c: dict, key):
    """Random weights from ``key``, in bf16 as they are served, in one
    jitted call; norm gains are float32 ``g`` with ``g ~ N(0, 0.1)``."""
    shapes = param_shapes(c)
    leaves, tree = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
        and all(isinstance(v, int) for v in x))

    def build(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            if "scale" in name:
                out.append(0.1 * jax.random.normal(k, shape, F32))
            else:
                w = jax.random.normal(k, shape, F32) * _init_scale(name, shape)
                out.append(w.astype(jnp.bfloat16))
        return jax.tree.unflatten(tree, out)

    return jax.jit(build)(key)


# ---------------------------------------------------------------- forward
def _fp8(x):
    """Fake-quantize to e4m3 with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(spec, a, b, low):
    if low:
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision="highest")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, theta):
    """x (B, T, H, D): rotate the two halves by position."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c, x, p, low):
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    p = jax.tree.map(lambda a: a.astype(F32), p)
    b, t, _ = x.shape
    m = p["mixer"]
    h = _rms(x, p["norm_mix"]["scale"], eps)
    q = _mm("btd,dhk->bthk", h, m["wq"], low)
    k = _mm("btd,dhk->bthk", h, m["wk"], low)
    v = _mm("btd,dhk->bthk", h, m["wv"], low)
    if qk_norm(c):
        q = _rms(q, m["q_norm"]["scale"], eps)
        k = _rms(k, m["k_norm"]["scale"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)          # head i reads kv head i // g
    v = jnp.repeat(v, group, axis=2)
    s = _mm("bqhd,bkhd->bhqk", q, k, low) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", a, v, low)
    x = x + _mm("bthk,hkd->btd", o, m["wo"], low)
    h = _rms(x, p["norm_ffn"]["scale"], eps)
    f = p["ffn"]
    g = jax.nn.silu(_mm("btd,df->btf", h, f["w_gate"], low))
    u = _mm("btd,df->btf", h, f["w_up"], low)
    return x + _mm("btf,fd->btd", g * u, f["w_down"], low)


def _forward(c, params, tokens, *, first, low):
    table = params["embed"]["table"]
    x = table[tokens].astype(F32) * jnp.sqrt(F32(c["hidden_size"]))

    def body(x, layer):
        return _layer(c, x, layer, low), None

    x, _ = jax.lax.scan(body, x, params["groups"][0][0])
    x = _rms(x[:, first:], params["final_norm"]["scale"].astype(F32),
             c["rms_norm_eps"])
    if c["tie_word_embeddings"]:
        return _mm("btd,vd->btv", x, table.astype(F32), low)
    return _mm("btd,dv->btv", x, params["embed"]["head"].astype(F32), low)


def logits(c: dict, params, tokens, first: int, *, low: bool = False):
    """Logits (B, T - first, V) at positions ``first`` … ``T - 1`` of
    the int32 sequences ``tokens`` (B, T)."""
    fn = jax.jit(partial(_forward, c, first=first, low=low))
    with jax.default_matmul_precision("highest"):
        return fn(params, tokens)


# ------------------------------------------------------------- readings
def served_gap(ref_logits, served):
    """Widest gap by which a served token's reference logit lies below
    the reference's best at its position: (B, N, V), (B, N) → scalar."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, served[..., None], -1)[..., 0]
    return jnp.max(best - got)
