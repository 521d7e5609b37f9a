"""Plain reference of a dense llama-style decoder, in float32.

RMSNorm, rotary position embedding on the two halves of each head,
grouped-query causal attention and a SwiGLU MLP, with untied input
embedding and output head: the architecture of DeepSeek-LLM-7B and
SmolLM-135M.  Nothing here imports the program.  The weights come from
:mod:`benchmarks.chip.weights` with the same seed the program was given,
so the reference knows the quantized model exactly; each quality tier is
the same draw with its low code planes cleared.

``leaf_specs`` also fixes the parameter layout the program is handed
(paths, shapes, dtypes) and the axis along which the program's quantizer
groups each leaf: the contraction axis of a kernel matmul weight; the
vocabulary axis of the token embedding; and the head-dimension axis of
the four-dimensional attention output projection, which the quantizer
views as a convolution kernel (input channels third), in runs of the
largest power of two up to 16 that divides it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.weights import LeafSpec, draw_leaf

HIGHEST = jax.lax.Precision.HIGHEST
HEAD = "embed/head"  # the output head's path
FP8_MAX = 448.0  # largest float8_e4m3fn value


def dims(model: dict) -> dict:
    d, h = model["hidden_size"], model["num_attention_heads"]
    return {"d": d, "h": h, "kv": model["num_key_value_heads"],
            "hd": d // h, "ff": model["intermediate_size"],
            "vocab": model["vocab_size"], "layers": model["num_hidden_layers"],
            "eps": model["rms_norm_eps"], "theta": model["rope_theta"]}


def _pow2_divisor(n: int, cap: int = 16) -> int:
    g = cap
    while n % g:
        g //= 2
    return g


def leaf_specs(model: dict, group: int = 16) -> dict[str, LeafSpec]:
    m = dims(model)
    d, h, kv, hd, ff, v, n = (m["d"], m["h"], m["kv"], m["hd"], m["ff"],
                              m["vocab"], m["layers"])
    bf = "bfloat16"

    def grid(shape, fan_in, axis, g=group):
        return LeafSpec(tuple(shape), bf, "grid", 1.0 / math.sqrt(fan_in),
                        axis, g)

    return {
        "embed/tok": LeafSpec((v, d), bf, "grid", 1.0, 0, group),
        "embed/head": grid((d, v), d, 0),
        "final_norm": LeafSpec((d,), "float32", "ones"),
        "blocks/ln1": LeafSpec((n, d), "float32", "ones"),
        "blocks/ln2": LeafSpec((n, d), "float32", "ones"),
        "blocks/attn/wq": grid((n, d, h, hd), d, 1),
        "blocks/attn/wk": grid((n, d, kv, hd), d, 1),
        "blocks/attn/wv": grid((n, d, kv, hd), d, 1),
        "blocks/attn/wo": grid((n, h, hd, d), h * hd, 2, _pow2_divisor(hd)),
        "blocks/mlp/wg": grid((n, d, ff), d, 1),
        "blocks/mlp/wu": grid((n, d, ff), d, 1),
        "blocks/mlp/wd": grid((n, ff, d), ff, 1),
    }


def matmul_shapes(model: dict) -> dict[str, tuple[int, int, int]]:
    """Path -> (layers, K, N) of every matmul weight, contraction K."""
    m = dims(model)
    d, h, kv, hd, ff, v, n = (m["d"], m["h"], m["kv"], m["hd"], m["ff"],
                              m["vocab"], m["layers"])
    return {"blocks/attn/wq": (n, d, h * hd), "blocks/attn/wk": (n, d, kv * hd),
            "blocks/attn/wv": (n, d, kv * hd), "blocks/attn/wo": (n, h * hd, d),
            "blocks/mlp/wg": (n, d, ff), "blocks/mlp/wu": (n, d, ff),
            "blocks/mlp/wd": (n, ff, d), "embed/head": (1, d, v)}


def attention_flops_per_token(model: dict, context: float) -> float:
    """q.k and p.v of one new token against ``context`` cached ones."""
    m = dims(model)
    return 4.0 * m["layers"] * m["h"] * m["hd"] * context


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _q8(x):
    """Round to float8 e4m3 with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, low):
    if low:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(t, theta):  # (B, T, H, hd), positions 0..T-1
    hd = t.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float32) * 2.0 / hd)
    ang = jnp.arange(t.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "low"))
def _layer(x, w, *, eps, theta, low):
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()}
    t = x.shape[1]
    hin = _rms(x, f32["ln1"], eps)
    q = _rope(_mm("btd,dhk->bthk", hin, f32["wq"], low), theta)
    k = _rope(_mm("btd,dhk->bthk", hin, f32["wk"], low), theta)
    v = _mm("btd,dhk->bthk", hin, f32["wv"], low)
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    score = _mm("bqhd,bkhd->bhqk", q, k, low) / np.sqrt(q.shape[-1])
    causal = jnp.tril(jnp.ones((t, t), bool))
    prob = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
    o = _mm("bhqk,bkhd->bqhd", prob, v, low)
    x = x + _mm("bqhd,hde->bqe", o, f32["wo"], low)
    hin = _rms(x, f32["ln2"], eps)
    gate = jax.nn.silu(_mm("btd,df->btf", hin, f32["wg"], low))
    up = _mm("btd,df->btf", hin, f32["wu"], low)
    return x + _mm("btf,fd->btd", gate * up, f32["wd"], low)


def draw(seed: int, model: dict, drops: dict[str, int]) -> dict:
    """Flat {path: leaf} of the served model at one tier, in bf16/f32."""
    return {p: draw_leaf(seed, p, s, drops.get(p, 0))
            for p, s in leaf_specs(model).items()}


def final_hidden(w: dict, model: dict, tokens: np.ndarray, low: bool) -> jax.Array:
    """(B, T, d) final-normed hidden states of right-padded ``tokens``,
    layer by layer; ``low`` computes every matmul in float8 (the control)."""
    m = dims(model)
    x = jnp.take(w["embed/tok"], jnp.asarray(tokens), axis=0).astype(jnp.float32)
    names = {"ln1": "blocks/ln1", "ln2": "blocks/ln2",
             "wq": "blocks/attn/wq", "wk": "blocks/attn/wk",
             "wv": "blocks/attn/wv", "wo": "blocks/attn/wo",
             "wg": "blocks/mlp/wg", "wu": "blocks/mlp/wu",
             "wd": "blocks/mlp/wd"}
    for i in range(m["layers"]):
        x = _layer(x, {k: w[p][i] for k, p in names.items()},
                   eps=float(m["eps"]), theta=float(m["theta"]), low=low)
    return _rms(x, w["final_norm"].astype(jnp.float32), float(m["eps"]))


CHUNK = 64  # positions per block of the output head


def _by_chunk(fn, *arrays):
    """Apply ``fn`` to CHUNK positions at a time of (B, T, ...) arrays
    (T a multiple of CHUNK), so no (B, T, vocab) logits are ever held."""
    b, t = arrays[0].shape[:2]
    split = [a.reshape((b, t // CHUNK, CHUNK) + a.shape[2:]).swapaxes(0, 1)
             for a in arrays]
    out = jax.lax.map(lambda xs: fn(*xs), split)
    return out.swapaxes(0, 1).reshape(b, t)


@jax.jit
def served_gaps(h, head, targets):
    """(B, T) gap by which each target token's reference logit lies below
    the reference's best logit at that position."""
    head32 = head.astype(jnp.float32)

    def gap(hc, tc):
        logits = jnp.einsum("btd,dv->btv", hc, head32, precision=HIGHEST)
        picked = jnp.take_along_axis(logits, tc[..., None], -1)[..., 0]
        return jnp.max(logits, -1) - picked

    return _by_chunk(gap, h, targets)


@jax.jit
def control_gaps(h_ref, h_low, head):
    """(B, T) gap under the reference of the token the float8 control puts
    first at each position."""
    head32 = head.astype(jnp.float32)
    head8 = _q8(head32)
    scale = jnp.maximum(jnp.max(jnp.abs(h_low)), 1e-30) / FP8_MAX

    def gap(hr, hl):
        ref = jnp.einsum("btd,dv->btv", hr, head32, precision=HIGHEST)
        hl = (hl / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        low = jnp.einsum("btd,dv->btv", hl, head8, precision=HIGHEST)
        top = jnp.argmax(low, -1)
        picked = jnp.take_along_axis(ref, top[..., None], -1)[..., 0]
        return jnp.max(ref, -1) - picked

    return _by_chunk(gap, h_ref, h_low)
