#!/usr/bin/env python3
"""Smoke run of the packed continuous serving path on one TPU chip.

    python chip_smoke.py

Builds SmolLM-135M at its published widths from a seeded random init,
compresses it (``repro.api.compress``) and serves a short stream through
the per-request packed continuous engine (``EdgeArtifact.engine`` ->
``submit``/``step``/``poll``): prompts at the hi, mid and lo tiers arrive
two steps apart, and one request decodes speculatively (draft at lo,
verify at hi).  It then checks, on the chip:

* every request ends DONE with all its tokens;
* one prompt's admission-prefill logits (packed Pallas kernels) agree
  with a plain ``jax.numpy`` dequantize-then-matmul forward of the same
  packed weights at the highest matmul precision, within ``LOGIT_RTOL``;
* no fallback ran: the dispatcher routed only to the Pallas kernels, the
  kernels were not interpreted, and the compiled admission and decode
  programs hold the kernel (``tpu_custom_call``).

Figures printed on the way are smoke figures from one run, not benchmark
metrics.  The last line of standard output is one JSON object naming the
device.  Without a TPU the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import dataclasses
import importlib.metadata
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent

# A random-init model is chaotic with depth: its attention weights' fan-in
# init makes the softmax saturate, so any rounding difference grows ~10x
# every two layers (float32 on the CPU: 1e-6 after 2 layers, 5e-2 after
# 30; bf16 after 30 layers is uncorrelated with float32).  The logits are
# therefore compared on the first REF_LAYERS layers of the served packed
# weights, in float32 at the highest matmul precision on both sides.
REF_LAYERS = 2
LOGIT_RTOL = 1e-3  # max |packed - reference| / max |reference|
# Served dtype, one matmul: the kernel rounds each decoded weight to bf16
# (relative 2**-9), so outputs stay well inside 1% of their range.
KERNEL_RTOL = 1e-2

SEED = 0  # weights, prompts and kernel-check inputs
TIERS = ("hi", "mid", "lo")
# rows of one packed matmul when serving 4 slots: decode, speculative
# verify (4 x (k + 1)), admission prefill (ServeConfig.max_prompt)
SERVE_ROWS = (4, 20, 64)
SPEC = ("lo", 4)  # speculative request: draft tier and window k


class SmokeFailure(RuntimeError):
    """A phase of the smoke run did not do what it must."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# --------------------------------------------------------------------------
# Reference: plain jax.numpy over the same packed weights
# --------------------------------------------------------------------------
_SM_MAGNITUDE = np.asarray([0.0, 1.0, 2.0, 4.0], np.float32)


def _dequantize(pw, drop: int = 0) -> jax.Array:
    """Plane-major sign-magnitude bit-planes -> float32 weight, with the
    ``drop`` least significant code bits cleared (a lower tier).

    planes (*stack, 3, K//32, *rest): plane p carries code bit 2 - p, and
    bit j of word w is the code of row 32 w + j.  Code bit 2 is the sign,
    bits 1..0 index the magnitude {0, 1, 2, 4}.  scales (*stack, K//G,
    *rest) scale each group of G rows."""
    _check(pw.plane_major and pw.sign_mag,
           "serving tree holds a packed leaf that is not plane-major "
           "sign-magnitude")
    planes = jnp.asarray(pw.planes).astype(jnp.uint32)
    st = planes.ndim - 2 - pw.rest_ndim
    words = jnp.expand_dims(planes, st + 2)  # (*stack, 3, W, 1, *rest)
    shifts = jnp.arange(32, dtype=jnp.uint32).reshape(
        (32,) + (1,) * pw.rest_ndim)
    bits = (words >> shifts) & jnp.uint32(1)  # (*stack, 3, W, 32, *rest)
    plane = [jnp.take(bits, p, axis=st) for p in range(3)]
    code = (plane[0] << 2) | (plane[1] << 1) | plane[2]
    code = code & jnp.uint32((0b111 << drop) & 0b111)
    lead = code.shape[:st]
    code = code.reshape(lead + (-1,) + code.shape[st + 2:])  # (*stack, K, *rest)
    level = (jnp.asarray(_SM_MAGNITUDE)[code & 3]
             * jnp.where((code >> 2) == 1, -1.0, 1.0))
    scales = jnp.repeat(jnp.asarray(pw.scales, jnp.float32), pw.group_size,
                        axis=st)
    return level * scales


def _reference_logits(params, cfg, prompt: np.ndarray) -> jax.Array:
    """Last-position logits of a dense llama-style decoder (RMSNorm, RoPE,
    grouped-query causal attention, SwiGLU), float32 throughout."""
    from repro.quant.store import PackedWeight

    w = jax.tree_util.tree_map(
        lambda a: (_dequantize(a) if isinstance(a, PackedWeight)
                   else jnp.asarray(a, jnp.float32)),
        params, is_leaf=lambda a: isinstance(a, PackedWeight))
    s, hd, half = len(prompt), cfg.hd, cfg.hd // 2
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(t):  # (S, H, hd): rotate the two halves of each head
        a, b = t[..., :half], t[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    def rms(t, scale):
        return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-6) * scale

    causal = jnp.tril(jnp.ones((s, s), bool))
    group = cfg.n_heads // cfg.n_kv
    x = w["embed"]["tok"][jnp.asarray(prompt)]
    for layer in range(cfg.n_layers):
        b = jax.tree_util.tree_map(lambda a, i=layer: a[i], w["blocks"])
        at, ml = b["attn"], b["mlp"]
        h = rms(x, b["ln1"])
        q = rope(jnp.einsum("sd,dhk->shk", h, at["wq"]))
        k = rope(jnp.einsum("sd,dhk->shk", h, at["wk"]))
        v = jnp.einsum("sd,dhk->shk", h, at["wv"])
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        score = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        prob = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", prob, v)
        x = x + jnp.einsum("qhd,hde->qe", o, at["wo"])
        h = rms(x, b["ln2"])
        x = x + (jax.nn.silu(h @ ml["wg"]) * (h @ ml["wu"])) @ ml["wd"]
    return rms(x[-1], w["final_norm"]) @ w["embed"]["head"]


# --------------------------------------------------------------------------
# The run
# --------------------------------------------------------------------------
def _requests(cfg, n: int, max_new: int, seed: int) -> list[dict]:
    """n seeded prompts (8-48 tokens) cycling the tiers, plus one
    speculative hi request first in line."""
    rng = np.random.default_rng(seed)

    def prompt():
        return rng.integers(0, cfg.vocab, int(rng.integers(8, 49))).tolist()

    from repro import api

    reqs = [{"prompt": prompt(), "quality": "hi",
             "speculate": api.SpecConfig(*SPEC)}]
    reqs += [{"prompt": prompt(), "quality": TIERS[i % len(TIERS)],
              "speculate": None} for i in range(n)]
    for r in reqs:
        r["max_new"] = max_new
    return reqs


def _stream(eng, reqs: list[dict]) -> dict:
    """Submit each request two steps after the last, drain, and return
    {request index: status}."""
    rids = []
    for i, r in enumerate(reqs):
        if i:
            eng.step()
            eng.step()
        rids.append(eng.submit(r["prompt"], r["max_new"], quality=r["quality"],
                               speculate=r["speculate"]))
    out = eng.run_until_drained()
    return {i: out[rid] for i, rid in enumerate(rids)}


def _logits_error(params, cfg, toks, length: int,
                  cache_len: int) -> tuple[float, float]:
    """(max abs, relative) error of the packed prefill's last-position
    logits against :func:`_reference_logits`, on the first REF_LAYERS
    layers of ``params`` in float32 at the highest matmul precision."""
    from repro.models.api import Model
    from repro.models.base import init_params
    from repro.quant.store import PackedWeight

    cut = dataclasses.replace(cfg, n_layers=min(REF_LAYERS, cfg.n_layers),
                              dtype=jnp.float32)
    params = dict(params, blocks=jax.tree_util.tree_map(
        lambda a: a[:cut.n_layers], params["blocks"]))
    params = jax.tree_util.tree_map(
        lambda a: a if isinstance(a, PackedWeight) else a.astype(jnp.float32),
        params, is_leaf=lambda a: isinstance(a, PackedWeight))
    model = Model(cut)
    with jax.default_matmul_precision("highest"):
        _, got = jax.jit(model.prefill, static_argnums=(5,))(
            params, init_params(jax.random.PRNGKey(0),
                                model.cache_descs(1, cache_len)),
            jnp.asarray(toks), jnp.asarray([length], jnp.int32),
            jnp.zeros((1,), jnp.int32), 0)
        want = _reference_logits(params, cut, toks[0, -length:])
    got = np.asarray(got[0], np.float64)
    want = np.asarray(want, np.float64)
    _check(bool(np.all(np.isfinite(got))), "packed prefill logits not finite")
    max_abs = float(np.max(np.abs(got - want)))
    return max_abs, max_abs / float(np.max(np.abs(want)))


def _kernel_error(params, seed: int) -> float:
    """Largest relative error of one packed matmul against x @ the
    dequantized weight, over every packed leaf of the served tree (its
    first layer), at the decode, verify and admission-prefill row counts,
    in the served dtype, with rows cycling the quality tiers."""
    from repro.quant.store import PackedWeight

    rng = np.random.default_rng(seed)
    worst = 0.0
    for pw in jax.tree_util.tree_leaves(
            params, is_leaf=lambda a: isinstance(a, PackedWeight)):
        if not isinstance(pw, PackedWeight):
            continue
        while pw.planes.ndim - 2 - pw.rest_ndim:  # first layer of a stack
            pw = jax.tree_util.tree_map(lambda a: a[0], pw)
        k = pw.planes.shape[1] * 32
        masks = pw.tier_plane_masks()
        drops = pw.tier_drops if masks is not None else (0,)
        dense = {d: _dequantize(pw, d).reshape(k, -1) for d in set(drops)}
        for m in SERVE_ROWS:
            x = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
            tiers = np.arange(m) % len(drops)
            got = pw.matmul(x, plane_mask=None if masks is None
                            else masks[jnp.asarray(tiers)], demand_tier=0)
            got = np.asarray(got, np.float64).reshape(m, -1)
            with jax.default_matmul_precision("highest"):
                ref = {d: np.asarray(x.astype(jnp.float32) @ w, np.float64)
                       for d, w in dense.items()}
            want = np.stack([ref[drops[t]][i] for i, t in enumerate(tiers)])
            err = np.max(np.abs(got - want)) / np.max(np.abs(want))
            worst = max(worst, float(err))
    return worst


def _program_sizes(eng) -> tuple[int, ...]:
    return tuple(f._cache_size() for f in (eng._admit, eng._cont_step,
                                           eng._verify))


def run(cfg, *, n_requests: int = 8, max_new: int = 32) -> dict:
    """Serve a seeded stream on ``cfg`` and check it; returns the figures.

    Raises :class:`SmokeFailure` when a phase fails.  Runs on whatever
    backend JAX has; :func:`main` is what insists on the chip."""
    from repro import api
    from repro.kernels import dispatch
    from repro.kernels.ops import auto_interpret
    from repro.models.api import Model
    from repro.models.base import init_params
    from repro.quant.store import PackedWeight
    from repro.serve import FinishReason

    dispatch.reset_counters()
    t0 = time.perf_counter()
    model = Model(cfg)
    params = init_params(jax.random.PRNGKey(SEED), model.param_descs())
    art = api.compress(model, params)
    eng = art.engine(quality="hi", batch_slots=4)
    jax.block_until_ready(eng.params)
    setup_s = time.perf_counter() - t0
    _check(eng.per_request_quality,
           "the engine does not serve per-request quality")
    _check(eng.n_packed_leaves > 0, "the engine holds no packed weights")

    reqs = _requests(cfg, n_requests, max_new, SEED)

    # pass 1 compiles every program the stream needs; pass 2 replays the
    # same stream on warm programs and must compile nothing
    t0 = time.perf_counter()
    first = _stream(eng, reqs)
    compile_s = time.perf_counter() - t0
    sizes = _program_sizes(eng)
    t0 = time.perf_counter()
    second = _stream(eng, reqs)
    jax.block_until_ready(eng._session.cache)
    stream_s = time.perf_counter() - t0
    _check(_program_sizes(eng) == sizes,
           f"the warm stream compiled again: {sizes} -> "
           f"{_program_sizes(eng)} cached programs")
    for i, r in enumerate(reqs):
        for st in (first[i], second[i]):
            _check(st.finish_reason is FinishReason.DONE
                   and len(st.tokens) == r["max_new"],
                   f"request {i} ({r['quality']}) ended "
                   f"{st.finish_reason} with {len(st.tokens or [])} of "
                   f"{r['max_new']} tokens")
        _check(first[i].tokens == second[i].tokens,
               f"request {i} served different tokens on the replay")
    spec = first[0]
    _check(spec.drafted > 0, "the speculative request drafted nothing")

    # admission-prefill logits of one prompt, packed path, hi tier
    prompt = np.asarray(reqs[1]["prompt"], np.int32)
    width = eng.cfg.max_prompt
    toks = np.zeros((1, width), np.int32)
    toks[0, width - len(prompt):] = prompt
    logit_abs, logit_rel = _logits_error(eng.params, cfg, toks, len(prompt),
                                         eng.cfg.max_len)
    _check(logit_rel <= LOGIT_RTOL,
           f"packed prefill logits differ from the reference: max abs "
           f"{logit_abs}, relative {logit_rel} > {LOGIT_RTOL}")
    kernel_rel = _kernel_error(eng.params, SEED)
    _check(kernel_rel <= KERNEL_RTOL,
           f"a packed matmul differs from the reference: relative "
           f"{kernel_rel} > {KERNEL_RTOL}")

    routes = {r: dispatch.counters[r] for r in
              (dispatch.ROUTE_GEMV, dispatch.ROUTE_GEMM, dispatch.ROUTE_XLA)}
    _check(routes[dispatch.ROUTE_GEMV] > 0 and routes[dispatch.ROUTE_GEMM] > 0
           and routes[dispatch.ROUTE_XLA] == 0,
           f"packed matmuls did not all take the Pallas kernels: {routes}")

    # the compiled programs a chip run must hold the kernel in
    s = eng._session
    tiers = jnp.zeros((1,), jnp.int32)
    admit_text = eng._admit.lower(
        eng.params, s.zero_slot_cache, s.cache, jnp.asarray(toks),
        jnp.asarray([len(prompt)], jnp.int32), jnp.int32(0), tiers, 0,
    ).as_text()
    decode_text = eng._cont_step.lower(
        eng.params, s.cache, jnp.asarray(s.cur), jnp.asarray(s.active),
        jnp.asarray(s.tiers), 0,
    ).as_text()
    kernel_in = {"admit": "tpu_custom_call" in admit_text,
                 "decode": "tpu_custom_call" in decode_text}

    # greedy agreement with the dense-weight engine: reported, not
    # required (bf16 rounding may flip near-tied argmaxes)
    dense = art.engine(quality="hi", batch_slots=4, packed=False)
    hi = [i for i, r in enumerate(reqs) if r["quality"] == "hi"]
    dense_out = _stream(dense, [dict(reqs[i], quality=None, speculate=None)
                                for i in hi])
    agree = sum(a == b for j, i in enumerate(hi)
                for a, b in zip(first[i].tokens, dense_out[j].tokens,
                                strict=True))
    total = sum(len(first[i].tokens) for i in hi)

    packed = [x for x in jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda a: isinstance(a, PackedWeight))
        if isinstance(x, PackedWeight)]
    bits_per_weight = (sum(x.nbits() for x in packed)
                       / sum(int(np.prod(x.shape)) for x in packed))
    stats = jax.devices()[0].memory_stats() or {}
    tokens = sum(len(st.tokens) for st in second.values())
    report = {
        "setup_s": setup_s, "compile_s": compile_s, "stream_s": stream_s,
        "tokens_served": tokens, "requests": len(reqs),
        "tiers": sorted({r["quality"] for r in reqs}),
        "spec_drafted": spec.drafted, "spec_accepted": spec.accepted,
        "n_packed_leaves": eng.n_packed_leaves,
        "bits_per_weight": bits_per_weight,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "logit_max_abs": logit_abs, "logit_rel": logit_rel,
        "logit_rtol": LOGIT_RTOL, "logit_layers": min(REF_LAYERS, cfg.n_layers),
        "kernel_rel": kernel_rel, "kernel_rtol": KERNEL_RTOL,
        "routes": routes, "interpret": auto_interpret(),
        "kernel_in_program": kernel_in,
        "dense_agreement": (agree, total),
    }
    print("smoke figures (one run of this script, not benchmark metrics):")
    for k, v in report.items():
        print(f"  {k}: {v}")
    return report


def main() -> int:
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"jax {jax.__version__}, libtpu {libtpu}, compile cache {cache_dir}")
    print(f"devices: {jax.devices()}")
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"no TPU: JAX's default backend is {backend!r}", file=sys.stderr)
        return 1

    from repro.configs import get_arch

    report = run(get_arch("smollm_135m"))
    _check(not report["interpret"], "the Pallas kernels ran interpreted")
    _check(all(report["kernel_in_program"].values()),
           f"a compiled serving program holds no Pallas kernel: "
           f"{report['kernel_in_program']}")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
