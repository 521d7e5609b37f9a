"""Serving launcher: --arch <id> [--wire [--quality T] [--dense]] [--stream].

Loads exact params (fresh init on this CPU container) or compresses the
model into a quality-dialed EdgeArtifact and serves batched decoding
through the facade (`repro.api`).  With --wire the engine keeps matmul
weights in 3-bit bit-plane form end-to-end; --quality picks the serving
tier (lower tiers drop LSB bit-planes from the least-sensitive layers —
no re-quantization); add --dense to decode everything at load and compare.

``--stream`` drives the continuous-batching scheduler instead of one
static generate(): synthetic prompts arrive staggered (every
``--arrival-every`` engine steps), are submitted mid-decode, and tokens
print as each request finishes — along with per-request waiting time and
latency in steps, the numbers a static batch cannot hit because a new
prompt would wait for the whole batch to drain.  With ``--wire`` add
``--mixed-tiers`` to cycle each arrival through the artifact's quality
tiers (hi/mid/lo/...): every request is prefilled and decoded at its OWN
tier inside the one shared dispatch — per-request quality, no retrace,
no param-tree swap.

Robust-serving knobs (with ``--stream``): ``--deadline`` ages requests on
the engine's cost clock and evicts them mid-decode once past it
(TIMED_OUT, partial tokens kept); ``--slo`` turns on QualityShed
admission (downgrade hi->mid->lo against the budget, shed past it);
``--max-queue`` bounds the scheduler queue (REJECTED beyond it).  Every
terminal request prints its typed finish_reason — nothing hangs.

``--speculate TIER[:K]`` (with ``--wire --stream``) turns on
self-speculative decoding: every request drafts K tokens per round at
TIER — a cheaper plane mask over the SAME packed weights, streamed via
the demand floor — then one hi-tier dispatch verifies the window and
keeps the longest agreeing prefix.  Tokens are identical to plain
serving; the wins print per request as drafted/accepted counters and as
the stream's acceptance rate and weight-bytes per accepted token.

The launcher serves on one device; it builds no mesh.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import api
from repro.configs import ARCH_IDS, get_arch
from repro.launch.compile_cache import use_compile_cache
from repro.models.api import Model
from repro.models.base import init_params
from repro.quant import tree_bits_report
from repro.serve import ServeConfig, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="smollm_135m")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--wire", action="store_true",
                    help="compress to the QSQ wire artifact and serve it")
    ap.add_argument("--quality", default="hi",
                    choices=api.DEFAULT_TIERS.names(),
                    help="with --wire: serving tier (plane truncation, "
                         "no re-quantization)")
    ap.add_argument("--dense", action="store_true",
                    help="with --wire: decode the whole tree at load instead "
                         "of serving packed bit-planes")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompts", type=int, default=None,
                    help="number of synthetic prompts to serve "
                         "(default: min(--slots, 3))")
    ap.add_argument("--stream", action="store_true",
                    help="continuous batching: submit prompts at staggered "
                         "arrivals and admit them mid-decode (attention "
                         "families, greedy)")
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="with --stream: engine steps between arrivals")
    ap.add_argument("--mixed-tiers", action="store_true",
                    help="with --wire --stream: cycle arrivals through the "
                         "artifact's quality tiers — each request served "
                         "at its own tier in the one shared dispatch")
    ap.add_argument("--deadline", type=float, default=None,
                    help="with --stream: per-request deadline in cost-clock "
                         "units — queued requests past it are cancelled, "
                         "in-flight ones evicted mid-decode (TIMED_OUT)")
    ap.add_argument("--slo", type=float, default=None,
                    help="with --stream: enable QualityShed admission — "
                         "downgrade tiers to hold estimated latency under "
                         "this budget (cost-clock units), shed when even "
                         "the lowest tier misses it")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="with --stream: bound the scheduler queue; "
                         "arrivals beyond it finish as REJECTED")
    ap.add_argument("--speculate", default=None, metavar="TIER[:K]",
                    help="with --wire --stream: self-speculative decoding — "
                         "draft K tokens/round (default 4) at TIER (a "
                         "cheaper plane mask of the same packed weights), "
                         "verify in one serving-tier dispatch; tokens stay "
                         "identical to plain serving")
    args = ap.parse_args()

    if args.slots < 1:
        ap.error("--slots must be >= 1")
    if args.prompts is None:
        # streams queue beyond the slot count — that's the point
        args.prompts = args.slots + 2 if args.stream else min(args.slots, 3)
    elif args.prompts < 1:
        ap.error(f"--prompts must be >= 1; got {args.prompts}")
    elif not args.stream and args.prompts > args.slots:
        ap.error(f"--prompts must be in [1, --slots={args.slots}] without "
                 f"--stream (a static batch cannot queue); got {args.prompts}")
    if args.arrival_every < 1:
        ap.error("--arrival-every must be >= 1")
    if not args.wire and (args.quality != "hi" or args.dense):
        ap.error("--quality/--dense only apply with --wire")
    if args.mixed_tiers and not (args.wire and args.stream):
        ap.error("--mixed-tiers needs --wire --stream (per-request quality "
                 "rides the continuous scheduler on the packed artifact)")
    if args.mixed_tiers and args.dense:
        ap.error("--mixed-tiers needs packed serving (drop --dense)")
    if not args.stream and (args.deadline is not None or args.slo is not None
                            or args.max_queue is not None):
        ap.error("--deadline/--slo/--max-queue only apply with --stream "
                 "(a static generate() has no queue to protect)")
    if args.deadline is not None and args.deadline <= 0:
        ap.error("--deadline must be > 0")
    if args.max_queue is not None and args.max_queue < 0:
        ap.error("--max-queue must be >= 0")
    speculate = None
    if args.speculate is not None:
        if not (args.wire and args.stream) or args.dense:
            ap.error("--speculate needs --wire --stream packed serving "
                     "(the draft tier is a plane mask over the packed "
                     "artifact inside the continuous scheduler)")
        if args.mixed_tiers:
            ap.error("--speculate cannot combine with --mixed-tiers: the "
                     "draft tier must sit strictly below every request's "
                     "serving tier, which a full tier cycle violates")
        draft, _, kstr = args.speculate.partition(":")
        names = api.DEFAULT_TIERS.names()
        if draft not in names:
            ap.error(f"--speculate tier must be one of {names}; got "
                     f"{draft!r}")
        if names.index(draft) <= names.index(args.quality):
            ap.error(f"--speculate tier {draft!r} must sit strictly below "
                     f"the serving tier {args.quality!r}")
        try:
            k = int(kstr) if kstr else 4
        except ValueError:
            ap.error(f"--speculate window must be an integer; got {kstr!r}")
        if k < 1:
            ap.error(f"--speculate window must be >= 1; got {k}")
        speculate = api.SpecConfig(draft, k)

    use_compile_cache()
    cfg = get_arch(args.arch, smoke=args.smoke)
    model = Model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_descs())

    admission = None
    if args.slo is not None:
        admission = api.QualityShed(api.SLOBudget(latency=args.slo,
                                                  max_queue=args.max_queue))
    if args.wire:
        artifact = api.compress(model, params)
        engine = artifact.engine(
            quality=args.quality, batch_slots=args.slots,
            packed=not args.dense, admission=admission,
            max_queue=args.max_queue,
        )
        rep = tree_bits_report(engine.params)
        print(
            f"serving tier {args.quality!r} from the QSQ wire artifact "
            f"({engine.n_packed_leaves} leaves served packed, "
            f"{rep['savings'] * 100:.0f}% below f32)"
        )
    else:
        engine = ServeEngine(model, params, ServeConfig(
            batch_slots=args.slots, admission=admission,
            max_queue=args.max_queue))

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab, size=rng.randint(2, 6)).tolist()
               for _ in range(args.prompts)]
    if args.stream:
        tiers = None
        if args.mixed_tiers:
            if not engine.per_request_quality:
                ap.error("this artifact/config cannot serve per-request "
                         "tiers (needs a greedy attention family AND an "
                         "artifact with a sensitivity ranking — rebuild a "
                         "bare wire with repro.api.compress)")
            names = engine.tier_names
            tiers = [names[i % len(names)] for i in range(len(prompts))]
        if speculate is not None and not engine.per_request_quality:
            ap.error("--speculate needs per-request quality serving (a "
                     "greedy attention family and an artifact with a "
                     "sensitivity ranking)")
        _serve_stream(engine, prompts, args.max_new, args.arrival_every,
                      tiers=tiers, deadline=args.deadline,
                      speculate=speculate)
        return
    t0 = time.time()
    outs = engine.generate(prompts, max_new=args.max_new)
    dt = time.time() - t0
    for p, o in zip(prompts, outs, strict=True):
        print(f"  {p} -> {o}")
    n = len(prompts) * args.max_new
    print(f"{n} tokens in {dt:.2f}s ({n / dt:.1f} tok/s)")


def _serve_stream(engine, prompts, max_new: int, arrival_every: int,
                  tiers=None, deadline: float | None = None,
                  speculate=None) -> None:
    """Feed staggered arrivals through submit()/step()/poll(): prompt i
    arrives at step i * arrival_every and joins the running decode as soon
    as a slot frees — no batch flush.  ``tiers`` (one name per prompt)
    submits each request at its own quality tier into the shared dispatch.
    ``speculate`` (a SpecConfig) drafts every request at a cheap tier and
    verifies at its serving tier; accepted/drafted counters print per
    request.  Prints each request as it terminates with its typed
    finish_reason (done / timed_out / cancelled / shed / rejected),
    realized tier, waiting time (queued steps) and latency (arrival ->
    last token)."""
    t0 = time.time()
    pending = list(enumerate(prompts))
    rid_to_prompt = {}
    while pending or engine.has_work:
        step_idx = engine.step_count
        while pending and pending[0][0] * arrival_every <= step_idx:
            i, p = pending.pop(0)
            tier = tiers[i] if tiers is not None else None
            rid = engine.submit(p, max_new=max_new, quality=tier,
                                deadline=deadline, speculate=speculate)
            rid_to_prompt[rid] = p
            tag = f" @{tier}" if tier is not None else ""
            print(f"  step {step_idx:3d}  submit    r{rid}{tag} {p}")
        engine.step()
        for rid, st in engine.poll().items():
            tag = f" @{st.quality}" if st.quality is not None else ""
            reason = st.finish_reason.value
            where = f"step {st.finished:3d}" if st.finished is not None \
                else f"step {step_idx:3d}"
            line = f"  {where}  {reason:9s} r{rid}{tag} {rid_to_prompt[rid]}"
            if st.tokens:
                line += f" -> {st.tokens}"
            if st.drafted:
                line += f" [spec {st.accepted}/{st.drafted} accepted]"
            if st.waiting is not None and st.latency is not None:
                line += f" (waited {st.waiting}, latency {st.latency} steps)"
            elif st.detail:
                line += f" ({st.detail})"
            print(line)
    dt = time.time() - t0
    done = [r for r in engine.completed_requests.values()
            if r.waiting is not None and r.latency is not None]
    n = sum(len(r.out) for r in done)
    mean_wait = np.mean([r.waiting for r in done]) if done else 0.0
    mean_lat = np.mean([r.latency for r in done]) if done else 0.0
    print(f"{n} tokens / {len(rid_to_prompt)} requests in {dt:.2f}s "
          f"({n / dt:.1f} tok/s; mean wait {mean_wait:.1f} steps, "
          f"mean latency {mean_lat:.1f} steps)")
    if speculate is not None:
        st = engine.stream_stats()
        print(f"speculative: drafted {st['drafted']}, accepted "
              f"{st['accepted']} (rate {st['acceptance_rate']:.3f}); "
              f"{st['bytes_per_token']:.0f} weight bytes per accepted "
              f"token ({st['read_frac']:.2f} of full-plane reads)")


if __name__ == "__main__":
    main()
