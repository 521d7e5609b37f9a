"""Batched serving engine — serves directly from the 3-bit wire.

Engines are normally built through the quality-dial facade
(:func:`repro.api.compress` -> ``EdgeArtifact.engine(quality=...)``): the
wire path is the paper's edge flow — the 3-bit + scalar artifact crosses
the channel and is served WITHOUT a full-tree dequantize.  Matmul weights
stay packed (:class:`~repro.quant.store.PackedWeight` bit-planes) end to
end and are decoded tile-by-tile inside the fused Pallas dequant-matmul,
so serving actually realizes the 3.2-4.6x weight-HBM cut the kernel was
built for.  Only non-matmul leaves (embeddings, norms, attention output
projections, convs) are decoded once at load, per the QuantPolicy
exclusions.  ``set_quality`` re-dials an artifact-built engine to another
tier in place — LSB plane truncation on the already-loaded wire, never a
re-quantize.

Serving is REQUEST-LEVEL continuously batched (attention families):
``submit()`` enqueues a prompt, each ``step()`` admits queued requests
into FREE slots — one single-slot prefill (the one-dispatch causal
forward on a zeroed batch-1 cache) plus a traced cache-lane insert per
admission — then runs ONE fixed-width greedy decode iteration over all
lanes.  Per-slot cache positions and an ``active`` mask make finished and
empty slots dead lanes rather than shape changes, so admissions and
evictions never retrace, and a new prompt starts decoding next step
instead of waiting for the whole batch to drain.  Finished requests are
evicted in the same step and surface through ``poll()`` /
``run_until_drained()``.

Quality is PER-REQUEST on artifact-built packed continuous engines:
``submit(prompt, max_new, quality="lo")`` admits the request at its own
tier, and the mixed-tier batch shares the one decode dispatch — each
packed matmul takes a per-row plane mask derived from the per-slot tier
indices (``PackedWeight.tier_drops``), so every lane's tokens are
bit-identical to a single-tier engine serving that prompt alone at that
tier, and tier changes are mask flips (no retrace, no param-tree swap).
``set_quality`` then only moves the default tier for quality-less
submissions.

The stream is OVERLOAD-GRACEFUL: ``submit(..., deadline=...)`` puts the
request on a cost-clock budget — each dispatch advances the stream clock
by its weight-read fraction (a full-quality forward costs 1.0, a
demand-shortened one its ``read_frac``), so the clock ticks in
HBM-bandwidth units, the resource the paper's plane truncation buys
back.  Past-deadline requests are TIMED_OUT: popped from the queue, or
evicted mid-decode by an active-mask flip (zero retrace; survivors are
bit-identical; any tokens already emitted remain as a partial result).
``cancel(rid)`` is the caller-initiated twin.  A pluggable
:class:`~repro.serve.admission.AdmissionPolicy` (``ServeConfig.admission``)
can downgrade incoming tiers — degrade quality instead of latency —
before shedding, and ``ServeConfig.max_queue`` bounds the queue; every
outcome surfaces as a typed
:class:`~repro.serve.scheduler.FinishReason` through the structured
:meth:`poll`.

``generate()`` is a thin submit-all/drain wrapper over that scheduler for
greedy attention-family engines, and otherwise falls back to the static
two-program path (one-dispatch prefill + multi-token decode scan, or the
temperature-sampled scan when ``ServeConfig.temperature > 0``).  The
wrapper trades the static scan's single host sync for one sync per
step() — the cost of a schedulable decode loop; throughput-bound batch
decoding with no arrival stream can set ``ServeConfig(continuous=False)``
to keep the one-scan path (tokens are identical either way).

Dense families keep the exactness guarantee: per-slot left padding and
active masking mean a prompt's tokens are invariant to its batch mates
AND to when they were admitted.  MoE keeps the weaker guarantee the
static batch had — all lanes share expert capacity, and under the
scheduler that includes DEAD lanes (a FREE/DONE slot's frozen token
still routes through the experts), so an MoE request's tokens can shift
with slot history under capacity overflow, exactly as they could with
live batch mates.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from repro.models.api import Model
from repro.models.base import init_params
from repro.serve.admission import ADMIT, REJECT, SHED, AdmissionPolicy, LoadView
from repro.serve.scheduler import (
    FinishReason,
    Request,
    RequestStatus,
    Scheduler,
    SpecConfig,
    SubmitRejected,
    plane_demand,
)
from repro.train.step import (
    make_admit_step,
    make_cache_prefill_step,
    make_cont_decode_step,
    make_decode_loop,
    make_sample_decode_loop,
    make_serve_step,
    make_verify_step,
    supports_fused_prefill,
)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch_slots: int = 8
    max_len: int = 256    # continuous sessions: KV cache length per slot
    temperature: float = 0.0  # 0 => greedy; > 0 => categorical sampling
    packed: bool = True  # wire loads: keep matmul weights in bit-plane form
    continuous: bool = True  # greedy attention-family generate() -> scheduler
    max_prompt: int = 64  # continuous sessions: fixed prefill width
    max_queue: int | None = None  # bound on queued requests; None = unbounded
    # pluggable SLO admission control (see repro.serve.admission); None
    # admits everything at the requested tier, exactly the pre-SLO behavior
    admission: AdmissionPolicy | None = None


@dataclasses.dataclass(frozen=True)
class StepInfo:
    """What one :meth:`ServeEngine.step` did — host-side accounting only.

    ``cost`` is the step's advance of the stream cost clock (sum of its
    dispatches' weight-read fractions); ``demand`` the decode dispatch's
    static plane-demand floor (None when no lane was live)."""

    admitted: tuple[int, ...]
    finished: tuple[int, ...]
    timed_out: tuple[int, ...]
    live: int
    demand: int | None
    cost: float
    # speculative round accounting: draft-tier tokens proposed this step
    # and how many of them the verify dispatch accepted (0/0 for plain
    # decode steps)
    drafted: int = 0
    accepted: int = 0


class _Session:
    """Device-side state of one continuous-batching stream: the live
    multi-slot cache, the per-slot current tokens / active mask, and the
    host-side :class:`Scheduler`.  All shapes are fixed at construction
    ((slots, cache_len) cache, (1, prefill_len) admission prompts), so
    every jitted program traces once per session shape."""

    def __init__(self, model: Model, slots: int, prefill_len: int,
                 cache_len: int, max_queue: int | None = None):
        if prefill_len < 1:
            raise ValueError(f"prefill width must be >= 1, got {prefill_len}")
        if prefill_len >= cache_len:
            raise ValueError(
                f"cache_len {cache_len} leaves no decode room after the "
                f"{prefill_len}-token prefill window"
            )
        self.prefill_len = prefill_len
        self.cache_len = cache_len
        self.sched = Scheduler(slots, max_queue=max_queue)
        key = jax.random.PRNGKey(0)
        self.cache = init_params(key, model.cache_descs(slots, cache_len))
        # zeroed batch-1 cache reused (never donated) by every admission
        self.zero_slot_cache = init_params(key, model.cache_descs(1, cache_len))
        self.cur = np.zeros((slots, 1), np.int32)
        self.active = np.zeros((slots,), np.int32)
        # per-slot quality-tier index (per-request quality): set at
        # admission, a traced operand of the decode dispatch — tier
        # changes are data changes, never retraces
        self.tiers = np.zeros((slots,), np.int32)
        self.step_idx = 0
        # stream cost clock: advances by each dispatch's weight-read
        # fraction (full quality = 1.0); deadlines are enforced on it
        self.now = 0.0
        # demand-streaming meter: packed weight-plane words the stream's
        # dispatches read vs. what full-quality streaming would have read,
        # and the tokens those dispatches emitted (host-side analytic
        # accounting — the device program's reads are shaped by the same
        # static demand, so the two agree by construction)
        self.plane_words_read = 0
        self.plane_words_full = 0
        self.tokens_emitted = 0
        # self-speculative decoding meter: draft-tier tokens proposed vs.
        # accepted by verify dispatches across the stream's lifetime
        self.drafted = 0
        self.accepted = 0


class ServeEngine:
    def __init__(self, model: Model, params, cfg: ServeConfig):
        self.model = model
        self.cfg = cfg
        self.params = params
        self.n_packed_leaves = 0  # overwritten by the artifact/wire loaders
        self.artifact = None      # set by EdgeArtifact.engine (quality dial)
        self.quality: str | None = None
        # per-request quality: tier-name order matching the tier_drops
        # vectors stamped on the packed leaves (set by EdgeArtifact.engine
        # when the engine serves per-request tiers); None = single-tier
        self.tier_names: list[str] | None = None
        # degraded-wire ceiling: the best (lowest) tier index this engine
        # may serve.  0 = pristine artifact; EdgeArtifact.engine raises it
        # when trailing LSB planes failed their checksums, so requests are
        # silently clamped DOWN to what the surviving planes support
        # (requested tier stays visible in RequestStatus.requested)
        self.tier_ceiling: int = 0
        self.serve_step = jax.jit(make_serve_step(model))
        self._prefill = jax.jit(make_cache_prefill_step(model),
                                static_argnums=(5,))  # demand: see below
        self._decode_loop = jax.jit(make_decode_loop(model))
        self._sample_loop = None  # jitted lazily; most engines stay greedy
        # continuous-batching programs (attention families; traced lazily).
        # ``demand`` — the batch plane-demand floor — is a STATIC argument:
        # plane-major packed weights shorten their HBM reads per demand, so
        # each distinct demand is its own trace, bounded by the tier count
        self._cont_step = jax.jit(make_cont_decode_step(model),
                                  static_argnums=(5,))
        self._admit = jax.jit(make_admit_step(model), static_argnums=(7,))
        # speculative verify: one trace per (demand, window width) pair —
        # demand is bounded by the tier count, width by the draft k
        self._verify = jax.jit(make_verify_step(model), static_argnums=(7,))
        self._session: _Session | None = None
        self._plane_words_cache: dict[int, tuple[int, int]] = {}

    # -- loading -----------------------------------------------------------
    @classmethod
    def from_wire(cls, model: Model, wire_tree, cfg: ServeConfig):
        """Deprecated shim over :class:`repro.quant.artifact.EdgeArtifact`.

        Equivalent to ``EdgeArtifact(wire, model.cfg).engine("hi",
        serve_cfg=cfg)``: full-quality serving with kernel-eligible matmul
        weights re-packed to bit-planes (``cfg.packed``, default) or a full
        dense decode at load (``packed=False``).  New code should call
        ``repro.api.compress(...)`` and dial quality on the artifact.
        """
        warnings.warn(
            "ServeEngine.from_wire is deprecated; use repro.api.compress() "
            "/ EdgeArtifact.engine(quality=...) instead",
            DeprecationWarning, stacklevel=2,
        )
        from repro.quant.artifact import EdgeArtifact

        art = EdgeArtifact(wire=wire_tree, arch_config=model.cfg)
        return art.engine(quality="hi", serve_cfg=cfg)

    # -- quality dial ------------------------------------------------------
    @property
    def per_request_quality(self) -> bool:
        """True when this engine serves quality PER REQUEST: packed leaves
        carry per-tier plane-drop vectors, ``submit(..., quality=...)``
        admits each request at its own tier inside the one continuous
        decode dispatch, and :meth:`set_quality` is just the default for
        quality-less submissions (no drain, no param rebuild)."""
        return self.tier_names is not None

    def _clamp_ceiling(self, quality: str | None) -> str | None:
        """Degraded-wire clamp: tiers better than ``tier_ceiling`` would
        stream planes that failed their checksums — serve the ceiling
        tier instead (degrade, don't fail)."""
        if (self.tier_ceiling and self.tier_names is not None
                and quality is not None
                and self.tier_names.index(quality) < self.tier_ceiling):
            return self.tier_names[self.tier_ceiling]
        return quality

    def _resolve_quality(self, quality: str | None) -> str | None:
        """Validate a submit-time tier name (None -> the engine default)."""
        if quality is None:
            return self._clamp_ceiling(self.quality)
        if self.tier_names is None:
            raise ValueError(
                "per-request quality needs an engine with per-tier packed "
                "weights; build it via repro.api.compress(...).engine() "
                "(this engine serves a single tier)"
            )
        if quality not in self.tier_names:
            raise KeyError(
                f"unknown quality tier {quality!r}; this engine has "
                f"{self.tier_names}"
            )
        return self._clamp_ceiling(quality)

    def _tier_index(self, quality: str | None) -> int:
        if self.tier_names is None or quality is None:
            return 0
        return self.tier_names.index(quality)

    def set_quality(self, quality: str) -> "ServeEngine":
        """Dial the engine's quality tier.

        Per-request engines (built by ``EdgeArtifact.engine`` with packed
        continuous serving): the params already carry every tier — this
        just changes the DEFAULT tier for future quality-less
        ``submit``/``generate`` calls.  No drain, no reload, no retrace;
        live requests keep the tier they were admitted at.

        Single-tier engines re-resolve the param tree at the new tier of
        this engine's artifact, in place — plane truncation on the loaded
        wire, no reload and no re-quantization.  The jitted programs take
        params as arguments, so the dial costs one retrace, not a rebuild.
        A live continuous stream must drain first (its KV entries were
        computed at the old tier); an idle session is dropped."""
        if self.artifact is None:
            raise ValueError(
                "this engine was not built from an EdgeArtifact; construct "
                "it via repro.api.compress(...).engine(quality=...) to dial "
                "quality"
            )
        if self.per_request_quality:
            self.quality = self._resolve_quality(quality)
            return self
        if self.has_work:
            raise RuntimeError(
                "cannot re-dial quality while a continuous stream has live "
                "requests; run_until_drained() (or poll results) first"
            )
        self._session = None
        self._plane_words_cache.clear()  # params change: re-derive meter
        self.params, self.n_packed_leaves = self.artifact.serve_params(
            quality, packed=self.cfg.packed
        )
        self.quality = quality
        return self

    # -- continuous batching ------------------------------------------------
    def _continuous_capable(self) -> bool:
        return supports_fused_prefill(self.model)

    def _require_continuous(self):
        if self.cfg.temperature > 0:
            raise ValueError(
                "the continuous scheduler is greedy-only; build the engine "
                "with temperature=0 (generate() still samples via the "
                "static path)"
            )
        if not self._continuous_capable():
            raise ValueError(
                f"continuous batching needs an attention family with "
                f"per-lane KV isolation; {self.model.cfg.family!r} "
                f"(cross_every={self.model.cfg.cross_every}) serves via "
                f"generate()"
            )

    def _ensure_session(self) -> _Session:
        if self._session is None:
            self._session = _Session(
                self.model, self.cfg.batch_slots,
                prefill_len=self.cfg.max_prompt, cache_len=self.cfg.max_len,
                max_queue=self.cfg.max_queue,
            )
        return self._session

    def _admission_view(self, s: _Session) -> LoadView:
        """Snapshot the stream load for an :class:`AdmissionPolicy`:
        per-request (tier index, remaining dispatches) for queued and live
        work plus the per-tier dispatch cost table."""
        names = (tuple(self.tier_names) if self.tier_names is not None
                 else (self.quality or "default",))
        return LoadView(
            step=s.step_idx, now=s.now, n_slots=s.sched.n_slots,
            tier_names=names, tier_costs=self.tier_cost_table(),
            queued=tuple((self._tier_index(r.quality), r.max_new)
                         for r in s.sched.queue),
            live=tuple((self._tier_index(r.quality),
                        max(r.max_new - len(r.out), 0))
                       for r in s.sched.slot_req if r is not None),
        )

    def submit(self, prompt: Sequence[int], max_new: int = 32,
               quality: str | None = None,
               deadline: float | None = None,
               speculate: SpecConfig | None = None) -> int:
        """Enqueue one prompt on the engine's continuous stream; returns a
        request id for :meth:`poll`.  The request is admitted into the
        first slot that frees up — immediately on the next :meth:`step`
        if one is FREE — without flushing the requests already decoding.

        ``quality`` names the request's OWN tier (per-request engines): it
        is prefilled AND decoded at that tier inside the shared fixed-width
        dispatches, sharing the batch with requests at other tiers.  None
        takes the engine default (``set_quality``), resolved at submission
        time.

        ``deadline`` is a RELATIVE cost-clock budget (see :attr:`now`):
        once the stream clock has advanced that far the request is timed
        out wherever it is — queued (popped) or mid-decode (evicted by an
        active-mask flip, keeping its partial tokens).

        ``speculate`` turns on SELF-SPECULATIVE decoding for this request
        (:class:`~repro.serve.scheduler.SpecConfig`): the engine drafts
        ``k`` tokens per round at ``draft_tier`` — a cheaper plane mask
        over the same packed weights, streamed at the draft demand floor —
        then verifies the whole window in one dispatch at the request's
        serving tier, accepting the longest agreeing prefix and rolling
        the KV ``pos`` back over rejections.  Tokens are identical to
        plain decode at the serving tier; only the dispatch mix changes.
        The draft tier must sit strictly below the serving tier, and the
        engine must serve per-request quality on a full-length cache.

        Requests that can NEVER be served raise :class:`SubmitRejected`
        (a ValueError) — oversized prompt, cache overflow, non-positive
        deadline, unusable speculation config — instead of queueing a
        guaranteed hang.  LOAD-dependent refusals never raise: a full
        ``max_queue`` or an admission-policy shed returns a rid that is
        already terminal with ``finish_reason`` ``REJECTED``/``SHED``."""
        self._require_continuous()
        quality = self._resolve_quality(quality)
        requested = quality
        if speculate is not None:
            self._check_speculate(speculate, quality)
        s = self._ensure_session()
        if len(prompt) > s.prefill_len:
            raise SubmitRejected(
                f"prompt of {len(prompt)} tokens exceeds the stream's "
                f"fixed {s.prefill_len}-token prefill window; raise "
                f"ServeConfig.max_prompt"
            )
        if s.prefill_len + max_new > s.cache_len:
            raise SubmitRejected(
                f"prefill window {s.prefill_len} + max_new {max_new} "
                f"exceeds the {s.cache_len}-entry slot cache; raise "
                f"ServeConfig.max_len"
            )
        if deadline is not None and not deadline > 0:
            raise SubmitRejected(
                f"deadline must be a positive cost-clock budget, "
                f"got {deadline}"
            )
        if s.sched.queue_full:
            return s.sched.finish_unadmitted(
                prompt, max_new, s.step_idx, FinishReason.REJECTED,
                quality=quality, requested=requested, arrival_t=s.now,
                detail=f"bounded queue full (max_queue={s.sched.max_queue})",
            )
        if self.cfg.admission is not None:
            d = self.cfg.admission.decide(
                self._tier_index(quality), max_new, self._admission_view(s))
            if d.action == ADMIT:
                if d.tier is not None and self.tier_names is not None:
                    # quality-scalable shedding: serve a cheaper tier
                    # instead of queueing past the SLO
                    quality = self.tier_names[
                        max(int(d.tier), self.tier_ceiling)]
            elif d.action in (SHED, REJECT):
                reason = (FinishReason.SHED if d.action == SHED
                          else FinishReason.REJECTED)
                return s.sched.finish_unadmitted(
                    prompt, max_new, s.step_idx, reason, quality=quality,
                    requested=requested, arrival_t=s.now, detail=d.detail,
                )
            else:
                raise ValueError(
                    f"admission policy returned unknown action {d.action!r}")
        abs_deadline = None if deadline is None else s.now + float(deadline)
        return s.sched.submit(prompt, max_new, arrival=s.step_idx,
                              quality=quality, requested=requested,
                              deadline=abs_deadline, arrival_t=s.now,
                              speculate=speculate)

    def _check_speculate(self, sc: SpecConfig, quality: str | None) -> None:
        """Reject speculation configs that could never save anything:
        guaranteed-useless setups fail loud at submit, while a mere
        admission-policy downgrade to the draft tier later just disables
        drafting for the affected rounds."""
        if not self.per_request_quality:
            raise SubmitRejected(
                "speculative decoding drafts at a cheaper tier of the same "
                "packed weights, which needs a per-request-quality engine; "
                "build it via repro.api.compress(...).engine()"
            )
        if self.model.cfg.window is not None:
            raise SubmitRejected(
                "speculative decoding needs a full-length KV cache; this "
                "model's sliding-window ring buffer cannot roll back "
                "rejected entries"
            )
        if sc.k < 1:
            raise SubmitRejected(
                f"speculate.k must be >= 1 drafted tokens, got {sc.k}")
        if sc.draft_tier not in self.tier_names:
            raise SubmitRejected(
                f"unknown draft tier {sc.draft_tier!r}; this engine has "
                f"{self.tier_names}"
            )
        if self.tier_names.index(sc.draft_tier) <= self._tier_index(quality):
            raise SubmitRejected(
                f"draft tier {sc.draft_tier!r} is not below serving tier "
                f"{quality!r} on the ladder {self.tier_names}; drafting "
                f"there could never save weight reads"
            )

    def cancel(self, rid: int) -> RequestStatus:
        """Caller-initiated abort.  A queued request is removed; a live one
        is evicted mid-decode — an active-mask flip, zero retrace, its
        partial tokens kept.  Idempotent: an already-terminal rid returns
        its (unchanged) status; unknown rids raise KeyError."""
        if self._session is None:
            raise KeyError(f"unknown request id {rid} (no active stream)")
        s = self._session
        _, slot = s.sched.cancel(rid, s.step_idx, s.now)
        if slot is not None:
            s.active[slot] = 0  # dead lane: a data change, never a retrace
        return s.sched.status(rid)

    def _forward_plane_words(self, demand: int) -> tuple[int, int]:
        """(words_read, words_full): packed weight-plane int32 words ONE
        full forward streams at static plane-demand floor ``demand``, vs.
        what it would stream reading every plane.  Analytic — derived from
        the packed leaves' shapes and per-tier drop vectors, the same
        quantities the demand-routed kernels shape their HBM reads by.
        Interleaved leaves always stream all three planes (masking happens
        post-load); plane-major leaves shorten the read."""
        from repro.quant.store import PackedWeight

        cached = self._plane_words_cache.get(demand)
        if cached is not None:
            return cached
        read = full = 0
        for leaf in jax.tree_util.tree_leaves(
            self.params, is_leaf=lambda x: isinstance(x, PackedWeight)
        ):
            if not isinstance(leaf, PackedWeight):
                continue
            words = leaf.planes.size // 3  # int32 words per plane
            full += 3 * words
            n_read = (3 - leaf.demand_drop(demand)
                      if leaf.plane_major else 3)
            read += n_read * words
        self._plane_words_cache[demand] = (read, full)
        return read, full

    def _dispatch_cost(self, demand: int) -> float:
        """One dispatch's advance of the stream cost clock: its weight
        read fraction at ``demand`` (packed weights dominate decode time
        on the HBM-bandwidth model the plane-streaming kernels optimize;
        a full-quality dispatch is the 1.0 reference).  Engines with no
        packed leaves tick 1.0 per dispatch — a plain step counter."""
        read, full = self._forward_plane_words(demand)
        return read / full if full else 1.0

    def tier_cost_table(self) -> tuple[float, ...]:
        """Per-tier dispatch cost (weight-read fraction at each tier's
        demand floor), indexed like ``tier_names`` — the cost side of the
        admission policy's quality/cost knapsack.  Single-tier engines
        get the one-entry table ``(1.0,)``."""
        n = len(self.tier_names) if self.tier_names is not None else 1
        return tuple(self._dispatch_cost(t) for t in range(n))

    def stream_stats(self) -> dict:
        """Demand-streaming meter for the current continuous stream:
        ``tokens`` emitted, packed weight-plane ``bytes_read`` the stream's
        dispatches streamed, ``bytes_full`` a full-quality stream would
        have, and ``bytes_per_token`` — the bench_serve headline number."""
        s = self._session
        if s is None or s.tokens_emitted == 0:
            return {"tokens": 0, "bytes_read": 0, "bytes_full": 0,
                    "bytes_per_token": 0.0, "read_frac": 1.0,
                    "drafted": 0, "accepted": 0, "acceptance_rate": 0.0}
        bytes_read = 4 * s.plane_words_read
        bytes_full = 4 * s.plane_words_full
        return {
            "tokens": s.tokens_emitted,
            "bytes_read": bytes_read,
            "bytes_full": bytes_full,
            # every emitted token is an accepted (verify-tier-exact) token,
            # so for speculative streams this IS bytes per accepted token:
            # draft reads land in the numerator, rejected drafts never
            # reach the denominator
            "bytes_per_token": bytes_read / s.tokens_emitted,
            "read_frac": bytes_read / bytes_full if bytes_full else 1.0,
            "drafted": s.drafted,
            "accepted": s.accepted,
            "acceptance_rate": (s.accepted / s.drafted
                                if s.drafted else 0.0),
        }

    def step(self) -> StepInfo:
        """One scheduler iteration: enforce deadlines (pop expired queued
        requests; evict expired live ones by active-mask flip), admit
        queued requests into FREE slots (single-slot prefill + cache lane
        insert each, emitting the request's first token from the prefill
        logits), then ONE decode dispatch over all lanes at fixed width.
        Requests that reach ``max_new`` are evicted — their slot is FREE
        for the next step's admissions — and surface via :meth:`poll`.

        Weight-plane reads are DEMAND-DRIVEN: each admission prefills at
        the request's own tier (its demand floor), and the decode dispatch
        streams at the batch floor — the min live tier index
        (:func:`~repro.serve.scheduler.plane_demand`) — so a lo-tier-heavy
        batch reads a fraction of the weight bytes.  Demand is a static
        jit argument; at most one retrace per distinct tier.  The stream
        cost clock (:attr:`now`) advances by the step's summed dispatch
        read fractions — cheaper tiers genuinely buy back clock time.

        Each step records host spans on the profiler's clock, which cost
        about a microsecond each and record nothing unless a profiler
        session runs: ``serve.step`` around it, then ``serve.admit`` per
        admission, ``serve.decode`` (or ``serve.draft`` /
        ``serve.verify`` for a speculative round), each with
        ``.dispatch`` and ``.sync`` children; every wait for the device
        is a span whose name ends in ``.sync``."""
        s = self._ensure_session()
        with StepTraceAnnotation("serve.step", step_num=s.step_idx,
                                 queued=len(s.sched.queue)):
            return self._step(s)

    def _step(self, s: _Session) -> StepInfo:
        admitted: list[int] = []
        finished: list[int] = []
        timed_out: list[int] = []
        cost = 0.0
        for req in s.sched.expire_queued(s.step_idx, s.now):
            timed_out.append(req.rid)
        for slot in s.sched.expired_decoding(s.now):
            req = s.sched.release(slot, s.step_idx, s.now,
                                  FinishReason.TIMED_OUT)
            s.active[slot] = 0  # dead lane: a data change, never a retrace
            timed_out.append(req.rid)
        for slot, req in s.sched.admissible():
            demand = self._tier_index(req.quality)
            with TraceAnnotation("serve.admit", rid=req.rid, slot=slot,
                                 tier=demand, prompt_len=len(req.tokens)):
                s.sched.activate(slot, req, s.step_idx, now=s.now)
                s.tiers[slot] = demand
                admitted.append(req.rid)
                toks = np.zeros((1, s.prefill_len), np.int32)
                toks[0, s.prefill_len - len(req.tokens):] = req.tokens
                # one dispatch: prefill + lane insert + on-device argmax;
                # the host syncs on a single int32, not a (vocab,) logits
                # row.  The prefill runs at the REQUEST's tier (per-row
                # plane masks) and streams only the planes it demands.
                with TraceAnnotation("serve.admit.dispatch"):
                    s.cache, first = self._admit(
                        self.params, s.zero_slot_cache, s.cache,
                        jnp.asarray(toks),
                        jnp.asarray([len(req.tokens)], jnp.int32),
                        jnp.int32(slot), jnp.asarray(s.tiers[slot:slot + 1]),
                        demand,
                    )
                r, f = self._forward_plane_words(demand)
                s.plane_words_read += r
                s.plane_words_full += f
                s.tokens_emitted += 1
                cost += self._dispatch_cost(demand)
                with TraceAnnotation("serve.admit.sync"):
                    first = int(first)
                s.sched.start_decoding(slot)
                s.cur[slot, 0] = first
                if s.sched.record(slot, first, s.step_idx, now=s.now):
                    s.sched.evict(slot)  # max_new == 1: done at admission
                    finished.append(req.rid)
                else:
                    s.active[slot] = 1
        live = s.sched.decoding_slots()
        demand_used: int | None = None
        drafted_n = accepted_n = 0
        # speculating slots this round: slot -> (k_eff, draft tier index).
        # k is clamped so a round never drafts past max_new (the verify
        # bonus token is the +1), and drafting is a no-op for requests
        # whose serving tier was downgraded to (or below) the draft tier.
        spec: dict[int, tuple[int, int]] = {}
        for slot in live:
            req = s.sched.slot_req[slot]
            if req.speculate is None:
                continue
            didx = self.tier_names.index(req.speculate.draft_tier)
            if didx <= int(s.tiers[slot]):
                continue
            k_eff = min(req.speculate.k, req.max_new - len(req.out) - 1)
            if k_eff >= 1:
                spec[slot] = (k_eff, didx)
        if spec:
            demand_used, rcost, drafted_n, accepted_n = self._spec_round(
                s, spec, finished)
            cost += rcost
        elif live:
            demand = plane_demand(s.tiers[slot] for slot in live)
            demand_used = demand
            with TraceAnnotation("serve.decode", live=len(live),
                                 demand=demand):
                with TraceAnnotation("serve.decode.dispatch"):
                    nxt, s.cache = self._cont_step(
                        self.params, s.cache, jnp.asarray(s.cur),
                        jnp.asarray(s.active), jnp.asarray(s.tiers), demand,
                    )
                r, f = self._forward_plane_words(demand)
                s.plane_words_read += r
                s.plane_words_full += f
                s.tokens_emitted += len(live)
                cost += self._dispatch_cost(demand)
                with TraceAnnotation("serve.decode.sync"):
                    nxt = np.asarray(nxt)  # the decode's one host sync
                with TraceAnnotation("serve.decode.record"):
                    for slot in live:
                        s.cur[slot, 0] = nxt[slot]
                        rid = s.sched.slot_req[slot].rid
                        if s.sched.record(slot, int(nxt[slot]), s.step_idx,
                                          now=s.now):
                            s.sched.evict(slot)
                            s.active[slot] = 0
                            finished.append(rid)
        s.step_idx += 1
        s.now += cost
        return StepInfo(admitted=tuple(admitted), finished=tuple(finished),
                        timed_out=tuple(timed_out), live=len(live),
                        demand=demand_used, cost=cost,
                        drafted=drafted_n, accepted=accepted_n)

    def _spec_round(self, s: _Session, spec: dict[int, tuple[int, int]],
                    finished: list[int]) -> tuple[int, float, int, int]:
        """One self-speculative draft/verify round over the live lanes.

        DRAFT: k ticks of the same jitted decode program plain serving
        uses — no new trace — with the speculating lanes' tier entries
        temporarily set to their draft tier, so the batch demand floor
        streams only the draft planes.  Non-speculating live lanes decode
        normally inside the same dispatches (per-row plane masks keep
        them exact) and their tokens are recorded each tick; drafted
        tokens are buffered host-side and the draft-tier KV they write is
        scratch.  Lanes whose k_eff is shorter than the round's go
        draft-inactive early — a mask flip.

        VERIFY: ONE batched dispatch at the lanes' serving tiers scores
        every window position, overwriting the scratch KV in place, and
        accepts each lane's longest agreeing prefix on device.  The lane
        emits its accepted drafts plus the verify pass's bonus token —
        always >= 1 token, every one exactly what plain serving-tier
        decode would have produced — and rejected entries cost one
        per-slot ``pos`` rollback (a data change inside the verify
        program; no retrace anywhere in the round).

        The cost clock is charged honestly: each draft tick advances it
        by the draft demand floor's read fraction, the verify by ONE
        serving-tier dispatch — not k — so deadlines and SLO admission
        stay denominated in actual weight reads.

        Returns (verify demand, round cost, drafted, accepted)."""
        k_round = max(k for k, _ in spec.values())
        # pos invariant: every live lane has prefill_len + emitted - 1
        # cache entries (admission leaves pos at the prefill width with
        # one token emitted; every emitted token since advanced it by 1)
        start = {slot: s.prefill_len + len(s.sched.slot_req[slot].out) - 1
                 for slot in spec}
        anchor = {slot: int(s.cur[slot, 0]) for slot in spec}
        drafts: dict[int, list[int]] = {slot: [] for slot in spec}
        cost = 0.0
        for j in range(k_round):
            draft_active = s.active.copy()
            draft_tiers = s.tiers.copy()
            for slot, (k_eff, didx) in spec.items():
                draft_active[slot] = 1 if j < k_eff else 0
                draft_tiers[slot] = didx
            live_now = [slot for slot in range(s.sched.n_slots)
                        if draft_active[slot]]
            if not live_now:
                break  # every non-spec lane finished and k_effs exhausted
            demand = plane_demand(int(draft_tiers[slot])
                                  for slot in live_now)
            with TraceAnnotation("serve.draft", live=len(live_now),
                                 demand=demand):
                with TraceAnnotation("serve.draft.dispatch"):
                    nxt, s.cache = self._cont_step(
                        self.params, s.cache, jnp.asarray(s.cur),
                        jnp.asarray(draft_active), jnp.asarray(draft_tiers),
                        demand,
                    )
                r, f = self._forward_plane_words(demand)
                s.plane_words_read += r
                s.plane_words_full += f
                cost += self._dispatch_cost(demand)
                with TraceAnnotation("serve.draft.sync"):
                    nxt = np.asarray(nxt)
                for slot in live_now:
                    s.cur[slot, 0] = int(nxt[slot])
                    if slot in spec:
                        drafts[slot].append(int(nxt[slot]))  # proposed only
                    else:
                        s.tokens_emitted += 1
                        rid = s.sched.slot_req[slot].rid
                        if s.sched.record(slot, int(nxt[slot]), s.step_idx,
                                          now=s.now):
                            s.sched.evict(slot)
                            s.active[slot] = 0
                            finished.append(rid)
        vdemand = plane_demand(int(s.tiers[slot]) for slot in spec)
        with TraceAnnotation("serve.verify", lanes=len(spec), demand=vdemand):
            w = k_round + 1
            window = np.zeros((s.sched.n_slots, w), np.int32)
            wlen = np.zeros((s.sched.n_slots,), np.int32)
            smask = np.zeros((s.sched.n_slots,), np.int32)
            starts = np.zeros((s.sched.n_slots,), np.int32)
            for slot, (k_eff, _) in spec.items():
                window[slot, 0] = anchor[slot]
                window[slot, 1:1 + k_eff] = drafts[slot]
                wlen[slot] = k_eff + 1
                smask[slot] = 1
                starts[slot] = start[slot]
            with TraceAnnotation("serve.verify.dispatch"):
                toks, acc, s.cache = self._verify(
                    self.params, s.cache, jnp.asarray(window),
                    jnp.asarray(starts), jnp.asarray(wlen),
                    jnp.asarray(smask), jnp.asarray(s.tiers), vdemand,
                )
            r, f = self._forward_plane_words(vdemand)
            s.plane_words_read += r
            s.plane_words_full += f
            cost += self._dispatch_cost(vdemand)
            with TraceAnnotation("serve.verify.sync"):
                toks = np.asarray(toks)
                acc = np.asarray(acc)  # the round's final host sync
            drafted_n = accepted_n = 0
            for slot, (k_eff, _) in spec.items():
                a = int(acc[slot])
                req = s.sched.slot_req[slot]
                req.drafted += k_eff
                req.accepted += a
                drafted_n += k_eff
                accepted_n += a
                s.cur[slot, 0] = int(toks[slot, a])  # bonus token: new cur
                s.tokens_emitted += a + 1
                rid = req.rid
                done = False
                for tok in toks[slot, :a + 1]:
                    done = s.sched.record(slot, int(tok), s.step_idx,
                                          now=s.now)
                if done:  # a+1 <= remaining: only the last token can finish
                    s.sched.evict(slot)
                    s.active[slot] = 0
                    finished.append(rid)
        s.drafted += drafted_n
        s.accepted += accepted_n
        return vdemand, cost, drafted_n, accepted_n

    def poll(self, rid: int | None = None):
        """Structured request status (see
        :class:`~repro.serve.scheduler.RequestStatus`).

        ``poll(rid)`` -> that request's status, an IDEMPOTENT read for any
        issued rid: ``.state`` says where it is
        (queued/prefilling/decoding/done), ``.finish_reason`` how it ended
        (``None`` means keep stepping), ``.tokens`` the emitted ids once
        terminal — partial for TIMED_OUT/CANCELLED, empty for
        SHED/REJECTED.  ``poll()`` -> {rid: status} for every request that
        TERMINATED since the last bare poll, handed out once (claimed
        results stay readable via ``poll(rid)`` /
        :attr:`completed_requests`).  Unknown rids raise KeyError."""
        if self._session is None:
            if rid is None:
                return {}
            raise KeyError(f"unknown request id {rid} (no active stream)")
        return self._session.sched.poll(rid)

    # -- stream introspection (the public view of the session state) -------
    @property
    def has_work(self) -> bool:
        """True while the stream has queued, prefilling or decoding
        requests."""
        return self._session is not None and self._session.sched.has_work

    @property
    def step_count(self) -> int:
        """Number of step() iterations the current stream has run."""
        return 0 if self._session is None else self._session.step_idx

    @property
    def now(self) -> float:
        """The stream cost clock: cumulative dispatch weight-read
        fractions (a full-quality dispatch = 1.0).  Deadlines and
        admission SLO budgets are denominated in this unit."""
        return 0.0 if self._session is None else self._session.now

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot (admission queue length)."""
        return 0 if self._session is None else len(self._session.sched.queue)

    def advance_clock(self, dt: float) -> float:
        """Advance the stream cost clock by ``dt`` without dispatching —
        models idle wall-time between arrivals and injected slow ticks
        (fault harness), so deadlines keep aging while the engine waits.
        Returns the new :attr:`now`."""
        if dt < 0:
            raise ValueError(f"cannot rewind the cost clock (dt={dt})")
        s = self._ensure_session()
        s.now += float(dt)
        return s.now

    @property
    def completed_requests(self) -> dict[int, Request]:
        """Every finished Request of the current stream (rid -> Request,
        with arrival/admitted/finished step indices for latency stats);
        unlike poll(), repeated reads see the same map."""
        return {} if self._session is None else dict(self._session.sched.completed)

    @property
    def live_requests(self) -> list[Request]:
        """Requests currently occupying slots (PREFILLING/DECODING)."""
        if self._session is None:
            return []
        return [r for r in self._session.sched.slot_req if r is not None]

    def reset_stream(self) -> None:
        """Drop the continuous stream unconditionally — queued and live
        requests are abandoned, the next submit() starts a fresh session."""
        self._session = None

    def run_until_drained(self, max_ticks: int | None = None):
        """step() until the queue and every slot are empty; returns
        everything :meth:`poll` would (statuses of requests that
        terminated since the last poll, keyed by request id).

        ``max_ticks`` is a WATCHDOG, not a deadline: every step with work
        emits at least one token (admissions emit their first token in
        the same step), so a drain can never legitimately exceed the
        outstanding token count — the default bound is twice that plus
        slack, and overrunning it raises RuntimeError instead of spinning
        forever on a stuck stream."""
        s = self._ensure_session()
        if max_ticks is None:
            outstanding = sum(r.max_new for r in s.sched.queue)
            outstanding += sum(max(r.max_new - len(r.out), 1)
                               for r in s.sched.slot_req if r is not None)
            max_ticks = 2 * outstanding + s.sched.n_slots + 16
        n = 0
        while s.sched.has_work:
            if n >= max_ticks:
                raise RuntimeError(
                    f"run_until_drained watchdog: stream not drained after "
                    f"{n} ticks ({len(s.sched.queue)} queued, "
                    f"{len(s.sched.decoding_slots())} decoding); every tick "
                    f"should retire tokens — this stream is stuck"
                )
            self.step()
            n += 1
        return self.poll()

    # -- generation ----------------------------------------------------------
    def generate(self, prompts: Sequence[Sequence[int]], max_new: int = 32,
                 seed: int = 0, qualities=None):
        """Decode a batch of token-id prompts.  Returns lists of ids.

        Greedy attention-family engines route through the continuous
        scheduler (submit all, drain) — a pure wrapper, token-identical to
        the static program for dense families.  Sampling engines
        (``cfg.temperature > 0``), recurrent/cross families, and
        ``cfg.continuous=False`` take the static two-program path:
        one-dispatch prefill + one decode scan, sampling from
        ``softmax(logits / temperature)`` with a PRNG derived from
        ``seed`` (same seed + prompts => same tokens).

        ``qualities`` (per-request engines, continuous path only) assigns
        each prompt its own tier: a name applied to all, or one name per
        prompt — the whole mixed-tier batch shares the one decode dispatch.
        """
        if len(prompts) == 0:
            return []
        if any(len(p) == 0 for p in prompts):
            raise ValueError("every prompt must contain at least one token")
        b = len(prompts)
        slots = self.cfg.batch_slots
        if b > slots:
            raise ValueError(
                f"{b} prompts exceed the engine's {slots} batch_slots; "
                f"raise ServeConfig.batch_slots, split the batch, or "
                f"submit() to the continuous stream (which queues)"
            )
        if max_new < 1:
            # legacy contract on every path: zero-length decode is a no-op
            return [[] for _ in prompts]
        if isinstance(qualities, str):
            qualities = [qualities] * b
        if qualities is not None and len(qualities) != b:
            raise ValueError(
                f"{len(qualities)} qualities for {b} prompts; pass one tier "
                f"name per prompt (or a single name for all)"
            )
        if (self.cfg.continuous and self.cfg.temperature == 0
                and self._continuous_capable()):
            return self._generate_continuous(prompts, max_new, qualities)
        if qualities is not None:
            raise ValueError(
                "per-request qualities need the continuous scheduler path "
                "(greedy attention family, ServeConfig(continuous=True)); "
                "use set_quality() to dial this engine as a whole"
            )
        return self._generate_static(prompts, max_new, seed)

    def _generate_continuous(self, prompts, max_new: int, qualities=None):
        """Submit-all/drain on a throwaway session sized to this batch
        (prefill width = longest prompt, cache = prompt + max_new), so the
        traced shapes match the call exactly like the static path's.  The
        throwaway session is UNBOUNDED (no max_queue): the batch API has
        no arrival stream to shed."""
        maxp = max(len(p) for p in prompts)
        saved = self._session
        self._session = _Session(
            self.model, self.cfg.batch_slots,
            prefill_len=maxp, cache_len=maxp + max_new + 1,
        )
        try:
            rids = [self.submit(p, max_new=max_new,
                                quality=None if qualities is None else qualities[i])
                    for i, p in enumerate(prompts)]
            done = self.run_until_drained()
            return [done[r].tokens for r in rids]
        finally:
            self._session = saved

    def _generate_static(self, prompts, max_new: int, seed: int):
        """The one-static-batch path: every slot prefills and decodes in
        lockstep, and the whole batch drains before the call returns."""
        b = len(prompts)
        slots = self.cfg.batch_slots
        maxp = max(len(p) for p in prompts)
        cache_len = maxp + max_new + 1

        cache = init_params(
            jax.random.PRNGKey(0), self.model.cache_descs(slots, cache_len)
        )
        toks = np.zeros((slots, maxp), dtype=np.int32)
        lens = np.zeros((slots,), dtype=np.int32)
        for i, p in enumerate(prompts):
            toks[i, maxp - len(p):] = p  # left-pad
            lens[i] = len(p)
        # one jitted dispatch primes the cache for the whole prompt batch
        # (lens masks each slot's left padding out of the KV cache)...
        cache, logits = self._prefill(
            self.params, cache, jnp.asarray(toks), jnp.asarray(lens)
        )
        temp = self.cfg.temperature
        # ...and one jitted scan emits all max_new tokens; the np.asarray
        # below is the only host sync of the generation.
        if temp > 0:
            if self._sample_loop is None:
                self._sample_loop = jax.jit(make_sample_decode_loop(self.model))
            k_first, k_loop = jax.random.split(jax.random.PRNGKey(seed))
            first = jax.random.categorical(
                k_first, logits / temp, axis=-1
            ).astype(jnp.int32)[:, None]
            out_toks, _ = self._sample_loop(
                self.params, cache, first, jax.random.split(k_loop, max_new),
                jnp.float32(temp),
            )
        else:
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
            out_toks, _ = self._decode_loop(
                self.params, cache, first, jnp.arange(max_new)
            )
        out = np.asarray(out_toks)  # (max_new, slots)
        return [out[:, i].tolist() for i in range(b)]
