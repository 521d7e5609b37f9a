"""Self-speculative decoding: draft cheap, verify exact, roll back free.

The tentpole contract under test —

* a speculating request's tokens are IDENTICAL to plain decode at its
  serving tier — fuzzed over mixed speculating/non-speculating batches,
  mid-stream admissions and evictions, draft windows clamped by
  ``max_new``, and every acceptance boundary (full rejection, partial
  prefix, full window) — because the verify dispatch overwrites the
  draft-tier KV and the per-slot ``pos`` rollback masks rejected entries;
* the whole draft/verify round is retrace-free: drafting reuses the one
  continuous-decode program, the verify program traces once per
  (demand, window width) pair, and a warmed stream replays under
  ``no_retrace`` across all of it;
* the cost clock stays honest: draft ticks charge the draft demand
  floor's read fraction, a verify dispatch charges ONE serving-tier
  dispatch (never k), so SLO admission sees real weight reads;
* ``poll()`` surfaces per-request ``drafted``/``accepted`` counters, and
  guaranteed-useless speculation configs die at submit as typed
  ``SubmitRejected`` errors.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.configs.base import ArchConfig
from repro.models.api import Model
from repro.models.base import init_params
from repro.quant.artifact import QualitySpec, QualityTier
from repro.serve import SpecConfig, SubmitRejected

SPEC_TIERS = QualitySpec((
    QualityTier("hi", drop_planes=0, drop_frac=0.0),
    QualityTier("mid", drop_planes=1, drop_frac=1.0),
    QualityTier("lo", drop_planes=2, drop_frac=1.0),
))

# a ladder whose "echo" tier drops NOTHING: drafting there is bit-identical
# to hi, so every draft is accepted — the deterministic full-window
# (a == k) boundary
ECHO_TIERS = QualitySpec((
    QualityTier("hi", drop_planes=0, drop_frac=0.0),
    QualityTier("echo", drop_planes=0, drop_frac=0.0),
))


def _build_artifact(tiers):
    cfg = ArchConfig(name="smollm-like", family="dense", n_layers=2,
                     d_model=64, n_heads=4, n_kv=2, d_ff=128, vocab=256,
                     dtype=jnp.float32, remat=False)
    model = Model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_descs())
    return api.compress(model, params, tiers=tiers)


@pytest.fixture(scope="module")
def spec_artifact():
    return _build_artifact(SPEC_TIERS)


@pytest.fixture(scope="module")
def echo_artifact():
    return _build_artifact(ECHO_TIERS)


def _oracle(art, requests):
    """Plain solo decode of each request at its own tier — the token
    ground truth speculation must reproduce exactly."""
    engines = {}
    out = []
    for prompt, quality, max_new, _ in requests:
        if quality not in engines:
            engines[quality] = art.engine(quality=quality, batch_slots=1,
                                          max_prompt=8, max_len=32)
        out.append(engines[quality].generate([prompt], max_new=max_new)[0])
    return out


def _fuzz_requests(seed):
    """A deterministic mixed stream: speculating and plain requests at
    several tiers, draft windows larger than some budgets allow."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(6):
        prompt = rng.integers(1, 255, size=int(rng.integers(2, 7))).tolist()
        max_new = int(rng.integers(2, 8))
        roll = i % 3
        if roll == 0:
            quality, spec = "hi", SpecConfig("lo", k=int(rng.integers(1, 6)))
        elif roll == 1:
            quality, spec = "mid", SpecConfig("lo", k=int(rng.integers(1, 6)))
        else:
            quality, spec = rng.choice(["hi", "mid"]), None
        reqs.append((prompt, str(quality), max_new, spec))
    return reqs


def _run_stream(eng, requests):
    eng.reset_stream()
    rids = [eng.submit(p, max_new=m, quality=q, speculate=s)
            for p, q, m, s in requests]
    done = eng.run_until_drained()
    return [done[r].tokens for r in rids]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_spec_token_identity_fuzz(spec_artifact, no_retrace, seed):
    """Speculative streams are token-identical to plain solo decode at
    each request's own tier, across mixed spec/plain batches with queueing
    (6 requests on 2 slots: mid-stream admits and evicts), and a warmed
    identical replay never retraces the decode/admit/verify programs."""
    art = spec_artifact
    requests = _fuzz_requests(seed)
    expect = _oracle(art, requests)
    eng = art.engine(quality="hi", batch_slots=2, max_prompt=8, max_len=32)
    assert _run_stream(eng, requests) == expect  # warm every trace
    with no_retrace(eng._cont_step, eng._admit, eng._verify):
        assert _run_stream(eng, requests) == expect
    stats = eng.stream_stats()
    assert stats["drafted"] > 0
    assert 0 <= stats["accepted"] <= stats["drafted"]


def test_spec_full_window_acceptance(echo_artifact):
    """Drafting at a tier that drops nothing is bit-identical to hi, so
    every round accepts its whole window (the a == k rollback boundary)
    and the acceptance rate is exactly 1.0."""
    art = echo_artifact
    requests = [([7, 7, 7], "hi", 9, SpecConfig("echo", k=3)),
                ([5, 2], "hi", 7, SpecConfig("echo", k=2))]
    expect = _oracle(art, requests)
    eng = art.engine(quality="hi", batch_slots=2, max_prompt=8, max_len=32)
    assert _run_stream(eng, requests) == expect
    stats = eng.stream_stats()
    assert stats["drafted"] > 0
    assert stats["acceptance_rate"] == 1.0


def test_spec_k_clamped_by_remaining_budget(spec_artifact):
    """k larger than the remaining max_new budget clamps the draft window
    (never drafts past the last token); max_new == 2 leaves no room to
    draft at all and serves as plain decode."""
    art = spec_artifact
    requests = [([3, 1, 4], "hi", 2, SpecConfig("lo", k=5)),
                ([1, 5, 9], "hi", 4, SpecConfig("lo", k=5))]
    expect = _oracle(art, requests)
    eng = art.engine(quality="hi", batch_slots=2, max_prompt=8, max_len=32)
    rids = [eng.submit(p, max_new=m, quality=q, speculate=s)
            for p, q, m, s in requests]
    done = eng.run_until_drained()
    assert [done[r].tokens for r in rids] == expect
    assert done[rids[0]].drafted == 0          # no room: 1 + k > max_new
    assert 0 < done[rids[1]].drafted <= 3      # clamped below k=5
    assert len(done[rids[0]].tokens) == 2
    assert len(done[rids[1]].tokens) == 4


def test_spec_mid_stream_cancel_keeps_survivors_exact(spec_artifact):
    """Cancelling a speculating request mid-stream (active-mask flip) does
    not perturb the batch mates' tokens."""
    art = spec_artifact
    keep = ([2, 4, 6], "hi", 6, SpecConfig("lo", k=2))
    expect = _oracle(art, [keep])[0]
    eng = art.engine(quality="hi", batch_slots=2, max_prompt=8, max_len=32)
    r_keep = eng.submit(keep[0], max_new=keep[2], quality=keep[1],
                        speculate=keep[3])
    r_dead = eng.submit([9, 9], max_new=6, quality="hi",
                        speculate=SpecConfig("mid", k=3))
    eng.step()  # both admitted and one round in flight
    st = eng.cancel(r_dead)
    assert st.finish_reason is not None
    done = eng.run_until_drained()
    assert done[r_keep].tokens == expect


def test_spec_status_counters_surface_via_poll(spec_artifact):
    art = spec_artifact
    eng = art.engine(quality="hi", batch_slots=1, max_prompt=8, max_len=32)
    rid = eng.submit([1, 2, 3], max_new=6, speculate=SpecConfig("lo", k=2))
    eng.step()
    live = eng.poll(rid)  # mid-flight reads see live draft counters
    assert live.drafted >= 0 and live.accepted <= live.drafted
    done = eng.run_until_drained()[rid]
    assert len(done.tokens) == 6
    assert done.drafted > 0
    assert 0 <= done.accepted <= done.drafted


def test_spec_cost_clock_charges_verify_as_one_tick(spec_artifact):
    """Satellite-6 honesty: one admission step with a lone speculating
    slot costs exactly prefill(hi) + k_eff x draft(lo) + ONE verify(hi)
    on the cost clock — a verify dispatch is never charged k."""
    art = spec_artifact
    eng = art.engine(quality="hi", batch_slots=1, max_prompt=8, max_len=32)
    costs = eng.tier_cost_table()  # per-tier dispatch read fractions
    rid = eng.submit([1, 2, 3], max_new=8, speculate=SpecConfig("lo", k=3))
    info = eng.step()
    assert info.drafted == 3
    lo = eng.tier_names.index("lo")
    expect = costs[0] + 3 * costs[lo] + costs[0]
    assert info.cost == pytest.approx(expect, rel=1e-9)
    assert costs[lo] < costs[0]  # the draft tier is genuinely cheaper
    eng.run_until_drained()
    assert eng.poll(rid).n_tokens == 8


def test_spec_round_spans(spec_artifact, host_spans):
    """A speculative round of k drafts records k ``serve.draft`` spans and
    one ``serve.verify``, each with one ``.dispatch`` and one ``.sync``
    child, inside the step's ``serve.step`` — and no plain decode."""
    art = spec_artifact
    eng = art.engine(quality="hi", batch_slots=1, max_prompt=8, max_len=32)
    eng.submit([1, 2, 3], max_new=8, speculate=SpecConfig("lo", k=3))
    with host_spans() as spans:
        info = eng.step()
    assert info.drafted == 3
    names = [sp[0] for sp in spans]
    assert names.count("serve.step") == 1
    assert names.count("serve.admit") == 1
    assert names.count("serve.draft") == 3
    assert names.count("serve.verify") == 1
    assert not any(n.startswith("serve.decode") for n in names)
    step = spans[names.index("serve.step")]
    for parent in (sp for sp in spans if sp[0] in ("serve.draft",
                                                   "serve.verify")):
        assert step[1] <= parent[1] and parent[2] <= step[2]
        kids = [sp[0] for sp in spans if sp[0].startswith(parent[0] + ".")
                and parent[1] <= sp[1] and sp[2] <= parent[2]]
        assert kids == [parent[0] + ".dispatch", parent[0] + ".sync"]
    lo = eng.tier_names.index("lo")
    assert [sp[3]["demand"] for sp in spans if sp[0] == "serve.draft"] \
        == [lo] * 3


def test_spec_submit_validation(spec_artifact):
    art = spec_artifact
    eng = art.engine(quality="hi", batch_slots=1, max_prompt=8, max_len=32)
    with pytest.raises(SubmitRejected):
        eng.submit([1], speculate=SpecConfig("lo", k=0))
    with pytest.raises(SubmitRejected):
        eng.submit([1], speculate=SpecConfig("nope", k=2))
    with pytest.raises(SubmitRejected):  # draft not BELOW the serving tier
        eng.submit([1], quality="lo", speculate=SpecConfig("lo", k=2))
    with pytest.raises(SubmitRejected):
        eng.submit([1], quality="mid", speculate=SpecConfig("mid", k=2))
    single = art.engine(quality="hi", per_request=False, batch_slots=1,
                        max_prompt=8, max_len=32)
    with pytest.raises(SubmitRejected):
        single.submit([1], speculate=SpecConfig("lo", k=2))
