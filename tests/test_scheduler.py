"""Continuous-batching scheduler invariants.

The contract under test: requests join a RUNNING decode without flushing
or perturbing batch mates.  Concretely —

* ``generate()`` through the scheduler is token-identical to the static
  two-program path for the same prompt set (dense family);
* a prompt admitted mid-decode produces exactly the tokens it produces
  served alone, and does not change the tokens of the slot it joined
  (extends the PR 3 batch-isolation guarantee across TIME);
* evicting a finished request and re-admitting into the same slot is
  clean — the lane insert replaces the whole lane;
* ``step()`` traces its programs once: admissions and evictions are mask
  flips, not shape changes (asserted via the kernel dispatch counters,
  which count packed-matmul routing at TRACE time only).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import Model
from repro.models.base import init_params
from repro.serve import ServeConfig, ServeEngine
from repro.serve.scheduler import FinishReason, Scheduler, SlotState


@pytest.fixture(scope="module")
def dense_setup():
    cfg = get_arch("deepseek_7b", smoke=True)
    model = Model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_descs())
    static = ServeEngine(model, params,
                         ServeConfig(batch_slots=4, continuous=False))
    return model, params, static


def _solo(static, prompt, max_new):
    return static.generate([prompt], max_new=max_new)[0]


# --------------------------------------------------------------------------
# Host-side state machine
# --------------------------------------------------------------------------
def test_scheduler_state_machine():
    s = Scheduler(2)
    assert not s.has_work
    r0 = s.submit([1, 2], max_new=2, arrival=0)
    r1 = s.submit([3], max_new=1, arrival=0)
    r2 = s.submit([4], max_new=1, arrival=1)  # queued: no third slot
    pairs = list(s.admissible())
    assert [slot for slot, _ in pairs] == [0, 1]
    assert [req.rid for _, req in pairs] == [r0, r1]
    assert len(s.queue) == 1  # r2 still queued
    for slot, req in pairs:
        s.activate(slot, req, step=0)
        assert s.states[slot] is SlotState.PREFILLING
        s.start_decoding(slot)
        assert s.states[slot] is SlotState.DECODING
    assert s.record(1, 7, step=0)  # r1: max_new=1 -> done immediately
    assert s.states[1] is SlotState.DONE
    done = s.evict(1)
    assert done.rid == r1 and s.states[1] is SlotState.FREE
    assert done.waiting == 0 and done.latency == 0
    # freed slot now admits the queued request
    assert [slot for slot, _ in s.admissible()] == [1]
    assert not s.record(0, 5, step=1)  # r0: 1 of 2 tokens
    assert s.record(0, 6, step=2)
    s.evict(0)
    assert s.completed[r0].out == [5, 6]
    assert s.completed[r0].latency == 2
    assert r2 not in s.completed


def test_scheduler_poll_structured_status():
    s = Scheduler(1)
    rid = s.submit([1], max_new=1, arrival=0)
    st = s.poll(rid)
    assert st.state == "queued" and st.finish_reason is None
    assert st.tokens is None  # non-terminal: no token hand-out yet
    slot, req = next(s.admissible())
    s.activate(slot, req, step=0)
    s.start_decoding(slot)
    s.record(slot, 9, step=0)
    s.evict(slot)
    st = s.poll(rid)
    assert st.state == "done" and st.finish_reason is FinishReason.DONE
    assert st.tokens == [9] and st.ok and st.done
    assert s.poll(rid).tokens == [9]  # per-rid polls are idempotent
    with pytest.raises(KeyError, match="unknown"):
        s.poll(rid + 1)  # never issued
    # the bare poll pops newly-terminal statuses exactly once
    batch = s.poll()
    assert batch[rid].tokens == [9]
    assert s.poll() == {}
    assert s.completed[rid].out == [9]  # stats survive the claim


def test_scheduler_submit_validation():
    s = Scheduler(1)
    with pytest.raises(ValueError, match="at least one token"):
        s.submit([], max_new=4, arrival=0)
    with pytest.raises(ValueError, match="max_new"):
        s.submit([1], max_new=0, arrival=0)
    with pytest.raises(ValueError, match="slot"):
        Scheduler(0)


# --------------------------------------------------------------------------
# Engine integration: exactness across scheduling decisions
# --------------------------------------------------------------------------
def test_continuous_generate_matches_static(dense_setup):
    """generate() is a submit-all/drain wrapper: token-identical to the
    static one-batch path for the same prompt set."""
    model, params, static = dense_setup
    cont = ServeEngine(model, params, ServeConfig(batch_slots=4))
    for prompts, max_new in [
        ([[1, 2, 3]], 6),
        ([[1, 2, 3], [9, 9], [100, 42, 7, 8]], 8),
        ([[5], [5, 6, 7, 8, 9, 10]], 5),
    ]:
        assert cont.generate(prompts, max_new=max_new) == \
            static.generate(prompts, max_new=max_new)
    # zero-length decode stays a no-op on every path (legacy contract)
    assert cont.generate([[1, 2]], max_new=0) == [[]]
    assert static.generate([[1, 2]], max_new=0) == [[]]


def test_midstream_admission_exact_and_isolated(dense_setup):
    """A prompt admitted MID-DECODE yields exactly its solo tokens, and the
    slot it joined keeps exactly the tokens it was already producing."""
    model, params, static = dense_setup
    eng = ServeEngine(model, params,
                      ServeConfig(batch_slots=2, max_prompt=8, max_len=32))
    r1 = eng.submit([1, 2, 3], max_new=10)
    for _ in range(4):
        eng.step()  # r1 is several tokens deep
    r2 = eng.submit([9, 9], max_new=6)  # joins the running decode
    out = eng.run_until_drained()
    assert out[r1].tokens == _solo(static, [1, 2, 3], 10)
    assert out[r2].tokens == _solo(static, [9, 9], 6)


def test_evict_readmit_reuses_slot(dense_setup):
    """One slot, three queued requests: each admission reuses the lane the
    previous request vacated, and every result matches its solo run."""
    model, params, static = dense_setup
    eng = ServeEngine(model, params,
                      ServeConfig(batch_slots=1, max_prompt=8, max_len=24))
    prompts = [[1, 2, 3], [9, 9], [100, 42, 7]]
    rids = [eng.submit(p, max_new=4) for p in prompts]
    out = eng.run_until_drained()
    sched = eng._session.sched
    assert sched.states == [SlotState.FREE]
    assert not sched.has_work
    for rid, p in zip(rids, prompts, strict=True):
        assert out[rid].tokens == _solo(static, p, 4)
    # the three admissions were strictly sequential through slot 0
    admits = sorted(sched.completed[r].admitted for r in rids)
    assert admits[0] < admits[1] < admits[2]


def test_poll_streams_results_incrementally(dense_setup):
    model, params, static = dense_setup
    eng = ServeEngine(model, params,
                      ServeConfig(batch_slots=2, max_prompt=8, max_len=24))
    r_short = eng.submit([4, 5], max_new=2)
    r_long = eng.submit([6, 7], max_new=8)
    seen = {}
    for _ in range(3):
        eng.step()
        seen.update(eng.poll())
    assert r_short in seen and r_long not in seen  # short one finished first
    assert seen[r_short].finish_reason is FinishReason.DONE
    live = eng.poll(r_long)  # structured: still decoding, keep stepping
    assert live.finish_reason is None and live.state == "decoding"
    assert live.n_tokens > 0 and live.tokens is None
    out = eng.run_until_drained()  # drains AND polls the remainder
    assert out[r_long].tokens == _solo(static, [6, 7], 8)
    assert r_long not in eng.poll()  # bare polls hand out once
    assert eng.poll(r_long).tokens == out[r_long].tokens  # per-rid: idempotent
    assert eng.completed_requests[r_long].out == out[r_long].tokens


def test_submit_rejects_unsupported(dense_setup):
    model, params, _ = dense_setup
    hot = ServeEngine(model, params,
                      ServeConfig(batch_slots=2, temperature=0.7))
    with pytest.raises(ValueError, match="greedy-only"):
        hot.submit([1, 2])
    eng = ServeEngine(model, params,
                      ServeConfig(batch_slots=2, max_prompt=4, max_len=16))
    with pytest.raises(ValueError, match="prefill window"):
        eng.submit([1, 2, 3, 4, 5])  # longer than max_prompt
    with pytest.raises(ValueError, match="max_len"):
        eng.submit([1, 2], max_new=100)


def test_recurrent_family_submit_rejected_generate_works():
    cfg = get_arch("mamba2_1_3b", smoke=True)
    model = Model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_descs())
    eng = ServeEngine(model, params, ServeConfig(batch_slots=2))
    with pytest.raises(ValueError, match="attention famil"):
        eng.submit([1, 2])
    assert len(eng.generate([[3, 1]], max_new=4)[0]) == 4  # static fallback


# --------------------------------------------------------------------------
# Trace stability: admissions/evictions are mask flips, not recompiles
# --------------------------------------------------------------------------
def test_step_traces_once_across_admissions(no_retrace):
    """After one admission + one decode step have traced the programs,
    further admissions, evictions and steps must not retrace: the packed
    dispatch counters (incremented ONLY at trace time) stay frozen."""
    from repro.configs.base import ArchConfig
    from repro.core.policy import QuantPolicy
    from repro.core.qsq import QSQConfig
    from repro.models import Model as M
    from repro.quant import pack_pytree_wire, quantize_pytree

    cfg = ArchConfig(name="smollm-like", family="dense", n_layers=2,
                     d_model=64, n_heads=4, n_kv=2, d_ff=128, vocab=256,
                     dtype=jnp.float32, remat=False)
    model = M(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_descs())
    wire = pack_pytree_wire(quantize_pytree(
        params,
        QuantPolicy(base=QSQConfig(group_size=16, refit_alpha=True),
                    min_numel=512),
        model.param_descs(),
    ))
    from repro.quant.artifact import EdgeArtifact

    eng = EdgeArtifact(wire=wire, arch_config=cfg).engine(
        quality="hi", batch_slots=2, max_prompt=8, max_len=24)
    assert eng.n_packed_leaves > 0

    # warmup: one admission traces prefill+insert, one step traces decode
    eng.submit([1, 2, 3], max_new=3)
    eng.step()
    with no_retrace(eng._cont_step, eng._admit):
        r2 = eng.submit([9, 9], max_new=4)       # admission into slot 1
        r3 = eng.submit([5, 6, 7, 8], max_new=2)  # queued, admitted post-evict
        out = eng.run_until_drained()
    assert len(out[r2].tokens) == 4 and len(out[r3].tokens) == 2
    # and the jitted programs each compiled exactly one specialization
    assert eng._cont_step._cache_size() == 1
    assert eng._admit._cache_size() == 1


# --------------------------------------------------------------------------
# MoE dead-lane routing: FREE/DONE slots drop out of expert competition
# --------------------------------------------------------------------------
def _moe_model():
    import dataclasses

    from repro.configs.base import MoEConfig

    cfg = get_arch("mixtral_8x22b", smoke=True)
    # tight capacity so expert competition actually bites at decode width
    cfg = dataclasses.replace(
        cfg, moe=MoEConfig(n_experts=4, top_k=2, capacity_factor=0.3),
        window=None)
    model = Model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_descs())
    return model, params


def test_moe_dead_lane_out_of_expert_competition():
    """layers.moe with an active mask: a dead lane's token must not change
    live lanes' outputs (it used to claim capacity slots like a live batch
    mate), and its own output must be zero.

    The dead lane 0 holds a copy of live lane 1's token, so it routes to
    the same top-k experts, and capacity is one token per expert
    (ceil(4 tokens * 2 / 4 experts * 0.3) = 1).  Routing ranks
    assignments in lane order, so an unmasked copy takes both of lane 1's
    capacity slots and lane 1 is dropped: competition is guaranteed by
    construction, not by the random init."""
    from repro.models import layers as L

    key = jax.random.PRNGKey(0)
    d, ff, e = 16, 32, 4
    p = init_params(key, L.moe_descs(d, ff, e))
    rng = np.random.RandomState(0)
    x1 = jnp.asarray(rng.randn(4, 1, d), jnp.float32)
    x1 = x1.at[0].set(x1[1])  # dead lane = copy of a live token
    x2 = x1.at[0].set(jnp.asarray(rng.randn(1, d), jnp.float32))
    active = jnp.asarray([0, 1, 1, 1], jnp.int32)
    live = np.array([1, 2, 3])
    y1 = np.asarray(L.moe(p, x1, top_k=2, capacity_factor=0.3,
                          active=active)[0])
    y2 = np.asarray(L.moe(p, x2, top_k=2, capacity_factor=0.3,
                          active=active)[0])
    np.testing.assert_array_equal(y1[live], y2[live])
    assert (y1[0] == 0).all()
    # masked: lane 1 is first in line for its experts and is served
    assert (y1[1] != 0).any()
    # unmasked: the copy claims lane 1's capacity and lane 1 is dropped —
    # the competition the mask removes
    z1 = np.asarray(L.moe(p, x1, top_k=2, capacity_factor=0.3)[0])
    assert (z1[1] == 0).all()
    assert not np.array_equal(z1[live], y1[live])


def test_moe_slot_history_invariance():
    """Mirror of the dense families' slot-history guarantee: an MoE
    request's tokens are invariant to DEAD lanes — a slot whose previous
    occupant finished leaves a frozen token that no longer competes for
    expert capacity."""
    model, params = _moe_model()
    scfg = ServeConfig(batch_slots=2, max_prompt=8, max_len=24)
    # history engine: a short request finishes first, freezing its last
    # token in the vacated lane while the probe request decodes
    hist = ServeEngine(model, params, scfg)
    r_warm = hist.submit([42, 17, 99], max_new=1)  # done at admission
    hist.step()
    assert hist.poll(r_warm).done
    r_probe = hist.submit([1, 2, 3], max_new=8)
    got = hist.run_until_drained()[r_probe].tokens
    # fresh engine: same probe, never-used second slot
    fresh = ServeEngine(model, params, scfg)
    r_solo = fresh.submit([1, 2, 3], max_new=8)
    want = fresh.run_until_drained()[r_solo].tokens
    assert got == want


def test_active_mask_freezes_dead_lanes(dense_setup):
    """A slot that finished early is a dead lane: its per-slot cache pos
    stops advancing while its batch mate keeps decoding."""
    model, params, _ = dense_setup
    eng = ServeEngine(model, params,
                      ServeConfig(batch_slots=2, max_prompt=8, max_len=32))
    eng.submit([1, 2, 3], max_new=2)   # finishes after one decode step
    eng.submit([9, 9], max_new=10)
    for _ in range(4):
        eng.step()
    pos = np.asarray(eng._session.cache.kv.pos)  # (L, B)
    assert (pos[:, 0] < pos[:, 1]).all()
    assert len({int(p) for p in pos[:, 0]}) == 1  # frozen since eviction
