"""Host spans of ``ServeEngine.step()`` on the profiler's clock.

Each step records ``serve.step``; each admission a ``serve.admit`` with
``.dispatch`` and ``.sync`` children; the decode a ``serve.decode`` with
``.dispatch``, ``.sync`` and ``.record`` children.  The spans are read
back from a real profiler session (``host_spans`` in ``conftest.py``),
and serving under the profiler emits the same tokens as without it.
"""
import jax
import pytest

from repro.configs import get_arch
from repro.models import Model
from repro.models.base import init_params
from repro.serve import ServeConfig, ServeEngine

# (prompt, max_new): three requests on two slots, so one queues, and one
# finishes at admission (max_new 1)
REQUESTS = [([5, 9, 2, 7], 4), ([3, 1], 1), ([8, 6, 4], 3)]


@pytest.fixture(scope="module")
def engine():
    cfg = get_arch("deepseek_7b", smoke=True)
    model = Model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_descs())
    return ServeEngine(model, params, ServeConfig(batch_slots=2, max_len=32,
                                                  max_prompt=8))


def _serve(eng):
    """Serve REQUESTS to the end; returns (StepInfo per step, tokens)."""
    eng.reset_stream()
    rids = [eng.submit(p, max_new=n) for p, n in REQUESTS]
    infos = []
    while eng.has_work:
        infos.append(eng.step())
    return infos, [eng.poll(r).tokens for r in rids]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_one_step_span_per_step_and_one_admit_span_per_admission(
        engine, host_spans):
    with host_spans() as spans:
        infos, _ = _serve(engine)
    steps = _named(spans, "serve.step")
    assert [s[3]["step_num"] for s in steps] == list(range(len(infos)))
    assert steps[0][3]["queued"] == len(REQUESTS)
    admits = _named(spans, "serve.admit")
    admitted = [rid for info in infos for rid in info.admitted]
    assert [a[3]["rid"] for a in admits] == admitted
    assert [a[3]["prompt_len"] for a in admits] == [len(p) for p, _ in REQUESTS]
    for a in admits:
        assert {"slot", "tier"} <= a[3].keys()
        assert any(_inside(a, s) for s in steps)
        for child in ("serve.admit.dispatch", "serve.admit.sync"):
            assert sum(_inside(c, a) for c in _named(spans, child)) == 1
    assert len(_named(spans, "serve.admit.sync")) == len(admitted)


def test_decode_children_nest_in_decode(engine, host_spans):
    with host_spans() as spans:
        infos, _ = _serve(engine)
    decodes = _named(spans, "serve.decode")
    assert [d[3]["live"] for d in decodes] == [i.live for i in infos if i.live]
    assert [d[3]["demand"] for d in decodes] == [i.demand for i in infos
                                                 if i.live]
    steps = _named(spans, "serve.step")
    for d in decodes:
        assert sum(_inside(d, s) for s in steps) == 1
        kids = [c for c in spans if c[0].startswith("serve.decode.")
                and _inside(c, d)]
        assert [c[0] for c in kids] == ["serve.decode.dispatch",
                                        "serve.decode.sync",
                                        "serve.decode.record"]
    assert len([s for s in spans if s[0].startswith("serve.decode.")]) \
        == 3 * len(decodes)


def test_tokens_same_with_and_without_a_profiler_session(engine, host_spans):
    _, plain = _serve(engine)
    with host_spans() as spans:
        _, traced = _serve(engine)
    assert spans and traced == plain
    assert [len(t) for t in plain] == [n for _, n in REQUESTS]
