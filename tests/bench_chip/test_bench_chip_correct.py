"""The chip benchmark's ``correct``, on the CPU at a tiny size: the two
tiny cells run through the real harness and prove correct, and a run whose
served path is broken underneath comes out not correct, once for each way
a served token can go wrong."""
from __future__ import annotations

import time

import pytest
from bench_chip_helpers import TINY_CONFIG, add_toy_architecture, tiny_root

from benchmarks.chip import cells, program
from benchmarks.chip import run as bench_run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def _cell(root, name):
    return cells.load(name, root, root / "benchmarks" / "chip")


@pytest.mark.parametrize("name", ["tiny.closed", "tiny.open"])
def test_tiny_cell_runs_correct(root, name):
    r = bench_run.run_cell(_cell(root, name), 2**31 + 11, 1.5, False,
                           t_start=time.perf_counter())
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert r["checks"]["max_gap"]["value"] <= TINY_CONFIG["check"]["max_gap"]
    assert r["_notes"]["compiled_in_window"] == 0
    assert list(r)[-2:] == ["checks", "_notes"]


@pytest.mark.parametrize("name, adapter", [("tiny.closed", "llama"),
                                           ("toy.closed", "toy")])
def test_a_traced_cell_runs_correct_through_its_adapter(tmp_path, name, adapter):
    """A traced tiny run builds the program through the adapter its
    reference names (the moved llama adapter, or one added as a file),
    proves correct and reads the engine's own spans."""
    root = tiny_root(tmp_path)
    add_toy_architecture(root)
    here = root / "benchmarks" / "chip"
    cell = _cell(root, name)
    assert cells.adapter(cell.config, here).__file__ == str(
        here / "programs" / f"{adapter}.py")
    r = bench_run.run_cell(cell, 2**31 + 13, 1.5, True,
                           t_start=time.perf_counter())
    assert r["correct"], r["checks"]
    assert r["_notes"]["compiled_in_window"] == 0
    assert 0 < r["metrics"]["step_host_ms"]["value"] < 1e3


def _alter_decoded(eng):
    step = eng._cont_step

    def altered(*args):
        nxt, cache = step(*args)
        return (nxt + 1) % TINY_CONFIG["vocab_size"], cache
    eng._cont_step = altered


def _alter_first(eng):
    admit = eng._admit

    def altered(*args):
        cache, first = admit(*args)
        return cache, (first + 1) % TINY_CONFIG["vocab_size"]
    eng._admit = altered


def _serve_lo(eng):
    submit = eng.submit

    def lowered(prompt, max_new, quality=None, **kw):
        return submit(prompt, max_new, quality="lo", **kw)
    eng.submit = lowered


@pytest.mark.parametrize("fault", [_alter_decoded, _alter_first, _serve_lo],
                         ids=["decoded-token", "first-token", "wrong-tier"])
def test_a_broken_served_path_is_not_correct(root, monkeypatch, fault):
    build = program.build

    def broken(*args, **kw):
        eng = build(*args, **kw)
        fault(eng)
        return eng
    monkeypatch.setattr(program, "build", broken)
    r = bench_run.run_cell(_cell(root, "tiny.closed"), 5, 1.0, False,
                           t_start=time.perf_counter())
    assert not r["correct"]
    assert r["checks"]["max_gap"]["value"] > TINY_CONFIG["check"]["max_gap"]
