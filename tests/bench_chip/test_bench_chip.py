"""The chip benchmark's yardstick, on the CPU: the seeded load generator,
the metric arithmetic, the lookup of cells by name, the work counts, the
peaks table, the trace reduction, the lossless grid weights and the
refusal to run without a TPU."""
from __future__ import annotations

import copy
import dataclasses
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest
from bench_chip_helpers import (CLOSED, OPEN, ROOT, TINY_CONFIG,
                                add_toy_architecture, tiny_root)

from benchmarks.chip import cells, loadgen, peaks, trace, work
from benchmarks.chip.record import check_token_steps, token_steps

DEEPSEEK_MIXED = json.loads(
    (ROOT / "benchmarks/chip/traffic/decode-mixed.json").read_text())
BURSTY = json.loads((ROOT / "benchmarks/chip/traffic/chat-bursty.json").read_text())


# -- load generator ---------------------------------------------------------
def test_same_seed_same_stream_and_other_seed_same_sizes():
    a = loadgen.stream(DEEPSEEK_MIXED, 2**31 + 7, 128, 102400)
    b = loadgen.stream(DEEPSEEK_MIXED, 2**31 + 7, 128, 102400)
    c = loadgen.stream(DEEPSEEK_MIXED, 99, 128, 102400)
    assert a == b
    assert a != c
    sizes = sorted((len(r.prompt), r.max_new, r.tier) for r in a)
    assert sizes == sorted((len(r.prompt), r.max_new, r.tier) for r in c)


@pytest.mark.parametrize("traffic", [DEEPSEEK_MIXED, BURSTY],
                         ids=["decode-mixed", "chat-bursty"])
def test_lengths_and_tiers_in_range(traffic):
    reqs = loadgen.stream(traffic, 5, 4 * traffic["block"], 1000)
    p = np.asarray([len(r.prompt) for r in reqs])
    o = np.asarray([r.max_new for r in reqs])
    assert p.min() >= traffic["prompt_len"]["min"]
    assert p.max() <= traffic["prompt_len"]["max"]
    assert o.min() >= traffic["output_len"]["min"]
    assert o.max() <= traffic["output_len"]["max"]
    assert abs(np.median(p) - traffic["prompt_len"]["median"]) <= 0.25 * traffic[
        "prompt_len"]["median"]
    shares = {t: sum(r.tier == t for r in reqs) / len(reqs)
              for t in traffic["tiers"]}
    want = {t: w / sum(traffic["tiers"].values())
            for t, w in traffic["tiers"].items()}
    assert all(abs(shares[t] - want[t]) <= 0.02 for t in want)
    assert all(0 <= x < 1000 for r in reqs for x in r.prompt)


def test_gamma_arrivals_have_cv_2_and_fill_the_window():
    traffic = dict(BURSTY, rate_per_s=2000.0)
    sched = loadgen.open_schedule(traffic, 3, 50.0, 100)
    due = np.asarray([r.offset_s for r in sched])
    gaps = np.diff(due)
    assert len(sched) == 100_000
    assert np.all(gaps >= 0) and due[0] == 0.0 and due[-1] < 50.0
    assert abs(gaps.std() / gaps.mean() - 2.0) < 0.15
    assert abs(gaps.mean() - 1 / 2000.0) < 0.02 / 2000.0


def test_open_schedule_same_seed_same_times():
    a = loadgen.open_schedule(BURSTY, 11, 30.0, 49152)
    assert a == loadgen.open_schedule(BURSTY, 11, 30.0, 49152)
    assert len(a) == round(BURSTY["rate_per_s"] * 30.0)


# -- metric arithmetic ----------------------------------------------------
def _synthetic(stall_s: float = 0.0) -> dict:
    """10 ms steps; a request of 8 tokens is due every 20 ms and admitted
    by the first step that starts after it is due.  Steps 20 to 23 each
    last ``stall_s`` longer: enough of the gaps between tokens for a 95th
    percentile to see."""
    steps, t = [], 0.0
    for i in range(80):
        dt = 0.010 + (stall_s if 20 <= i < 24 else 0.0)
        steps.append({"t0": t, "t1": t + dt, "admitted": [], "live": 4,
                      "demand": 0})
        t += dt
    requests = {}
    for rid in range(30):
        due = 0.02 * rid
        a = next(i for i, s in enumerate(steps) if s["t0"] >= due)
        steps[a]["admitted"].append(rid)
        requests[rid] = {"due": due, "prompt_len": 10, "max_new": 8,
                         "tier": "hi", "admitted_step": a,
                         "finished_step": a + 6, "n_tokens": 8, "done": True}
    return {"window": [0.0, steps[70]["t1"]], "steps": steps,
            "requests": requests, "batch_slots": 4, "setup_s": 1.0}


@pytest.mark.parametrize("name, worse", [
    ("tokens_per_s", lambda a, b: b < a),
    ("ttft_p95_ms", lambda a, b: b > a),
    ("itl_p95_ms", lambda a, b: b > a),
])
def test_a_stall_in_the_window_moves_each_end_to_end_metric(name, worse):
    read = cells.reader(name)
    calm, stalled = read(_synthetic()), read(_synthetic(stall_s=0.5))
    assert worse(calm, stalled), (calm, stalled)


def test_synthetic_record_places_tokens_by_step():
    rec = _synthetic()
    assert token_steps(rec["requests"][1]) == [2, 2, 3, 4, 5, 6, 7, 8]
    assert check_token_steps(rec) == []
    rec["requests"][3]["finished_step"] += 1
    assert len(check_token_steps(rec)) == 1


# -- cells found by name --------------------------------------------------
def test_files_added_to_a_copy_are_found_by_name(tmp_path):
    root = tiny_root(tmp_path)
    here = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    (here / "configs" / "other.json").write_text(json.dumps(
        dict(TINY_CONFIG, hidden_size=96)))
    (here / "traffic" / "burst-3.json").write_text(json.dumps(
        dict(OPEN, rate_per_s=3.0)))
    (here / "metrics" / "steps_per_s.py").write_text(
        "def read(rec):\n    lo, hi = rec['window']\n"
        "    return len(rec['steps']) / (hi - lo)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "other", "source": "test",
                             "file": "benchmarks/chip/configs/other.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "other.burst-3", "config": "other",
                               "traffic": "burst-3", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "steps_per_s", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "engine step", "moves": "tokens_per_s",
                               "workloads": ["other.burst-3"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.load("other.burst-3", root, here)
    assert cell.config["hidden_size"] == 96
    assert cell.traffic["rate_per_s"] == 3.0
    assert "steps_per_s" in [m["name"] for m in cell.per_layer]
    assert "steps_per_s" not in [m["name"] for m in
                                 cells.load("tiny.open", root, here).per_layer]
    assert cells.reader("steps_per_s", here)(_synthetic()) > 0
    assert cells.load("tiny.closed", root, here).traffic == CLOSED
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there changed


# a reader for each thing an architecture's own reader can find in a record
TOY_READERS = {
    "toy_expert_ms": "1e3 * rec['trace']['op_s']['toy_expert']",
    "toy_steps": "sum(n == 'serve.step' for n, _, _ in rec['trace']['spans'])",
    "toy_sync_idle_ms": "1e3 * rec['trace']['idle_by_span']['serve.decode.sync']",
    "toy_drafted": "sum(s['drafted'] for s in rec['steps'])",
    "toy_expert_width": "rec['config']['model']['intermediate_size']",
}


def test_an_architecture_added_to_a_copy_is_found_by_name(tmp_path):
    """A new architecture is new files: its reference (with a routed leaf),
    its program adapter, a configuration, a cell and readers of the
    record's device ops, program spans, idle by span, step fields and
    configuration.  Every file that was there stays as it was."""
    from benchmarks.chip import program
    from benchmarks.chip.record import work_config

    root = tiny_root(tmp_path)
    here = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    add_toy_architecture(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for name, expr in TOY_READERS.items():
        (here / "metrics" / f"{name}.py").write_text(
            f"def read(rec):\n    return {expr}\n")
        bench["per_layer"].append({
            "name": name, "unit": "1", "better": "higher",
            "source": "device_trace", "layer": "experts",
            "moves": "itl_p95_ms", "workloads": ["toy.closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load("toy.closed", root, here)
    ref = cells.reference(cell.config, here)
    assert ref.__file__ == str(here / "references" / "toy.py")
    assert ref.ROUTED == {"blocks/mlp/wd"}
    assert cells.adapter(cell.config, here).__file__ == str(here / "programs" / "toy.py")
    assert program.arch_config(cell.config, here).d_model == TINY_CONFIG["hidden_size"]
    assert set(TOY_READERS) <= {m["name"] for m in cell.per_layer}
    assert not set(TOY_READERS) & {m["name"] for m in
                                   cells.load("tiny.closed", root, here).per_layer}

    rec = _synthetic()
    for s in rec["steps"]:
        s["drafted"] = 2
    rec["config"] = work_config(cell.config, ref)
    assert "blocks/mlp/wd" in rec["config"]["routed_shapes"]
    assert "blocks/mlp/wd" not in rec["config"]["packed_shapes"]
    ev = _events()
    ev["spans"] += SERVE_SPANS
    ev["ops"].append(("%toy_expert.3 = f32[8] custom-call(...)", 1400, 1450))
    rec["trace"] = trace.reduce(ev, n_window_steps=2)
    got = {name: cells.reader(name, here)(rec) for name in TOY_READERS}
    assert got == pytest.approx({"toy_expert_ms": 50e-6, "toy_steps": 2,
                                 "toy_sync_idle_ms": 550e-6, "toy_drafted": 160,
                                 "toy_expert_width": 128})
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there changed


def test_a_missing_adapter_names_the_file_to_add(tmp_path):
    here = tiny_root(tmp_path) / "benchmarks" / "chip"
    with pytest.raises(FileNotFoundError, match="benchmarks/chip/programs/moe.py"):
        cells.adapter({"reference": "moe"}, here)


# ArchConfig fields that program.arch_config gave before the adapters
ARCH_BEFORE_ADAPTERS = {
    "deepseek-7b": dict(name="deepseek-7b", n_layers=6, d_model=4096,
                        n_heads=32, n_kv=32, d_ff=11008, vocab=102400,
                        source="arXiv:2401.02954; hf"),
    "smollm-135m": dict(name="smollm-135m", n_layers=30, d_model=576,
                        n_heads=9, n_kv=3, d_ff=1536, vocab=49152,
                        source="hf:HuggingFaceTB/SmolLM-135M; hf"),
}
ARCH_SHARED = dict(family="dense", head_dim=0, qk_norm=False, window=None,
                   rope_theta=10000.0, moe=None, ssm_state=0, ssm_head_dim=64,
                   ssm_groups=1, ssm_chunk=256, hybrid=None, enc_layers=0,
                   enc_seq=1500, cross_every=0, vision_tokens=1024,
                   dtype=jnp.bfloat16, remat=True)


@pytest.mark.parametrize("name", sorted(ARCH_BEFORE_ADAPTERS))
def test_llama_adapter_gives_the_architecture_as_before(name):
    from benchmarks.chip import program
    from benchmarks.chip.programs import llama

    cfg = json.loads((ROOT / f"benchmarks/chip/configs/{name}.json").read_text())
    arch = llama.arch_config(cfg)
    assert dataclasses.asdict(arch) == dict(ARCH_SHARED, **ARCH_BEFORE_ADAPTERS[name])
    assert program.arch_config(cfg) == arch
    with pytest.raises(ValueError, match="not a plain dense decoder"):
        llama.arch_config(dict(cfg, arch="mamba2_1_3b"))


def test_a_step_record_keeps_every_field_of_the_engines_step_info():
    """Each step record holds every ``StepInfo`` field, tuples as lists,
    a field that a later program adds among them."""
    from benchmarks.chip.driver import Driver
    from repro.serve.engine import StepInfo

    @dataclasses.dataclass(frozen=True)
    class Later(StepInfo):
        routed_rows: tuple[int, ...] = ()

    info = Later(admitted=(7,), finished=(), timed_out=(3,), live=3, demand=1,
                 cost=0.5, drafted=2, accepted=1, routed_rows=(4, 0, 2))
    drv = Driver(SimpleNamespace(step=lambda: info), CLOSED, 1, 256)
    drv.requests[7] = {"admitted_step": None, "finished_step": None}
    drv.step()
    (s,) = drv.steps
    assert set(s) == {"t0", "t1"} | {f.name for f in dataclasses.fields(Later)}
    assert {k: s[k] for k in ("admitted", "timed_out", "live", "demand", "cost",
                              "routed_rows")} == {
        "admitted": [7], "timed_out": [3], "live": 3, "demand": 1, "cost": 0.5,
        "routed_rows": [4, 0, 2]}
    assert drv.requests[7]["admitted_step"] == 0


def test_a_per_layer_metric_without_a_list_follows_what_it_moves(tmp_path):
    """A per-layer metric with no ``workloads`` list is reported in every
    cell that reports the end-to-end metric it moves, and in no other."""
    root = tiny_root(tmp_path)
    here = root / "benchmarks" / "chip"
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"]:
        if m["name"] == "tokens_per_s":
            m["workloads"] = ["tiny.closed"]
    bench["per_layer"].append({"name": "slot_occupancy.copy", "unit": "%",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "tokens_per_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    closed, opened = (cells.load(c, root, here) for c in ("tiny.closed",
                                                          "tiny.open"))
    assert "tokens_per_s" not in [m["name"] for m in opened.end_to_end]
    assert "slot_occupancy.copy" in [m["name"] for m in closed.per_layer]
    assert "slot_occupancy.copy" not in [m["name"] for m in opened.per_layer]
    assert "itl_p95_ms" in [m["moves"] for m in opened.per_layer]


def test_every_cell_of_the_benchmark_loads():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = cells.load(w["name"])
        for m in cell.end_to_end + cell.per_layer:
            assert callable(cells.reader(m["name"]))
        assert cells.reference(cell.config).leaf_specs(cell.config)


# -- no chip, no result ---------------------------------------------------
def test_the_command_refuses_to_run_without_a_tpu():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload",
         bench["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0
    assert '"metrics"' not in out.stdout
    assert "TPU" in out.stderr


# -- work counts, peaks ---------------------------------------------------
def test_packed_gemv_work_of_one_smollm_leaf_by_hand():
    # SmolLM-135M wg: K 576, N 1536, group 16, 8 decoding rows
    k, n, rows = 576, 1536, 8
    flops, nbytes = work.packed_call(k, n, rows, 16, planes=3)
    assert flops == 2 * 8 * 576 * 1536
    planes = 3 * (576 // 32) * 1536 * 4    # 3 planes of 18 int32 words
    scales = (576 // 16) * 1536 * 4        # 36 f32 scales per column
    assert nbytes == planes + scales + 8 * 576 * 2 + 8 * 1536 * 2
    # at lo the leaf drops one plane: two planes stream
    _, lo = work.packed_call(k, n, rows, 16, planes=2)
    assert nbytes - lo == (576 // 32) * 1536 * 4
    # a leaf that mid drops and hi keeps: a dispatch at floor 1 skips a
    # plane, one at floor 0 does not
    assert work.demand_drop((0, 1, 1), 0) == 0
    assert work.demand_drop((0, 1, 1), 1) == 1
    assert work.demand_drop((0, 0, 1), 1) == 0


def test_gemv_roofline_by_hand_on_a_synthetic_deepseek_record():
    """Decode dispatches of 4 live hi lanes stream all 3 planes of every
    packed weight; each admission streams the head once for its last
    position.  A kernel time of twice the least time reads 50%."""
    from benchmarks.chip.record import window_steps, work_config
    from benchmarks.chip.references import llama

    cfg = json.loads((ROOT / "benchmarks/chip/configs/deepseek-7b.json").read_text())
    rec = _synthetic()
    rec["config"] = work_config(cfg, llama)
    rec["peaks"] = peaks.peak("TPU v5 lite")
    shapes = llama.matmul_shapes(cfg)
    per_dispatch = sum(n * (k * m * 3 / 8 + k // 16 * m * 4 + 4 * k * 2 + 4 * m * 2)
                       for p, (n, k, m) in shapes.items() if p in cfg["quant"]["packed"])
    _, k, v = shapes["embed/head"]
    per_admission = k * v * 3 / 8 + k // 16 * v * 4 + k * 2 + v * 2
    steps = window_steps(rec)
    admissions = sum(len(s["admitted"]) for s in steps)
    least = (len(steps) * per_dispatch + admissions * per_admission) / 819e9
    rec["trace"] = {"gemv_s": 2 * least}
    assert cells.reader("gemv_roofline")(rec) == pytest.approx(50.0)


def test_a_routed_leaf_leaves_the_gemv_count():
    """A leaf the reference lists in ``ROUTED`` is read by the rows routed
    to it, not by every live row: the dense GEMV count leaves it out, by
    hand as in the test above, and ``routed_shapes`` gives it instead."""
    from benchmarks.chip.record import window_steps, work_config
    from benchmarks.chip.references import llama

    cfg = json.loads((ROOT / "benchmarks/chip/configs/deepseek-7b.json").read_text())
    routed = SimpleNamespace(
        ROUTED={"blocks/mlp/wd"}, HEAD=llama.HEAD,
        matmul_shapes=llama.matmul_shapes,
        attention_flops_per_token=llama.attention_flops_per_token)
    rec = _synthetic()
    rec["config"] = work_config(cfg, routed)
    rec["peaks"] = peaks.peak("TPU v5 lite")
    shapes = llama.matmul_shapes(cfg)
    assert rec["config"]["matmul_shapes"] == shapes
    assert rec["config"]["routed_shapes"] == {"blocks/mlp/wd": shapes["blocks/mlp/wd"]}
    assert "blocks/mlp/wd" not in rec["config"]["packed_shapes"]
    assert "blocks/mlp/wd" not in rec["config"]["tier_vectors"]
    assert rec["config"]["model"] is cfg
    dense = set(cfg["quant"]["packed"]) - {"blocks/mlp/wd"}
    per_dispatch = sum(n * (k * m * 3 / 8 + k // 16 * m * 4 + 4 * k * 2 + 4 * m * 2)
                       for p, (n, k, m) in shapes.items() if p in dense)
    _, k, v = shapes["embed/head"]
    per_admission = k * v * 3 / 8 + k // 16 * v * 4 + k * 2 + v * 2
    steps = window_steps(rec)
    admissions = sum(len(s["admitted"]) for s in steps)
    least = (len(steps) * per_dispatch + admissions * per_admission) / 819e9
    rec["trace"] = {"gemv_s": 2 * least}
    assert cells.reader("gemv_roofline")(rec) == pytest.approx(50.0)
    assert work_config(cfg, llama)["packed_shapes"].keys() == set(cfg["quant"]["packed"])


def test_every_per_layer_reader_reads_a_traced_record():
    """Each per-layer metric of the benchmark reads a number from a traced
    record of a v5e run, and a share of a roofline or peak stays in
    (0, 100]."""
    from benchmarks.chip.record import work_config
    from benchmarks.chip.references import llama

    cfg = json.loads((ROOT / "benchmarks/chip/configs/deepseek-7b.json").read_text())
    rec = _synthetic()
    rec["config"] = work_config(cfg, llama)
    rec["peaks"] = peaks.peak("TPU v5 lite")
    rec["trace"] = {"window_s": 0.7, "busy_s": 0.5, "gemv_s": 0.5,
                    "gemm_s": 0.05, "admit_s": 0.1, "decode_s": 0.5,
                    "spans": [("serve.step", 0.0, 0.004),
                              ("serve.decode.sync", 0.001, 0.003)]}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        v = cells.reader(m["name"])(rec)
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%" and ("roofline" in m["name"] or "mfu" in m["name"]):
            assert v <= 100.0, (m["name"], v)


def test_a_tier_plan_other_than_the_configurations_is_a_fault():
    """The reference serves the drops the configuration states; an engine
    whose artifact ranks the leaves otherwise serves another mid tier."""
    from types import SimpleNamespace

    from benchmarks.chip import program

    q = TINY_CONFIG["quant"]
    plan = work.tier_vectors(q["drops"], q["tiers"], q["packed"])
    plan = {p: v for p, v in plan.items() if any(v)}

    def engine(vectors):
        return SimpleNamespace(artifact=SimpleNamespace(
            tier_drop_vectors=lambda: vectors))
    assert program.tier_plan_faults(engine(plan), q) == []
    swapped = dict(plan, **{"blocks/attn/wq": (0, 1, 1),
                            "blocks/mlp/wd": (0, 0, 1)})
    faults = program.tier_plan_faults(engine(swapped), q)
    assert [f.split(":")[0] for f in faults] == ["blocks/attn/wq",
                                                 "blocks/mlp/wd"]


def test_least_time_takes_the_larger_bound_per_call():
    t, comp, mem = work.least_time([(197e12, 1.0), (1.0, 819e9)], 197e12, 819e9)
    assert t == pytest.approx(2.0) and comp == pytest.approx(1.0)
    assert mem == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("TPU v9 imaginary")


# -- trace reduction ------------------------------------------------------
def _events() -> dict:
    return {
        "spans": [("bench.submit", 0, 100), ("bench.step", 100, 1100),
                  ("bench.step", 1200, 2200), ("bench.wait", 2200, 3000),
                  ("bench.step", 3000, 4000)],
        "ops": [("%while.3 = (s32[]) while(...)", 150, 750),  # holds the next two
                ("%qsq_matvec_masked.43 = f32[8,49152] custom-call(...)", 200, 500),
                ("%fusion.1 = f32[8] fusion(...)", 400, 700),
                ("%qsq_matmul_masked.7 = f32[64,576] custom-call(...)", 1300, 2000),
                ("%fusion.2 = f32[8] fusion(...)", 3100, 3900)],  # after the window
        "modules": [("jit_admit(3)", 1250, 2100), ("jit_cont_step", 150, 800)],
    }


# the program's spans inside the first two steps, and a step after the window
SERVE_SPANS = [("serve.step", 110, 1090), ("serve.decode", 120, 1080),
               ("serve.decode.dispatch", 120, 160),
               ("serve.decode.sync", 160, 1070),
               ("serve.step", 1210, 2190), ("serve.admit", 1220, 2150),
               ("serve.admit.sync", 1250, 2095), ("serve.step", 3010, 3990)]


def test_trace_reduction_on_synthetic_events():
    ev = _events()
    r = trace.reduce(ev, n_window_steps=2)
    assert r["window_s"] == pytest.approx(2200e-9)
    assert r["busy_s"] == pytest.approx((750 - 150 + 2000 - 1300) * 1e-9)
    assert r["gemv_s"] == pytest.approx(300e-9)
    assert r["gemm_s"] == pytest.approx(700e-9)
    assert r["admit_s"] == pytest.approx(850e-9)
    idle = dict(r["idle_gaps"])
    # a gap goes to the innermost span around its middle
    assert idle["bench.submit"] == pytest.approx(150e-9)
    assert idle["bench.step"] == pytest.approx((550 + 200) * 1e-9)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert [n for n, _ in r["device_ops"]] == ["qsq_matmul_masked",
                                                "qsq_matvec_masked", "fusion"]


def test_trace_reduction_lists_the_programs_spans_beside_the_old_keys():
    """The program's spans change none of the old keys; they are listed in
    the window, and each idle gap goes to the innermost span of either."""
    old = trace.reduce(_events(), n_window_steps=2)
    ev = _events()
    ev["spans"] = SERVE_SPANS + ev["spans"]
    r = trace.reduce(ev, n_window_steps=2)
    program_keys = {"spans", "idle_by_span"}
    assert {k: v for k, v in r.items() if k not in program_keys} == {
        k: v for k, v in old.items() if k not in program_keys}
    assert old["spans"] == [] and old["idle_by_span"] == dict(old["idle_gaps"])
    assert [s[0] for s in r["spans"]] == [n for n, _, _ in SERVE_SPANS[:-1]]
    assert r["spans"][0] == ("serve.step", pytest.approx(110e-9),
                             pytest.approx(1090e-9))
    assert r["idle_by_span"] == pytest.approx({
        "bench.submit": 150e-9, "serve.decode.sync": 550e-9,
        "serve.admit": 200e-9})
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # one sync per step: (980 - 910) and (980 - 845) ns, median 102.5 ns
    assert cells.reader("step_host_ms")({"trace": r}) == pytest.approx(102.5e-6)


def test_device_ops_are_the_top_ten_of_every_op():
    ev = _events()
    ev["ops"] = [(f"%op{i}.{i} = f32[8] fusion(...)", 100 * i, 100 * i + 10 + i)
                 for i in range(12)]
    ev["ops"].append(("%while.1 = (s32[]) while(...)", 0, 1500))
    r = trace.reduce(ev, n_window_steps=2)
    assert len(r["op_s"]) == 12 and "while" not in r["op_s"]
    assert r["op_s"]["op0"] == pytest.approx(10e-9)
    top = sorted(r["op_s"].items(), key=lambda kv: -kv[1])[:trace.TOP]
    assert r["device_ops"] == [[n, v] for n, v in top]
    assert sum(r["idle_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_merge_unions_overlapping_intervals():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


# -- lossless weights -----------------------------------------------------
def test_grid_weights_survive_compression_exactly():
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import program, weights
    from benchmarks.chip.references import llama
    from repro import api
    from repro.models.api import Model

    cfg = copy.deepcopy(TINY_CONFIG)
    specs = llama.leaf_specs(cfg)
    params = weights.draw_tree(2**33 + 5, specs)
    art = api.compress(Model(program.arch_config(cfg)), params)
    dense = art.dense_params("hi", like=params)
    for a, b in zip(jax.tree_util.tree_leaves(dense),
                    jax.tree_util.tree_leaves(params), strict=True):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert {t: sorted(art.drop_map(t)) for t in cfg["quant"]["tiers"]} == {
        t: sorted(cfg["quant"]["drops"][t]) for t in cfg["quant"]["tiers"]}
    # a tier's truncation on the program's side is the reference's draw
    lo = art.dense_params("lo", like=params)
    want = weights.draw_leaf(2**33 + 5, "blocks/mlp/wg",
                             specs["blocks/mlp/wg"], drop=1)
    np.testing.assert_array_equal(np.asarray(lo["blocks"]["mlp"]["wg"], np.float32),
                                  np.asarray(want, jnp.float32))
