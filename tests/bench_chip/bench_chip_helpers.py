"""A tiny copy of the chip benchmark for tests on the CPU.

``tiny_root(tmp)`` copies ``benchmarks/chip`` into ``tmp`` and adds a
two-layer llama configuration with grouped-query attention, a closed and
an open traffic mix and a ``BENCHMARK.json`` naming both cells, so a test
drives the real harness on the CPU (Pallas kernels interpreted) in
seconds.  ``add_toy_architecture(root)`` adds to such a copy, as new files
only, an architecture of its own: a reference that routes one leaf, its
program adapter, a configuration and a cell.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_CONFIG = {
    "source": "test", "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "arch": "deepseek_7b", "reference": "llama",
    "serve": {"batch_slots": 4, "max_len": 48, "max_prompt": 16},
    "quant": json.loads((ROOT / "benchmarks/chip/configs/deepseek-7b.json")
                        .read_text())["quant"],
    # on the CPU at this size, over 96 served tokens: the program's widest
    # gap read 0 to 0.0122 on seeds 1-6, the float8 control's 0.114 to 0.287
    "check": {"min_tokens": 96, "max_gap": 0.04},
}
CLOSED = {"why": "test", "loop": "closed", "clients": 3,
          "prompt_len": {"law": "lognormal", "median": 8, "sigma": 0.5,
                         "min": 2, "max": 16},
          "output_len": {"law": "lognormal", "median": 6, "sigma": 0.5,
                         "min": 2, "max": 12},
          "tiers": {"hi": 1, "mid": 1, "lo": 1}, "block": 16, "base_seed": 1,
          "lead_s": 0.5}
OPEN = dict(CLOSED, loop="open", rate_per_s=6.0,
            arrival={"law": "gamma", "cv": 2.0})


def tiny_root(tmp: Path) -> Path:
    here = tmp / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (here / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (here / "traffic" / "tiny-closed.json").write_text(json.dumps(CLOSED))
    (here / "traffic" / "tiny-open.json").write_text(json.dumps(OPEN))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "benchmarks/chip/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny-closed",
         "chips": 1, "why": "test"},
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny-open",
         "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


# the llama reference with its MLP down projection routed to experts
TOY_REFERENCE = """from benchmarks.chip.references.llama import *  # noqa: F403

ROUTED = {"blocks/mlp/wd"}
"""
TOY_ADAPTER = "from benchmarks.chip.programs.llama import arch_config  # noqa: F401\n"
TOY_CONFIG = dict(TINY_CONFIG, reference="toy")


def add_toy_architecture(root: Path) -> None:
    """Adds ``references/toy.py``, ``programs/toy.py``,
    ``configs/toy.json`` and the cell ``toy.closed`` to a tiny copy."""
    here = root / "benchmarks" / "chip"
    (here / "references" / "toy.py").write_text(TOY_REFERENCE)
    (here / "programs" / "toy.py").write_text(TOY_ADAPTER)
    (here / "configs" / "toy.json").write_text(json.dumps(TOY_CONFIG))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "test",
                             "file": "benchmarks/chip/configs/toy.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy.closed", "config": "toy",
                               "traffic": "tiny-closed", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
