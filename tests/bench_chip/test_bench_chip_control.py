"""The control of the chip benchmark's ``correct``, on the CPU at a tiny
size: the plain reference with every matmul in float8 (the precision below
the configuration's bfloat16), put in the program's place, reads a wider
served-token gap than the limit on three seeds, while the program on the
same seeds stays under it."""
from __future__ import annotations

import pytest
from bench_chip_helpers import TINY_CONFIG, tiny_root

from benchmarks.chip import cells, control


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = tiny_root(tmp_path_factory.mktemp("bench"))
    return cells.load("tiny.closed", root, root / "benchmarks" / "chip")


def test_float8_control_fails_the_limit_on_three_seeds(cell):
    for seed in (3, 2**32 + 4, 77):
        got = control.readings(cell, seed, 1.0)
        assert got["program_gap"] <= TINY_CONFIG["check"]["max_gap"], got
        assert got["control_gap"] > TINY_CONFIG["check"]["max_gap"], got
