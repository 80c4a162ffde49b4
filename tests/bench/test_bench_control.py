"""The control fails the comparison that decides ``correct``: the
reference put in the program's place at the next precision below the
configuration's (fp8 operands under a bf16 model, three bf16 passes
under fp32 at HIGHEST) reads above the cell's limit, while the program
reads below it.  Tiny cells on a CPU; ``bench/control.py`` makes the
same readings on the chip at the cells' own sizes."""

import json

import pytest

from bench import control
from tinycells import CELLS, LIMITS, jax_config_restored, make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_fails_where_the_program_passes(root, cell, capsys):
    with jax_config_restored():
        assert control.main(["--workload", cell, "--seeds", "11,2147483659",
                             "--seconds", "1"], root=root) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert len(lines) == 2
    for rec in lines:
        for name, lim in LIMITS[cell].items():
            assert rec["program"][name] <= lim["limit"], rec
        assert any(rec["control_readings"][name] > lim["limit"]
                   for name, lim in LIMITS[cell].items()), rec
