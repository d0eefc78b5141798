"""The calibration reads the program as the harness does: its program
readings, through one compiled round for several seeds, give the numbers
that the harness's own run of each seed compares."""

from bench import calibrate
from bench.spec import Cell
from bench.tests.conftest import make_checkout
from bench.tests.test_cells import run_cell, smoke_limits


def test_calibration_reads_the_program_as_the_harness_does(tmp_path,
                                                           no_gate):
    root = make_checkout(str(tmp_path / "co"), {
        "smoke-stc": ("smoke-lm.json", "smoke-stc.json", 1, smoke_limits())})
    cell = Cell(root, "smoke-stc")
    step = None
    for seed in (7, 3000000029):
        line, step = calibrate.calibrate(cell, seed, program=True,
                                         controls=False, step=step)
        assert "control_fp8" not in line
        res = run_cell(no_gate, root, "smoke-stc", seed)
        assert line["program"] == {k: c["value"]
                                   for k, c in res["checks"].items()}
        assert line["prog_losses"] != line["ref_losses"]
