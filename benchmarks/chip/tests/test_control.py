"""The control: the float32 reference in the program's place with every
reuse-site input rounded to int4 codes over the int8 clip range, the
precision below the int8 codes the configuration states. On three seeds it
reads far above what the program reads, and above the cell's limit."""

from chip import calibrate
from chip.tests.conftest import tiny_cell


def test_control_fails_where_program_passes():
    cell = tiny_cell()
    rows = calibrate.readings(cell, [31, 32, 2**31 + 33],
                              require_chip=False)
    for name, limit in cell.limits.items():
        for row in rows:
            prog, ctl = row[f"{name}.program"], row[f"{name}.control"]
            assert prog <= limit["limit"] < ctl, (name, row)
            assert ctl >= 3 * prog, (name, row)
