"""The control: the reference itself, computed one step below the stated
precision and put in the program's place, must come out not correct.

On the CPU every f32 product is exact, so the f32 control here is the one
that holds on any backend: the XLA layers and the TCONVs in bfloat16.  The
readings that set the limits were taken on the chip (``control.py``)."""

import pytest

import _cpu_cell

CONTROLS = [("pix2pix.f32.closed", {"xla": "bfloat16"}),
            ("dcgan.int8.poisson", {"int_bits": 4}),
            ("pix2pix.int8.closed", {"int_bits": 4})]


@pytest.mark.parametrize("name,changes", CONTROLS)
def test_control_is_not_correct(name, changes, tmp_path, monkeypatch):
    c = _cpu_cell.cell(name)
    result, _ = _cpu_cell.run(c, 77, hook=_cpu_cell.reference_in_place(
        c, **changes), monkeypatch=monkeypatch, tmp_path=tmp_path)
    assert not result["correct"]
    value, limit = _cpu_cell.compared(result, c)
    assert value > limit
