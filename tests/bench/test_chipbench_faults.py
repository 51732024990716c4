"""Whole runs on the CPU with the timed path sound, then broken: a sound
run is correct, an altered answer is not."""

import pytest

import _cpu_cell

CELLS = ["pix2pix.f32.closed", "dcgan.int8.poisson", "pix2pix.int8.closed"]


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_an_altered_answer_is_not(name, tmp_path,
                                                           monkeypatch):
    c = _cpu_cell.cell(name)
    result, extra = _cpu_cell.run(c, 2 ** 32 + 9, monkeypatch=monkeypatch,
                                  tmp_path=tmp_path)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in c.e2e}
    assert list(result)[-1] == "checks"

    bad, _ = _cpu_cell.run(c, 2 ** 32 + 10, hook=_cpu_cell.alter_answer,
                           monkeypatch=monkeypatch, tmp_path=tmp_path)
    assert not bad["correct"]
    value, limit = _cpu_cell.compared(bad, c)
    assert value > limit
