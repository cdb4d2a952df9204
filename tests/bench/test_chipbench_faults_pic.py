"""A run with the timed path broken underneath reads ``correct`` false:
an answer altered where the program produces it, in the mesh cell."""

from repro.io import reader as reader_mod


def test_read_value_altered(run_cell, monkeypatch):
    orig = reader_mod.Dataset.read

    def read(self, *a, **kw):
        arr, st = orig(self, *a, **kw)
        arr.reshape(-1)[-1] += 1.0
        return arr, st

    monkeypatch.setattr(reader_mod.Dataset, "read", read)
    rc, result, err = run_cell("pic-read", seed=31)
    assert rc == 0, err
    assert result["correct"] is False
    assert result["checks"]["read_elements_differing"]["value"] >= 1
