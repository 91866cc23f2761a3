"""The control -- the reference in bfloat16 in the program's place --
comes out not correct in every cell, at a tiny size."""
import pytest

from bench import control
from conftest import DP4, SERVE, tiny


@pytest.mark.parametrize("name", ["mnist-stored.train",
                                  DP4,
                                  "webspam-regen-packed.featurize",
                                  SERVE])
def test_control_fails(name):
    rows = control.readings(name, [2 ** 40 + 11, 12], "control", 0.5,
                            need_chip=False, cell=tiny(name))
    assert not any(r["correct"] for r in rows), rows
