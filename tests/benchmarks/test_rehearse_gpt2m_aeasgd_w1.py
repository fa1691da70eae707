"""The rehearsal of ``gpt2m_aeasgd_w1``: untraced, then traced on the compile
cache the first case filled (``rehearsal.py`` says how)."""

import pytest
from rehearsal import rehearse

CELL = "gpt2m_aeasgd_w1"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_line_the_check_accepts(trace, tmp_path_factory):
    rehearse(CELL, trace, tmp_path_factory)
