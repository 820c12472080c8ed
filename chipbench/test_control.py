"""The comparison's control, at a small size: the reference with each
epoch's cycle count kept in bfloat16 in the program's place is not
correct under the cell's limits."""
from __future__ import annotations

from chipbench import control
from chipbench.conftest import CONFIG, SMALL_MIX


def test_control_is_not_correct():
    got = control.readings(CONFIG["nmp_config"], SMALL_MIX, seed=2**33 + 9,
                           calls=2)
    limits = SMALL_MIX["limits"]
    assert (got["mismatched_counts"] > limits["mismatched_counts"]
            or got["max_rel_gap"] > limits["max_rel_gap"])
