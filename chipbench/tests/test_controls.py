"""The comparison that decides ``correct`` fails where it must: the control
(the reference one precision step down) and each planted fault in the
timed path make ``correct`` false, while the plain decoder at full
precision passes, so the control fails for its precision alone."""
import time

import pytest

from chipbench import controls, harness


def _run(root, cpu, workload, patch):
    return harness.run(str(root), workload, 2**32 + 99, 0.5, False,
                       t_start=time.perf_counter(), devices=cpu,
                       log=lambda m: None, patch=patch)


@pytest.mark.parametrize("workload", ["decode.long_lists",
                                      "decode.short_lists"])
@pytest.mark.parametrize("name", ["control", "altered", "half"])
def test_fault_makes_correct_false(tiny_root, cpu, workload, name):
    r = _run(tiny_root, cpu, workload, controls.PATCHES[name])
    assert not r["correct"], r["checks"]
    assert r["checks"]["wrong_ints"]["value"] > 0, r["checks"]


def test_plain_decoder_at_full_precision_passes(tiny_root, cpu):
    r = _run(tiny_root, cpu, "decode.long_lists", controls.decode_plain)
    assert r["correct"], r["checks"]
