"""Bytes of work and bits per int from the operands agree with the
program's own accounting of host-encoded lists, padding adds nothing, the
device form gives the same count, and an unknown device kind has no
peaks."""
import numpy as np
import pytest

from chipbench import gen, harness, work

from conftest import ROOT

FORMATS = ("vbyte", "streamvbyte", "binpack")


def _lists():
    rng = gen.rng_for(4, 1)
    return [gen.sorted_gap_list(rng, n, u) for n, u in
            ((1, 10), (127, 1000), (128, 50_000_000), (5000, 50_000_000),
             (70_000, 50_000_000), (3000, 1 << 32))]


def _ops(arr):
    return {k: np.asarray(getattr(arr, k))
            for k in ("payload", "control", "widths", "data", "counts")
            if getattr(arr, k) is not None}


@pytest.mark.parametrize("fmt", FORMATS)
def test_payload_matches_bits_per_int(fmt):
    from repro.core import CompressedIntArray

    for docids in _lists():
        arr = CompressedIntArray.encode(docids, format=fmt, block_size=128,
                                        differential=True)
        payload = int(work.payload_bytes(fmt, _ops(arr)))
        assert 8.0 * payload / arr.n == arr.bits_per_int
        stored = int(work.stored_bytes(fmt, _ops(arr)))
        assert stored == payload + 8 * arr.n_blocks
        assert work.bits_per_int(stored, arr.n) == pytest.approx(
            arr.bits_per_int + 64.0 * arr.n_blocks / arr.n)
        assert work.bytes_of_work(stored, arr.n) == stored + 4 * arr.n


@pytest.mark.parametrize("fmt", FORMATS)
def test_padding_and_device_form_count_the_same(fmt):
    import jax.numpy as jnp

    from repro.core import CompressedIntArray

    docids = _lists()[3]
    arr = CompressedIntArray.encode(docids, format=fmt, block_size=128,
                                    differential=True)
    padded = arr.slice_blocks(0, arr.n_blocks, pad_to=2 * arr.n_blocks)
    want = int(work.stored_bytes(fmt, _ops(arr)))
    assert int(work.stored_bytes(fmt, _ops(padded))) == want
    dev = {k: jnp.asarray(v) for k, v in _ops(padded).items()}
    assert int(work.stored_bytes(fmt, dev, jnp)) == want


def test_unknown_format_and_empty_set_raise():
    with pytest.raises(ValueError):
        work.payload_bytes("pfor", {})
    with pytest.raises(ValueError):
        work.bits_per_int(10, 0)


def test_peaks_are_keyed_by_device_kind():
    peaks = harness._device_kind_peaks(ROOT, "TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness._device_kind_peaks(ROOT, "TPU v9 imaginary")
