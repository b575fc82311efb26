"""Generators: lists are sorted, distinct and in range at every length;
every seed gets the same sizes; the benchmark's VByte encoder writes the
program's host encoder's bytes."""
import numpy as np
import pytest

from chipbench import gen


@pytest.mark.parametrize("length,universe", [
    (1, 1), (1, 50_000_000), (1000, 1000), (1000, 50_000_000),
    ((1 << 22) + 5, 50_000_000)])
def test_sorted_gap_list(length, universe):
    d = gen.sorted_gap_list(gen.rng_for(2**40 + 1, 0), length, universe)
    assert d.dtype == np.uint32 and d.size == length
    assert int(d[-1]) < universe
    assert (np.diff(d.astype(np.int64)) > 0).all()


def test_seed_range():
    with pytest.raises(ValueError):
        gen.rng_for(-1)
    a, b = gen.rng_for(2**31 + 7), gen.rng_for(2**31 + 8)
    assert a.integers(1 << 62) != b.integers(1 << 62)


@pytest.mark.parametrize("k,n", [(10, 8), (13, 256), (22, 16)])
def test_group_lengths(k, n):
    lens = gen.group_lengths(k, n)
    assert len(lens) == n and len(set(lens)) == n
    assert all((1 << k) <= x < (1 << (k + 1)) for x in lens)


def _block_bytes(d, block=128):
    """Sorted encoded bytes per block of d-gap VByte."""
    gaps = np.diff(d.astype(np.int64), prepend=0)
    nbytes = 1 + sum((gaps >= 1 << (7 * k)).astype(int) for k in (1, 2, 3, 4))
    nbytes = np.pad(nbytes, (0, -nbytes.size % block))
    return np.sort(nbytes.reshape(-1, block).sum(axis=1))


@pytest.mark.parametrize("length,universe", [
    (1, 5), (128, 300), (1000, 1001), (5000, 50_000_000),
    (70_001, 50_000_000)])
def test_shuffled_list_keeps_every_blocks_bytes(length, universe):
    t = 1 + gen.sorted_gap_list(gen.rng_for(7, length), length, universe - 1)
    a = gen.shuffled_list(t, gen.rng_for(2**35 + 1, 0), 128)
    b = gen.shuffled_list(t, gen.rng_for(2**35 + 1, 0), 128)
    assert (a == b).all() and a.dtype == np.uint32 and a.size == length
    assert int(a[0]) >= 1 and int(a[-1]) == int(t[-1]) < universe
    assert (np.diff(a.astype(np.int64)) > 0).all()
    assert gen.vbyte_blocked(a)[0].shape == gen.vbyte_blocked(t)[0].shape
    assert (_block_bytes(a) == _block_bytes(t)).all()
    if length > 128 and universe > 2 * length:  # gaps that differ
        c = gen.shuffled_list(t, gen.rng_for(2**35 + 2, 0), 128)
        assert (a != c).any()
    with pytest.raises(ValueError):
        gen.shuffled_list(t - t[0], gen.rng_for(1), 128)


@pytest.mark.parametrize("length,universe", [
    (1, 5), (127, 200), (128, 1 << 32), (300, 1000), (5000, 50_000_000),
    (3000, 1 << 32), (70_000, 50_000_000)])
def test_vbyte_blocked_writes_the_programs_bytes(length, universe):
    from repro.core.vbyte import encode as host

    d = gen.sorted_gap_list(gen.rng_for(5, length), length, universe)
    payload, counts, bases = gen.vbyte_blocked(d)
    want = host.encode_blocked(d, block_size=128, differential=True)
    np.testing.assert_array_equal(payload, want.payload)
    np.testing.assert_array_equal(counts, want.counts)
    np.testing.assert_array_equal(bases, want.bases)
