"""Core codec: format vectors (paper Table 1), round-trips, property tests
(seeded case generators from conftest — no hypothesis dependency)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import CompressedIntArray
from repro.core.vbyte import encode as venc
from repro.core.vbyte import masked as vmask
from repro.core.vbyte import ref as vref

from conftest import (BOUNDARY_VALUES, make_valid_stream, sorted_u32_cases,
                      u32_cases)


# -- paper Table 1: exact byte-level vectors ---------------------------------
TABLE1 = {
    1: [0b00000001],
    2: [0b00000010],
    4: [0b00000100],
    128: [0b10000000, 0b00000001],
    256: [0b10000000, 0b00000010],
    512: [0b10000000, 0b00000100],
    16384: [0b10000000, 0b10000000, 0b00000001],
    32768: [0b10000000, 0b10000000, 0b00000010],
}


@pytest.mark.parametrize("value,expected", sorted(TABLE1.items()))
def test_paper_table1_format(value, expected):
    assert venc.encode_stream(np.array([value], np.uint64)).tolist() == expected


def test_lengths_match_stream():
    vals = np.array([0, 127, 128, 16383, 16384, 2**21 - 1, 2**21, 2**28 - 1,
                     2**28, 2**32 - 1], np.uint64)
    lens = venc.vbyte_lengths(vals)
    assert lens.tolist() == [1, 1, 2, 2, 3, 3, 4, 4, 5, 5]
    assert venc.encode_stream(vals).size == lens.sum()


def test_scalar_roundtrip(rng):
    vals = make_valid_stream(rng, 500)
    s = venc.encode_stream(vals)
    assert np.array_equal(vref.decode_stream_scalar(s, len(vals)), vals)


def test_masked_stream_matches_scalar(rng):
    vals = make_valid_stream(rng, 300)
    s = venc.encode_stream(vals)
    data = np.concatenate([s, np.zeros(32, np.uint8)])
    out, n = vmask.decode_stream(jnp.asarray(data), 512, nbytes=len(s))
    assert int(n) == 300
    assert np.array_equal(np.asarray(out[:300], np.uint64), vals)


def test_lax_scalar_matches(rng):
    vals = make_valid_stream(rng, 200)
    s = venc.encode_stream(vals)
    out, n = vref.decode_stream_scalar_jax(jnp.asarray(s), 256)
    assert int(n) == 200
    assert np.array_equal(np.asarray(out[:200], np.uint64), vals)


@pytest.mark.parametrize("differential", [False, True])
@pytest.mark.parametrize("n,block_size", [(1, 128), (127, 128), (128, 128),
                                          (129, 128), (1000, 64), (4096, 128)])
def test_blocked_roundtrip(rng, differential, n, block_size):
    if differential:
        vals = np.sort(rng.integers(0, 2**31, size=n)).astype(np.uint64)
    else:
        vals = make_valid_stream(rng, n)
    arr = CompressedIntArray.encode(vals, block_size=block_size,
                                    differential=differential)
    assert np.array_equal(arr.decode().astype(np.uint64), vals)
    assert np.array_equal(arr.decode_scalar_oracle().astype(np.uint64), vals)


def test_differential_requires_sorted():
    with pytest.raises(ValueError):
        venc.delta_encode(np.array([5, 3], np.uint64))


def test_differential_compresses_sorted_ids(rng):
    ids = np.sort(rng.choice(50_000_000, size=1 << 14, replace=False)).astype(np.uint64)
    plain = CompressedIntArray.encode(ids, differential=False)
    delta = CompressedIntArray.encode(ids, differential=True)
    assert delta.bits_per_int < plain.bits_per_int
    assert delta.compression_ratio > 1.5  # gaps ~3000 → ≤2 bytes/int


def test_count_integers(rng):
    vals = make_valid_stream(rng, 77)
    s = venc.encode_stream(vals)
    data = np.concatenate([s, np.zeros(16, np.uint8)])
    assert int(vmask.count_integers(jnp.asarray(data), len(s))) == 77


# -- seeded property tests (conftest harness) --------------------------------
def test_prop_stream_roundtrip():
    for case, vals in u32_cases(n_cases=60, max_len=300):
        s = venc.encode_stream(vals)
        got = vref.decode_stream_scalar(s, len(vals))
        assert np.array_equal(got, vals), case


def test_prop_blocked_masked_equals_scalar():
    for case, vals in u32_cases(n_cases=40, max_len=300):
        arr = CompressedIntArray.encode(vals, block_size=32)
        assert np.array_equal(arr.decode(), arr.decode_scalar_oracle()), case


def test_prop_differential_roundtrip():
    for case, vals in sorted_u32_cases(n_cases=40, max_len=200):
        arr = CompressedIntArray.encode(vals, block_size=32, differential=True)
        assert np.array_equal(arr.decode().astype(np.uint64), vals), case


@pytest.mark.parametrize("block_size", [8, 32, 128])
def test_prop_payload_bytes_is_tight_size(block_size):
    # the vectorized size accounting equals the per-block scalar decode's
    for case, vals in u32_cases(n_cases=30, max_len=600):
        enc = CompressedIntArray.encode(vals, block_size=block_size).enc
        want = sum(int(venc.vbyte_lengths(vref.decode_stream_scalar(
            enc.payload[b], int(enc.counts[b]))).sum())
            for b in range(enc.n_blocks))
        assert enc.payload_bytes == want == venc.vbyte_lengths(vals).sum(), \
            case


def test_prop_length_formula(rng):
    # every byte-length threshold (±1 via BOUNDARY_VALUES) plus random draws
    samples = np.concatenate([
        BOUNDARY_VALUES,
        rng.integers(0, 2**32, size=100, dtype=np.uint64),
    ])
    for v in samples:
        n = venc.vbyte_lengths(np.array([v], np.uint64))[0]
        assert n == max(1, -(-int(v).bit_length() // 7)), v
