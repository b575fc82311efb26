"""Data and traffic generators: everything a run makes from ``--seed``.

Every seed gets the same sizes: list lengths are fixed points inside each
length group (:func:`group_lengths`), each list's gaps are those of a
template drawn from a fixed seed, and a seed decides only the order of
those gaps (:func:`shuffled_list`) and the order of the requests. So every
block of a list encodes to the same number of bytes for every seed, each
list gets the same stride and shape, and two seeds do the same work in
another order.

Lists come from sorted-gap sampling at every length: ``length`` draws from
``[0, universe - length]``, sorted, plus ``arange(length)``. That is
O(length) time and memory, strictly increasing, and below ``universe``;
the program's own generator permutes the whole universe for lists under
2^22 ids, which costs seconds per list at the ClueWeb09 universe.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent numpy generator for one purpose of one seed."""
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    return np.random.default_rng(np.random.SeedSequence([seed, *stream]))


def group_lengths(k: int, n_lists: int) -> list[int]:
    """``n_lists`` fixed lengths spread evenly over ``[2^k, 2^(k+1))``,
    the paper's length group K (midpoints of ``n_lists`` equal slices)."""
    return [int((1 << k) * (1.0 + (i + 0.5) / n_lists))
            for i in range(n_lists)]


def sorted_gap_list(rng: np.random.Generator, length: int,
                    universe: int) -> np.ndarray:
    """One sorted list of ``length`` distinct uint32 docids below
    ``universe`` (sorted-gap sampling, see the module docstring)."""
    if not 0 < length <= universe <= 1 << 32:
        raise ValueError(f"need 0 < length <= universe <= 2^32, got "
                         f"{length}, {universe}")
    y = np.sort(rng.integers(0, universe - length + 1, size=length,
                             dtype=np.int64))
    return (y + np.arange(length, dtype=np.int64)).astype(np.uint32)


def shuffled_list(template: np.ndarray, rng: np.random.Generator,
                  block: int) -> np.ndarray:
    """The docids whose gaps are ``template``'s in another order.

    Whole blocks of ``block`` gaps trade places and the gaps inside each
    block are shuffled; a last, partial block stays last. Every block keeps
    its multiset of gaps, so it encodes to the same bytes count, and the
    list ends where the template ends. The template's first id must be at
    least 1, so that no gap is 0 wherever it lands. Gaps of sorted uniform
    draws are exchangeable, so the result is as likely a list as a fresh
    draw with those gaps."""
    if template.size == 0 or template[0] < 1:
        raise ValueError("the template's first docid must be at least 1")
    gaps = np.diff(template, prepend=np.uint32(0))
    full = gaps.size // block * block
    head = gaps[:full].reshape(-1, block)
    head = rng.permuted(head[rng.permutation(head.shape[0])], axis=1)
    tail = rng.permutation(gaps[full:])
    return np.cumsum(np.concatenate([head.reshape(-1), tail]),
                     dtype=np.uint32)


def vbyte_blocked(docids: np.ndarray, block: int = 128,
                  stride_multiple: int = 128):
    """D-gap Masked VByte in the program's blocked layout, on the host:
    ``(payload u8[n_blocks, stride], counts i32[n_blocks],
    bases u32[n_blocks])``, byte for byte what the program's host encoder
    writes (each block's gaps from its first id's predecessor, the stride
    the largest block rounded up to ``stride_multiple``). About 60 ns an
    int, several times the program's encoder, so a run can encode 195M
    ints in its set-up."""
    n = docids.size
    nb = -(-n // block)
    gaps = np.zeros(nb * block, np.uint32)
    gaps[:n] = docids
    gaps[1:n] -= docids[:-1]
    nbytes = np.ones(gaps.size, np.uint8)
    for threshold in (1 << 7, 1 << 14, 1 << 21, 1 << 28):
        nbytes += gaps >= threshold
    nbytes[n:] = 0
    ends = np.cumsum(nbytes.reshape(nb, block), axis=1, dtype=np.int32)
    stride = -(-max(int(ends[:, -1].max()), 1) // stride_multiple)
    stride = min(stride * stride_multiple, 5 * block)
    payload = np.zeros((nb, stride), np.uint8)
    starts = (ends.reshape(-1)[:n] - nbytes[:n]
              + np.repeat(np.arange(nb, dtype=np.int64) * stride, block)[:n])
    flat = payload.reshape(-1)
    for k in range(5):  # byte k of every int that has one
        at = np.flatnonzero(nbytes[:n] > k)
        if at.size == 0:
            break
        part = (gaps[at] >> np.uint32(7 * k)) & np.uint32(0x7F)
        more = (nbytes[at] > k + 1).astype(np.uint8) << 7
        flat[starts[at] + k] = part.astype(np.uint8) | more
    counts = np.full(nb, block, np.int32)
    counts[-1] = n - (nb - 1) * block
    bases = np.zeros(nb, np.uint32)
    bases[1:] = docids[np.arange(1, nb) * block - 1]
    return payload, counts, bases
