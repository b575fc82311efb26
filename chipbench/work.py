"""Bytes of work and stored bits per integer, per format, from the operands.

The numbers here describe the work, not the way a kernel happens to do it:

* the tight encoded payload of the blocks that hold integers, as the format
  stores it (never the padded ``[n_blocks, stride]`` grid, the tiles, or
  the power-of-two padding blocks);
* the per-block metadata the format keeps beside the payload: ``counts``
  (int32) and ``bases`` (uint32), 8 bytes for each block that holds
  integers (binpack's one-byte width column is part of its payload, as the
  format's own ``payload_bytes`` counts it);
* 4 bytes for every real decoded integer written out.

So a change that drops padding or a wider intermediate form does the same
bytes of work in less time, and its roofline share rises. Every function
takes the operand dict of one compressed stream (``payload`` for vbyte,
``control``/``data`` for streamvbyte, ``widths``/``data`` for binpack, plus
``counts``) and an array module: numpy on the host, ``jax.numpy`` where the
operands live on the device. Count-0 blocks add nothing.
"""
from __future__ import annotations

import numpy as np

META_BYTES_PER_BLOCK = 8  # counts (int32) + bases (uint32)
OUT_BYTES_PER_INT = 4  # uint32 docids written out


def _acc(xp):
    """Accumulator type: int64 on the host; int32 under jax (64-bit types
    are off there), which holds one stream's bytes below 2^31."""
    return np.int64 if xp is np else xp.int32


def _vbyte_payload(ops, xp):
    payload, counts = ops["payload"], ops["counts"].reshape(-1)
    # block b's integers end at its counts[b]-th terminator byte (high bit
    # clear); the bytes in use are those before it, plus the terminator
    ends = xp.cumsum((payload < 0x80).astype(xp.int32), axis=1)
    inner = (ends < counts[:, None].astype(xp.int32)).sum(dtype=_acc(xp))
    return inner + (counts > 0).sum(dtype=_acc(xp))


def _streamvbyte_payload(ops, xp):
    control, counts = ops["control"], ops["counts"].reshape(-1)
    nb, quarter = control.shape
    shifts = xp.arange(4, dtype=xp.uint8) * xp.uint8(2)
    codes = (control[:, :, None] >> shifts) & xp.uint8(3)
    codes = codes.reshape(nb, 4 * quarter).astype(_acc(xp))
    valid = xp.arange(4 * quarter)[None, :] < counts[:, None]
    data = ((codes + 1) * valid).sum(dtype=_acc(xp))
    ctrl = ((counts.astype(_acc(xp)) + 3) // 4).sum(dtype=_acc(xp))
    return data + ctrl


def _binpack_payload(ops, xp):
    w = ops["widths"].reshape(-1).astype(_acc(xp))
    c = ops["counts"].reshape(-1).astype(_acc(xp))
    packed = ((w * c + 7) // 8).sum(dtype=_acc(xp))
    return packed + (c > 0).sum(dtype=_acc(xp))


PAYLOAD_BYTES = {"vbyte": _vbyte_payload,
                 "streamvbyte": _streamvbyte_payload,
                 "binpack": _binpack_payload}


def payload_bytes(fmt: str, ops: dict, xp=np):
    """Tight encoded payload bytes of one stream (``bits_per_int``'s base)."""
    if fmt not in PAYLOAD_BYTES:
        raise ValueError(f"no payload accounting for format {fmt!r}")
    return PAYLOAD_BYTES[fmt](ops, xp)


def stored_bytes(fmt: str, ops: dict, xp=np):
    """Payload plus the per-block metadata of the blocks holding integers."""
    blocks = (ops["counts"] > 0).sum(dtype=_acc(xp))
    return payload_bytes(fmt, ops, xp) + META_BYTES_PER_BLOCK * blocks


def bytes_of_work(stored, n_ints):
    """Bytes one whole-stream decode has to move: its ``stored_bytes`` in,
    and ``n_ints`` real uint32 out (scalars or arrays)."""
    return stored + OUT_BYTES_PER_INT * n_ints


def bits_per_int(stored: int, n_ints: int) -> float:
    """``index_bits_per_int``: stored bits over the integers they hold."""
    if n_ints <= 0:
        raise ValueError("bits per int of an empty set of lists")
    return 8.0 * float(stored) / float(n_ints)
