"""Public jit'd wrappers for the Pallas decode kernels (both formats).

On CPU (this container) the kernels execute in interpret mode; on TPU they
compile through Mosaic. ``vbyte_decode_blocked`` matches
``ref.vbyte_decode_blocked_ref`` and ``repro.core.vbyte.masked.decode_blocked``;
``stream_vbyte_decode_blocked`` matches
``repro.core.vbyte.stream_masked.decode_blocked``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .binpack_kernel import binpack_decode_blocked_pallas
from .kernel import decode_blocked_pallas
from .stream_kernel import stream_decode_blocked_pallas


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def normalize_block_meta(name: str, x: jax.Array, n_blocks: int) -> jax.Array:
    """Validate per-block metadata (``counts``/``bases``) shape; return 1-D.

    The public contract accepts ``[n_blocks]`` or ``[n_blocks, 1]`` (the
    kernels' internal tile shape). Anything else — wrong length, transposed,
    extra dims — raises a clear ValueError instead of a silent reshape.
    """
    shape = tuple(x.shape)
    if shape == (n_blocks,):
        return x
    if shape == (n_blocks, 1):
        return x[:, 0]
    raise ValueError(
        f"{name} must have shape [n_blocks] or [n_blocks, 1] with "
        f"n_blocks={n_blocks}; got {shape}")


def normalize_probe(probe, width: int):
    """Validate + pad a sorted probe set for the membership/bm25 epilogues.

    ``probe`` is a 1-D sorted array of docids (< 2^31 — the in-kernel
    comparison runs in int32). Returns ``int32 [1, width]`` padded with -1
    (the never-matches sentinel the epilogue masks out). Raises on unsorted,
    too-long, or out-of-range inputs instead of silently mis-matching.
    """
    import numpy as np

    p = np.asarray(probe).reshape(-1)
    if p.size > width:
        raise ValueError(f"probe has {p.size} ids > width={width}")
    if p.size:
        if p.min() < 0 or int(p.max()) >= 1 << 31:
            raise ValueError("probe docids must be in [0, 2^31) — the "
                             "membership epilogue compares in int32")
        if np.any(np.diff(p.astype(np.int64)) < 0):
            raise ValueError("probe must be sorted (non-decreasing)")
    out = np.full((1, width), -1, np.int32)
    out[0, : p.size] = p.astype(np.int32)
    return out


@functools.partial(
    jax.jit, static_argnames=("block_size", "differential", "block_tile",
                              "interpret")
)
def vbyte_decode_blocked(
    payload: jax.Array,  # uint8 [n_blocks, stride]
    counts: jax.Array,  # int   [n_blocks] or [n_blocks, 1]
    bases: jax.Array,  # uint32/int32 [n_blocks] or [n_blocks, 1]
    *,
    block_size: int,
    differential: bool,
    block_tile: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode a blocked VByte payload to uint32[n_blocks, block_size]."""
    if interpret is None:
        interpret = _auto_interpret()
    nb, stride = payload.shape
    counts = normalize_block_meta("counts", counts, nb)
    bases = normalize_block_meta("bases", bases, nb)

    pad = (-nb) % block_tile
    if pad:
        payload = jnp.pad(payload, ((0, pad), (0, 0)))
        counts = jnp.pad(counts, ((0, pad),))
        bases = jnp.pad(bases, ((0, pad),))

    counts2 = counts.astype(jnp.int32)[:, None]
    bases2 = jax.lax.bitcast_convert_type(bases.astype(jnp.uint32), jnp.int32)[:, None]

    out = decode_blocked_pallas(
        payload,
        counts2,
        bases2,
        block_size=block_size,
        differential=differential,
        block_tile=block_tile,
        interpret=interpret,
    )
    out = jax.lax.bitcast_convert_type(out, jnp.uint32)
    return out[:nb]


@functools.partial(
    jax.jit, static_argnames=("block_size", "differential", "block_tile",
                              "chunk_width", "interpret")
)
def stream_vbyte_decode_blocked(
    control: jax.Array,  # uint8 [n_blocks, block_size // 4]
    data: jax.Array,  # uint8 [n_blocks, data_stride]
    counts: jax.Array,  # int   [n_blocks] or [n_blocks, 1]
    bases: jax.Array,  # uint32/int32 [n_blocks] or [n_blocks, 1]
    *,
    block_size: int,
    differential: bool,
    block_tile: int = 8,
    chunk_width: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode a blocked Stream-VByte payload to uint32[n_blocks, block_size]."""
    if interpret is None:
        interpret = _auto_interpret()
    nb, _ = control.shape
    counts = normalize_block_meta("counts", counts, nb)
    bases = normalize_block_meta("bases", bases, nb)

    pad = (-nb) % block_tile
    if pad:
        control = jnp.pad(control, ((0, pad), (0, 0)))
        data = jnp.pad(data, ((0, pad), (0, 0)))
        counts = jnp.pad(counts, ((0, pad),))
        bases = jnp.pad(bases, ((0, pad),))

    counts2 = counts.astype(jnp.int32)[:, None]
    bases2 = jax.lax.bitcast_convert_type(bases.astype(jnp.uint32), jnp.int32)[:, None]

    out = stream_decode_blocked_pallas(
        control,
        data,
        counts2,
        bases2,
        block_size=block_size,
        differential=differential,
        block_tile=block_tile,
        chunk_width=chunk_width,
        interpret=interpret,
    )
    out = jax.lax.bitcast_convert_type(out, jnp.uint32)
    return out[:nb]


@functools.partial(
    jax.jit, static_argnames=("block_size", "differential", "block_tile",
                              "interpret")
)
def binpack_decode_blocked(
    widths: jax.Array,  # uint8 [n_blocks, 1] or [n_blocks]
    data: jax.Array,  # uint8 [n_blocks, stride]
    counts: jax.Array,  # int   [n_blocks] or [n_blocks, 1]
    bases: jax.Array,  # uint32/int32 [n_blocks] or [n_blocks, 1]
    *,
    block_size: int,
    differential: bool,
    block_tile: int = 8,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode a blocked binpack payload to uint32[n_blocks, block_size]."""
    if interpret is None:
        interpret = _auto_interpret()
    nb, _ = data.shape
    widths = normalize_block_meta("widths", widths, nb)[:, None].astype(jnp.uint8)
    counts = normalize_block_meta("counts", counts, nb)
    bases = normalize_block_meta("bases", bases, nb)

    pad = (-nb) % block_tile
    if pad:
        widths = jnp.pad(widths, ((0, pad), (0, 0)))
        data = jnp.pad(data, ((0, pad), (0, 0)))
        counts = jnp.pad(counts, ((0, pad),))
        bases = jnp.pad(bases, ((0, pad),))

    counts2 = counts.astype(jnp.int32)[:, None]
    bases2 = jax.lax.bitcast_convert_type(bases.astype(jnp.uint32), jnp.int32)[:, None]

    out = binpack_decode_blocked_pallas(
        widths,
        data,
        counts2,
        bases2,
        block_size=block_size,
        differential=differential,
        block_tile=block_tile,
        interpret=interpret,
    )
    out = jax.lax.bitcast_convert_type(out, jnp.uint32)
    return out[:nb]
