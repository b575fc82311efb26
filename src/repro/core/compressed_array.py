"""Public API: device-resident compressed integer arrays.

``CompressedIntArray`` is the framework's first-class compressed-id type
(DESIGN.md §3): posting lists, token streams, adjacency lists, user
histories and retrieval candidate lists are all stored in this form and
decoded on device by a vectorized decoder or its Pallas kernel
(``repro.kernels.vbyte_decode``).

The array is a **registered JAX pytree**: the blocked operand arrays
(``payload`` — or ``control``/``data`` for Stream VByte — plus ``counts``
and ``bases``) are traced leaves, while ``format`` / ``block_size`` /
``differential`` / ``n`` / ``ragged`` are static aux data. That means a
``CompressedIntArray`` passes through ``jit`` / ``grad`` / ``scan`` /
``shard_map`` like any other array — call sites hand the array itself to
models and kernels instead of unpacking ``device_operands()`` dicts, and
two arrays with the same shapes share one jit trace.

Three on-device formats are supported, selected with ``format=``:

* ``"vbyte"`` (default) — the classic format of Plaisance, Kurz & Lemire:
  7 payload bits per byte, the high bit a continuation flag. Densest for
  small gaps (1 byte spans values < 2^7) and the paper's own format, but
  the decoder must recover integer boundaries from the continuation bits
  (``repro.core.vbyte.masked``). Blocked operands:
  ``payload [n_blocks, stride]`` + ``counts`` + ``bases``.

* ``"streamvbyte"`` — Stream VByte (Lemire, Kurz & Rupp): 2-bit length
  codes live in a separate control stream and every data byte carries a
  full 8 payload bits, so the decoder skips the continuation-bit scan
  entirely (``repro.core.vbyte.stream_masked``,
  ``repro.kernels.vbyte_decode.stream_kernel``). Costs 2 control bits per
  integer and rounds each integer to whole bytes (1 byte spans values
  < 2^8, ≤4 bytes total), so compression is within ~2 bits/int of VByte on
  typical gap distributions — and decode is faster because byte→integer
  routing comes straight from the control stream.

* ``"binpack"`` — binary packing (Lemire & Boytsov): every block's values
  are packed at the block's max bit width ``w``, recorded in a one-byte
  per-block width column. Integer ``j`` starts at bit ``j·w`` — affine,
  so decode needs **no boundary recovery and no length prefix sum at
  all** (``repro.core.vbyte.binpack_masked``,
  ``repro.kernels.vbyte_decode.binpack_kernel``): the fastest decode of
  the three. Compression is width-outlier-sensitive (one large gap costs
  the whole block), which the index builder's optimal block partition
  turns back into a win (``repro.index.partition``). Blocked operands:
  ``widths [n_blocks, 1]`` + ``data [n_blocks, stride]`` + ``counts`` +
  ``bases``.

Rule of thumb (see docs/formats.md): pick ``"vbyte"`` when bits/int is the
binding constraint, ``"streamvbyte"`` for fast decode on mixed-width gaps,
``"binpack"`` for the fastest decode on width-homogeneous blocks. All
formats share the blocked SPMD layout (``block_size`` integers per block,
per-block ``counts``/``bases``) so every block decodes independently, and
all support fused differential (delta) decoding of sorted id lists.

Because blocks are independent, the block dimension is also the natural
**sharding** dimension: ``arr.shard(mesh, axis="data")`` places the block
dim of every leaf across a mesh axis with ``NamedSharding``, and the
dispatch layer (``repro.kernels.vbyte_decode.dispatch``) decodes each
shard's blocks where they live via ``shard_map`` — no cross-device decode
traffic (see docs/serving.md).
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field, replace
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from .vbyte import binpack as bpk
from .vbyte import encode as venc
from .vbyte import ref as vref
from .vbyte import stream_vbyte as svb

FORMATS = ("vbyte", "streamvbyte", "binpack")

# pytree leaves per format, in flatten order (the block dim leads every leaf)
FORMAT_LEAVES = {
    "vbyte": ("payload", "counts", "bases"),
    "streamvbyte": ("control", "data", "counts", "bases"),
    "binpack": ("widths", "data", "counts", "bases"),
}

def block_checksums(grid: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Per-block position-weighted checksum of a decoded value grid.

    ``cs[b] = (Σ_{j < counts[b]} grid[b, j] · (2j+1)) mod 2^32``, returned
    as ``int32 [n_blocks]`` (bit pattern of the uint32 sum). Odd positional
    weights make the sum order-sensitive. Computed in uint64 — products are
    ≤ 2^32·(2·block_size) and blocks are short, so the sum never overflows
    before the final mask. The device twin is the fused ``checksum``
    epilogue (``kernels/vbyte_decode/epilogues.py``), whose int32
    two's-complement arithmetic wraps bit-identically.
    """
    g = np.asarray(grid, dtype=np.uint64) & np.uint64(0xFFFFFFFF)
    B = g.shape[1]
    w = (2 * np.arange(B, dtype=np.uint64) + 1)[None, :]
    valid = np.arange(B)[None, :] < np.asarray(counts).reshape(-1, 1)
    cs = (g * w * valid).sum(axis=1, dtype=np.uint64)
    return (cs & np.uint64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)


_USE_KERNEL_MSG = (
    "use_kernel= is deprecated; pass plan= instead "
    "(use_kernel=True -> plan='kernel', use_kernel=False -> plan='jnp'; "
    "see repro.kernels.vbyte_decode.dispatch)")


def warn_use_kernel(use_kernel: bool) -> str:
    """Map the legacy ``use_kernel`` boolean to a plan name, with a warning."""
    warnings.warn(_USE_KERNEL_MSG, DeprecationWarning, stacklevel=3)
    return "kernel" if use_kernel else "jnp"


@dataclass(frozen=True)
class CompressedIntArray:
    """A compressed, block-decodable array of uint32 (VByte or Stream VByte).

    Leaves (traced; any of numpy / jax / ShapeDtypeStruct / PartitionSpec —
    the class is a pytree container, not an array wrapper):

    * ``payload`` — ``uint8 [n_blocks, stride]`` (``format="vbyte"`` only)
    * ``control`` — ``uint8 [n_blocks, block_size // 4]`` (streamvbyte)
    * ``widths``  — ``uint8 [n_blocks, 1]`` per-block bit width (binpack)
    * ``data``    — ``uint8 [n_blocks, data_stride]`` (streamvbyte/binpack)
    * ``counts``  — ``int32 [n_blocks]`` valid integers per block
    * ``bases``   — ``uint32 [n_blocks]`` differential carry-in

    Static aux data (part of the jit trace key, never traced): ``format``,
    ``block_size``, ``differential``, ``n``, ``ragged``.
    """

    payload: Any = None  # vbyte
    control: Any = None  # streamvbyte
    widths: Any = None  # binpack
    data: Any = None  # streamvbyte / binpack
    counts: Any = None
    bases: Any = None
    format: str = "vbyte"
    block_size: int = 128
    differential: bool = False
    n: int = 0
    ragged: bool = False  # one independent list (bag) per block
    # original host-side encoding (BlockedEncoding / StreamVByteEncoding);
    # carries exact-size accounting (payload_bytes). NOT a pytree child —
    # arrays reconstructed inside jit/shard_map have host_enc=None.
    host_enc: Any = field(default=None, compare=False, repr=False)
    # optional per-block checksum column (int32 [n_blocks], see
    # block_checksums) written by encode(..., checksum=True) and verified by
    # repro.robustness.validate.decode_checked in the same decode tile pass.
    # Off-tree like host_enc: host metadata, dropped on pytree unflatten.
    checksums: Any = field(default=None, compare=False, repr=False)

    # -- pytree protocol ---------------------------------------------------
    def tree_flatten_with_keys(self):
        names = FORMAT_LEAVES[self.format]
        children = tuple(
            (jax.tree_util.GetAttrKey(nm), getattr(self, nm)) for nm in names)
        aux = (self.format, self.block_size, self.differential, self.n,
               self.ragged)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        fmt, block_size, differential, n, ragged = aux
        kw = dict(zip(FORMAT_LEAVES[fmt], children))
        return cls(format=fmt, block_size=block_size,
                   differential=differential, n=n, ragged=ragged, **kw)

    # -- construction -----------------------------------------------------
    @classmethod
    def _from_encoding(cls, enc, format: str) -> "CompressedIntArray":
        names = FORMAT_LEAVES[format]
        kw = {nm: getattr(enc, nm) for nm in names}
        return cls(format=format, block_size=enc.block_size,
                   differential=enc.differential, n=enc.n,
                   ragged=getattr(enc, "ragged", False), host_enc=enc, **kw)

    @classmethod
    def from_operands(
        cls,
        operands: dict[str, Any],
        *,
        format: str = "vbyte",
        block_size: int = 128,
        differential: bool = False,
        n: int | None = None,
        ragged: bool = False,
    ) -> "CompressedIntArray":
        """Wrap existing blocked operand arrays (no re-encoding).

        ``operands`` holds the format leaves (``payload`` or
        ``control``/``data``, plus ``counts``/``bases``). ``n`` defaults to
        ``sum(counts)`` when the counts are concrete. The leaves may also be
        ``ShapeDtypeStruct``s or ``PartitionSpec``s — useful for building
        abstract batch templates and sharding-spec trees with the same
        treedef as a real array.
        """
        if format not in FORMAT_LEAVES:
            raise ValueError(
                f"unknown format {format!r}; expected one of {FORMATS}")
        names = FORMAT_LEAVES[format]
        missing = [k for k in names if k not in operands]
        if missing:
            raise ValueError(f"format {format!r} operands missing {missing}")
        if n is None:
            try:
                n = int(np.asarray(operands["counts"]).sum())
            except TypeError:
                raise ValueError(
                    "n= is required when counts are abstract") from None
        return cls(format=format, block_size=block_size,
                   differential=differential, n=n, ragged=ragged,
                   **{nm: operands[nm] for nm in names})

    @classmethod
    def encode(
        cls,
        values: np.ndarray | None = None,
        *,
        format: str = "vbyte",
        block_size: int = 128,
        differential: bool = False,
        stride_multiple: int = 128,
        wrap: bool = False,
        checksum: bool = False,
        meta=None,
    ) -> "CompressedIntArray":
        """Encode ``values`` (or a pre-computed ``BlockedMeta`` via
        ``meta=``, sharing one metadata pass with the skip-table path)."""
        encoders = {"vbyte": venc.encode_blocked,
                    "streamvbyte": svb.encode_blocked,
                    "binpack": bpk.encode_blocked}
        if format not in encoders:
            raise ValueError(f"unknown format {format!r}; expected one of {FORMATS}")
        if meta is None:
            meta = venc.prepare_blocked(
                values, block_size=block_size, differential=differential,
                wrap=wrap)
        enc = encoders[format](stride_multiple=stride_multiple, meta=meta)
        arr = cls._from_encoding(enc, format)
        if checksum:
            # checksum the *decoded* (absolute) values: pad the input to the
            # block grid — identical for all formats and both differential
            # flavors, since decode always recovers the absolute values
            v = meta.values
            grid = np.zeros((enc.counts.shape[0], meta.block_size), np.uint64)
            grid.reshape(-1)[: v.size] = v
            arr = replace(arr, checksums=block_checksums(grid, enc.counts))
        return arr

    @classmethod
    def encode_ragged(
        cls,
        lists,
        *,
        format: str = "vbyte",
        block_size: int = 128,
        differential: bool = False,
        stride_multiple: int = 128,
        wrap: bool = False,
        checksum: bool = False,
    ) -> "CompressedIntArray":
        """Encode ragged id bags: block b holds list b (≤ block_size ids).

        The one-bag-per-block layout feeds the fused bag-sum / dot-score
        kernel epilogues (``repro.kernels.vbyte_decode.dispatch``) — one
        kernel block reduces straight to one output row, so the decoded ids
        never leave VMEM. With ``differential=True`` each (sorted) list is
        delta-encoded independently, first gap absolute, ``bases`` all zero.
        """
        encoders = {"vbyte": venc.encode_ragged_blocked,
                    "streamvbyte": svb.encode_ragged_blocked,
                    "binpack": bpk.encode_ragged_blocked}
        if format not in encoders:
            raise ValueError(f"unknown format {format!r}; expected one of {FORMATS}")
        enc = encoders[format](
            lists, block_size=block_size, differential=differential,
            stride_multiple=stride_multiple, wrap=wrap)
        arr = cls._from_encoding(enc, format)
        if checksum:
            vpad, counts = venc.ragged_block_values(
                lists, block_size=block_size, differential=False, wrap=wrap)
            arr = replace(arr, checksums=block_checksums(vpad, counts))
        return arr

    # -- metadata ----------------------------------------------------------
    @property
    def enc(self):
        """The host-side encoding object (exact-size accounting). ``None``
        for arrays reconstructed from traced/abstract leaves."""
        return self.host_enc

    def _require_host_enc(self, what: str):
        if self.host_enc is None:
            raise RuntimeError(
                f"{what} needs the host-side encoding, which this "
                "CompressedIntArray no longer carries (it was rebuilt from "
                "pytree leaves, e.g. inside jit). Compute it on the array "
                "returned by encode()/encode_ragged().")
        return self.host_enc

    @property
    def n_blocks(self) -> int:
        return self.counts.shape[0]

    @property
    def bits_per_int(self) -> float:
        return self._require_host_enc("bits_per_int").bits_per_int

    @property
    def compression_ratio(self) -> float:
        """Raw uint32 bytes / tight compressed bytes (the paper's framing)."""
        enc = self._require_host_enc("compression_ratio")
        return 4.0 * self.n / max(enc.payload_bytes, 1)

    @property
    def sharding(self):
        """The NamedSharding of the block dimension (None when unsharded)."""
        s = getattr(self.counts, "sharding", None)
        return s

    # -- device form --------------------------------------------------------
    def device_operands(self) -> dict[str, Any]:
        """Arrays consumed by the decoders / the Pallas kernels."""
        return {nm: jnp.asarray(getattr(self, nm))
                for nm in FORMAT_LEAVES[self.format]}

    def shard(self, mesh, axis="data") -> "CompressedIntArray":
        """Place the block dimension of every leaf across ``mesh[axis]``.

        Returns a new array whose leaves carry ``NamedSharding``s (block dim
        over ``axis``, trailing dims replicated). ``n_blocks`` is padded with
        count=0 blocks to a multiple of the axis size so ``shard_map``
        decode divides evenly — padding blocks decode to nothing. The
        dispatch layer auto-selects the block-parallel ``shard_map`` decode
        path when it sees sharded operands (``repro.kernels.vbyte_decode.
        dispatch``); see docs/serving.md.
        """
        from repro.distributed.sharding import shard_compressed

        return shard_compressed(self, mesh, axis=axis)

    def replace_leaves(self, **leaves) -> "CompressedIntArray":
        """New array with some leaves substituted (host_enc dropped if any
        leaf changed shape is the caller's concern; sizes stay as declared)."""
        return replace(self, **leaves)

    def slice_blocks(self, start: int, stop: int, *,
                     pad_to: int | None = None) -> "CompressedIntArray":
        """Contiguous block range ``[start, stop)`` as a new array.

        Blocks decode independently (per-block ``counts``/``bases`` carry
        all cross-block state), so any contiguous range is itself a valid
        compressed array — this is what the inverted index's skip-table
        pruning decodes instead of whole posting lists (repro.index.query).
        ``pad_to`` appends count-0 blocks up to a fixed block count so
        pruned decodes hit a bounded set of jitted shapes. Host-side
        (numpy) slicing; ``host_enc`` is dropped.
        """
        return self.take_blocks(np.arange(start, stop), pad_to=pad_to)

    def take_blocks(self, blocks, *, pad_to: int | None = None
                    ) -> "CompressedIntArray":
        """Arbitrary block subset (row gather) as a new array.

        Like :meth:`slice_blocks` but for a non-contiguous block set —
        what skip-table pruning decodes when the probe set is spread out:
        only blocks whose docid range contains a probe are gathered, in
        order, everything else is never decoded. ``pad_to`` appends
        count-0 blocks to a fixed block count (bounded jitted shapes).
        """
        idx = np.asarray(blocks, dtype=np.int64).reshape(-1)
        names = FORMAT_LEAVES[self.format]
        leaves = {}
        for nm in names:
            a = np.asarray(getattr(self, nm))[idx]
            if pad_to is not None and a.shape[0] < pad_to:
                pad = ((0, pad_to - a.shape[0]),) + ((0, 0),) * (a.ndim - 1)
                a = np.pad(a, pad)
            leaves[nm] = a
        cs = self.checksums
        if cs is not None:
            cs = np.asarray(cs)[idx]  # count-0 pad blocks checksum to 0
            if pad_to is not None and cs.shape[0] < pad_to:
                cs = np.pad(cs, ((0, pad_to - cs.shape[0]),))
        return replace(self, host_enc=None, checksums=cs,
                       n=int(leaves["counts"].sum()), **leaves)

    # -- decoding ------------------------------------------------------------
    def decode_blocked(self, *, plan="auto"):
        """Decode on device to the padded uint32[n_blocks, block_size] grid.

        ``plan`` is a dispatch plan name or ``DecodePlan``
        (``repro.kernels.vbyte_decode.dispatch``): ``"auto"`` is the
        backend's default route for the format (``dispatch.default_plan``),
        ``"kernel"``/``"jnp"`` force the Pallas / pure-jnp path,
        ``"sharded"`` forces the block-parallel ``shard_map`` path
        (auto-selected anyway when the operands are sharded).
        """
        from repro.kernels.vbyte_decode import dispatch

        return dispatch.decode(self, plan=plan)

    def decode(self, *, use_kernel: bool | None = None, plan="auto",
               check: bool = False) -> np.ndarray:
        """Decode to uint32[n] (host-visible).

        ``use_kernel`` is the deprecated legacy boolean (True → Pallas
        kernel, False → jnp decoder); it maps onto the dispatch plan and
        emits a ``DeprecationWarning``. Use ``plan=``.

        ``check=True`` decodes through the fused ``checksum`` epilogue and
        verifies the per-block column written by ``encode(checksum=True)``
        in the same tile pass, raising
        :class:`repro.robustness.validate.ChecksumError` (with block
        coordinates) on mismatch — see docs/robustness.md.
        """
        if use_kernel is not None:
            plan = warn_use_kernel(use_kernel)
        if check:
            from repro.robustness.validate import decode_checked

            grid = np.asarray(decode_checked(self, plan=plan))
        else:
            grid = np.asarray(self.decode_blocked(plan=plan))
        # concatenate each block's valid prefix. (Not a flat [:n] trim —
        # that silently corrupts outputs when a partial block precedes a
        # full one, as a non-contiguous take_blocks gather can produce.)
        mask = (np.arange(self.block_size)[None, :]
                < np.asarray(self.counts)[:, None])
        return grid[mask].astype(np.uint32)

    def decode_scalar_oracle(self) -> np.ndarray:
        """Byte-at-a-time reference decode (slow; tests/benchmarks only)."""
        if self.format == "streamvbyte":
            out = svb.decode_blocked_scalar(
                np.asarray(self.control),
                np.asarray(self.data),
                np.asarray(self.counts),
                np.asarray(self.bases),
                self.block_size,
                differential=self.differential,
            )
        elif self.format == "binpack":
            out = bpk.decode_blocked_scalar(
                np.asarray(self.widths),
                np.asarray(self.data),
                np.asarray(self.counts),
                np.asarray(self.bases),
                self.block_size,
                differential=self.differential,
            )
        else:
            out = vref.decode_blocked_scalar(
                np.asarray(self.payload),
                np.asarray(self.counts),
                np.asarray(self.bases),
                self.block_size,
                differential=self.differential,
            )
        # concatenate valid prefixes (same rule as decode(): partial blocks
        # may precede full ones, e.g. in optimally-partitioned arrays)
        mask = (np.arange(self.block_size)[None, :]
                < np.asarray(self.counts)[:, None])
        return out[mask].astype(np.uint32)


jax.tree_util.register_pytree_with_keys(
    CompressedIntArray,
    CompressedIntArray.tree_flatten_with_keys,
    CompressedIntArray.tree_unflatten,
)
