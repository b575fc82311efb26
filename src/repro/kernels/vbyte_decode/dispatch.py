"""Autotuned dispatch for the blocked decode kernels (both formats).

Single entry point (:func:`decode`) that picks the execution plan — Pallas
kernel vs vectorized jnp decoder, fused vs unfused epilogue, ``block_tile``
— replacing the ad-hoc ``use_kernel`` booleans that used to be threaded
through ``compressed_array.py``, ``models/recsys.py`` and ``nn/gnn.py``.

A :class:`DecodePlan` names one concrete path:

* ``path="pallas"`` — the Pallas kernels (Mosaic on TPU, interpret on CPU).
* ``path="jnp"``    — the vectorized jnp decoders (XLA-CPU SIMD proxy).
* ``fused=True``    — decode and consumer epilogue run as ONE program: the
  fused Pallas kernel on TPU, or a single jit (one XLA executable, no
  materialized id-stream round-trip between dispatches) on CPU.
* ``fused=False``   — two programs: decode the ``uint32 [n_blocks, B]``
  grid, then apply the epilogue in a second dispatch (the legacy shape of
  every call site before this layer existed).

``plan="auto"`` consults a small measured autotune cache persisted under
``experiments/autotune.json`` (:func:`autotune` populates it; run via
``python -m benchmarks.run --only fused``). With no cache entry the
heuristic default is the fused path on the current backend. Legacy string
plans keep old call sites working: ``"kernel"`` → Pallas, ``"jnp"`` → jnp,
``"fused"``/``"unfused"`` force fusion on the default path.

**Sharded block-parallel decode.** Because every block decodes
independently (per-block ``counts``/``bases`` carry all cross-block
state), a compressed stream whose block dimension is placed across a mesh
axis (``CompressedIntArray.shard(mesh, axis="data")``) decodes where it
lives: :func:`decode` detects block-sharded operands and runs the chosen
single-device plan **per shard** under ``shard_map`` — same decode-tile
code, zero cross-device decode traffic, so the sharded result is bit-exact
with the single-device path by construction (fused epilogues included:
each block's bag/score/rebase output is block-local). ``plan="sharded"``
forces this path (raises if the operands aren't sharded); otherwise it is
auto-selected. Detection needs concrete arrays — call :func:`decode`
outside any enclosing ``jit`` (it jits internally) to use it.

**Per-signature launch cache.** Everything :func:`decode` resolves before
it launches (operand checks, epilogue checks, plan resolution, metadata
shape checks, mesh detection) is a pure function of the call's signature,
so the first call of a signature stores one entry and every later call
runs only that entry: one key, one lookup, one program call.

* *Key:* the static aux (format, block size, differential), each format
  leaf's and each extra's name, shape, dtype and sharding, the epilogue,
  the ``plan`` argument, ``interpret``, the autotune cache's path
  (``REPRO_AUTOTUNE_CACHE``) and a generation number. Leaves are read off
  a ``CompressedIntArray`` by ``getattr``, with no ``jnp.asarray``.
* *Entry:* no arrays, only what the signature fixed: the span attributes
  and one callable over the leaves and extras with everything static bound
  in. A single-program plan is one ``jit`` of the whole call, with the
  ``[n_blocks, 1]`` → ``[n_blocks]`` metadata normalisation inside it; an
  unfused plan keeps its two programs; block-sharded operands run
  ``_build_sharded_fn``'s program.
* *Bypass:* a call whose leaves or extras are not all concrete
  ``jax.Array``s (numpy input, tracers under an enclosing ``jit``) takes
  the full path and stores nothing, as does a call that raises: a
  malformed call raises on every call.
* *Invalidation:* reloading the autotune cache (``load_cache(reload=True)``,
  which :func:`autotune` ends with, or a switch of its path) bumps the
  generation and drops every entry.
* *Bound:* an LRU of :data:`LAUNCH_CACHE_SIZE` entries.

``decode_fastpath_total{result=hit|miss|bypass}`` counts which path each
call took (only while telemetry is installed).
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
from collections import OrderedDict
from collections.abc import Callable
from dataclasses import asdict, dataclass, replace

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.obs import counter_inc as _obs_counter_inc, trace as _obs_trace

from repro.core.compressed_array import FORMAT_LEAVES, CompressedIntArray
from repro.core.vbyte import binpack_masked as bpk_masked
from repro.core.vbyte import masked as vmasked
from repro.core.vbyte import stream_masked as svb_masked

from . import epilogues as eplib
from .ops import (binpack_decode_blocked, normalize_block_meta,
                  stream_vbyte_decode_blocked, vbyte_decode_blocked)

# cache lives under the repo's experiments/ dir (resolved relative to this
# file, NOT the process cwd — library call sites run from anywhere); the
# REPRO_AUTOTUNE_CACHE env var overrides. Falls back to a cwd-relative path
# when the source tree layout isn't present (installed package).
_SRC_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))  # <repo>/src in-tree
DEFAULT_CACHE_PATH = (
    os.path.join(os.path.dirname(_SRC_DIR), "experiments", "autotune.json")
    if os.path.basename(_SRC_DIR) == "src"
    else "experiments/autotune.json")

# broadcast epilogue operands (bag_sum's embedding table, probe sets) above
# this size cannot be VMEM-resident per grid step on TPU; the fused Pallas
# plan falls back to pallas-decode + jnp epilogue (docs/kernels.md §TPU
# notes). dot_score's table is not broadcast: its kernel fetches rows from
# HBM, so it runs fused at every table size.
VMEM_BROADCAST_BUDGET = 4 << 20


@dataclass(frozen=True)
class DecodePlan:
    """One concrete decode execution plan (see module docstring).

    ``chunk`` is the banded-scatter chunk width W: ``None`` runs the
    format's unchunked routing (VPU compaction for vbyte, the dense O(S·B)
    one-hot for streamvbyte), an integer W the chunked O(S·W) MXU routing
    (see ``banded.py``). On the Pallas path it selects the banded tile cores;
    on the jnp path the chunked prefix decomposition of the vectorized
    decoders. Both produce bit-identical uint32 grids, so the axis is a
    pure perf knob — which is why it lives on the autotuned plan.
    """

    path: str  # "pallas" | "jnp" | "ref" (gather-lowered; GSPMD-friendly)
    fused: bool = True
    block_tile: int = 8
    chunk: int | None = None  # W; None = the format's unchunked route

    def __post_init__(self):
        if self.path not in ("pallas", "jnp", "ref"):
            raise ValueError(f"unknown plan path {self.path!r}")
        if self.chunk is not None and (self.chunk <= 0 or self.chunk % 8):
            raise ValueError(
                f"plan chunk width must be a positive multiple of 8 or "
                f"None; got {self.chunk!r}")

    @property
    def label(self) -> str:
        return f"{self.path}{'_fused' if self.fused else '_unfused'}" \
               + (f"_bt{self.block_tile}" if self.path == "pallas" else "") \
               + (f"_w{self.chunk}" if self.chunk is not None else "")


# ---------------------------------------------------------------------------
# plan resolution + persisted autotune cache
# ---------------------------------------------------------------------------
_CACHE: dict | None = None
_CACHE_FILE: str | None = None

# Autotune-cache schema version. Bumped to 2 when "binpack" became a third
# format: older caches were measured in a two-format world (candidate sets,
# default chunk widths, and cost trade-offs that no longer hold) and carry
# no schema tag at all, so version-mismatched entries are dropped on load
# and the plan resolver falls back to the heuristic default instead of
# mis-resolving from a stale measurement.
CACHE_SCHEMA = 2


def cache_path() -> str:
    return os.environ.get("REPRO_AUTOTUNE_CACHE", DEFAULT_CACHE_PATH)


def cache_key(format: str, epilogue: str, block_size: int,
              backend: str | None = None) -> str:
    backend = backend or jax.default_backend()
    return f"{backend}/{format}/{epilogue}/bs{block_size}"


def _migrate_cache(raw: dict) -> dict:
    """Drop entries from a different (or missing) schema version."""
    if not isinstance(raw, dict):
        return {}
    return {k: v for k, v in raw.items()
            if isinstance(v, dict) and v.get("schema") == CACHE_SCHEMA}


def load_cache(path: str | None = None, *, reload: bool = False) -> dict:
    global _CACHE, _CACHE_FILE
    path = path or cache_path()
    if _CACHE is None or _CACHE_FILE != path or reload:
        if _CACHE is not None:
            _invalidate_launches()  # their plans came from the old cache
        _CACHE_FILE = path
        try:
            with open(path) as f:
                _CACHE = _migrate_cache(json.load(f))
        except (OSError, ValueError):
            _CACHE = {}
    return _CACHE


# per-format banded chunk width (plan="banded", autotune candidates): the
# smallest W that clears the ≥4x modeled routing-MAC reduction at default
# shapes without shrinking the MXU tiles below usefulness (docs/kernels.md
# §Banded chunked scatter).
BANDED_CHUNK = {"vbyte": 64, "streamvbyte": 32}
# per-format default chunk width of the TPU plan. vbyte routes by VPU
# compaction, which has no chunk axis (docs/kernels.md §Compaction
# routing); binpack has no length scan, so no chunk axis either.
DEFAULT_CHUNK = {"vbyte": None, "streamvbyte": BANDED_CHUNK["streamvbyte"],
                 "binpack": None}


def default_plan(epilogue: str = "stream",
                 format: str = "vbyte") -> DecodePlan:
    """Heuristic when the cache has no measurement for a workload."""
    if jax.default_backend() == "tpu":
        return DecodePlan("pallas", fused=True, block_tile=8,
                          chunk=DEFAULT_CHUNK.get(format, 64))
    # CPU proxy: interpret-mode Pallas is a correctness path, not a perf
    # path; the jnp decoders vectorize through XLA-CPU. Fusion still wins
    # (one executable, no id-stream round-trip) — see benchmarks.json.
    return DecodePlan("jnp", fused=True)


def _clamp_chunk(chunk: int | None, block_size: int) -> int | None:
    """Shrink a heuristic chunk width to the workload's block size (a band
    can't be wider than the output row); None when no multiple of 8 fits."""
    if chunk is None or chunk <= block_size:
        return chunk
    clamped = (block_size // 8) * 8
    return clamped or None


def resolve_plan(plan, *, format: str, epilogue: str,
                 block_size: int) -> DecodePlan:
    if isinstance(plan, DecodePlan):
        return plan
    if plan in (None, "auto"):
        entry = load_cache().get(cache_key(format, epilogue, block_size))
        if entry and "plan" in entry:
            _obs_counter_inc("plan_cache_total", result="hit")
            p = entry["plan"]
            return DecodePlan(p["path"], p["fused"], p.get("block_tile", 8),
                              p.get("chunk"))
        _obs_counter_inc("plan_cache_total", result="miss")
        d = default_plan(epilogue, format)
        return replace(d, chunk=_clamp_chunk(d.chunk, block_size))
    if plan in ("kernel", "pallas"):
        return DecodePlan("pallas", fused=True)
    if plan == "jnp":
        return DecodePlan("jnp", fused=True)
    if plan == "ref":
        return DecodePlan("ref", fused=False)
    if plan == "fused":
        return DecodePlan(default_plan(epilogue, format).path, fused=True)
    if plan == "unfused":
        return DecodePlan(default_plan(epilogue, format).path, fused=False)
    if plan == "banded":
        return replace(default_plan(epilogue, format),
                       chunk=_clamp_chunk(BANDED_CHUNK.get(format),
                                          block_size))
    if plan == "dense":
        return replace(default_plan(epilogue, format), chunk=None)
    raise ValueError(
        f"unknown plan {plan!r}; expected a DecodePlan or one of "
        "'auto', 'kernel', 'pallas', 'jnp', 'fused', 'unfused', "
        "'banded', 'dense'")


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------
def _decode_grid(operands: dict, *, format: str, block_size: int,
                 differential: bool, plan: DecodePlan,
                 interpret: bool | None = None) -> jax.Array:
    """Step-1 decode to the uint32 [n_blocks, block_size] grid."""
    if plan.path == "pallas":
        fn = {"vbyte": vbyte_decode_blocked,
              "streamvbyte": stream_vbyte_decode_blocked,
              "binpack": binpack_decode_blocked}[format]
        return fn(**operands, block_size=block_size, differential=differential,
                  block_tile=plan.block_tile, chunk_width=plan.chunk,
                  interpret=interpret)
    if plan.path == "ref":
        if format != "vbyte":
            raise ValueError(
                "plan path 'ref' (the gather-lowered decoder) only exists "
                f"for format='vbyte'; got {format!r} — stream_masked is "
                "already gather-based, use path 'jnp'")
        # gather-lowered decoder: the scatter-based masked path emits a
        # cross-shard scatter-add under GSPMD; the searchsorted/gather
        # lowering stays block-local (§Perf retrieval iteration 2)
        from .ref import vbyte_decode_blocked_ref

        return vbyte_decode_blocked_ref(
            **operands, block_size=block_size, differential=differential)
    dec = {"vbyte": vmasked.decode_blocked,
           "streamvbyte": svb_masked.decode_blocked,
           "binpack": bpk_masked.decode_blocked}[format]
    return dec(**operands, block_size=block_size, differential=differential,
               chunk_width=plan.chunk)


@functools.partial(
    jax.jit, static_argnames=("format", "epilogue", "block_size",
                              "differential", "chunk_width")
)
def _jnp_fused(operands: dict, extras: dict, *, format: str, epilogue: str,
               block_size: int, differential: bool,
               chunk_width: int | None = None):
    """Fused CPU path: decode + epilogue in ONE XLA executable.

    The optimization barrier pins the decoded grid as a fusion boundary:
    without it XLA-CPU may inline the whole decode into the epilogue's
    gather-index computation (producer recompute), which is slower than
    keeping the grid as an in-executable intermediate. The grid still never
    crosses a dispatch boundary — that round trip is what fusion removes.
    """
    dec = {"vbyte": vmasked.decode_blocked,
           "streamvbyte": svb_masked.decode_blocked,
           "binpack": bpk_masked.decode_blocked}[format]
    grid = dec(**operands, block_size=block_size, differential=differential,
               chunk_width=chunk_width)
    grid = lax.optimization_barrier(grid)
    return eplib.apply_grid(epilogue, grid, operands["counts"], extras)


@functools.partial(jax.jit, static_argnames=("epilogue",))
def _apply_only(grid: jax.Array, counts: jax.Array, extras: dict, *,
                epilogue: str):
    """Unfused step 2: the epilogue as its own dispatch (reference shape)."""
    return eplib.apply_grid(epilogue, grid, counts, extras)


def _run_plan(extras: dict, *, epilogue: str,
              plan: DecodePlan) -> tuple[DecodePlan, bool]:
    """The plan :func:`_execute` runs for these extras, and whether it is a
    downgrade: broadcast extras must be VMEM-resident per grid step, so past
    the budget a fused Pallas plan degrades to pallas-decode + jnp epilogue
    instead of failing Mosaic compilation (docs/kernels.md §TPU notes).
    Extras the kernel reads from HBM (dot_score's table) are not broadcast.
    Reads only the extras' shapes and dtypes."""
    if not (plan.path == "pallas" and plan.fused) or epilogue == "stream":
        return plan, False
    ep = eplib.get_epilogue(epilogue)
    broadcast_bytes = sum(
        int(np.prod(v.shape)) * v.dtype.itemsize
        for k, v in extras.items()
        if k not in ep.tiled_extras and k not in ep.hbm_extras)
    if broadcast_bytes <= VMEM_BROADCAST_BUDGET:
        return plan, False
    return DecodePlan("pallas", fused=False, block_tile=plan.block_tile,
                      chunk=plan.chunk), True


def _execute(operands: dict, extras: dict, *, format: str, epilogue: str,
             block_size: int, differential: bool, plan: DecodePlan,
             interpret: bool | None = None):
    """Run one resolved plan on (already validated/normalized) operands.

    This is the single-device execution body; the sharded path runs exactly
    this function per shard under ``shard_map``, which is what makes the
    sharded decode bit-exact with the single-device one by construction.
    """
    if epilogue == "stream":
        return _decode_grid(operands, format=format, block_size=block_size,
                            differential=differential, plan=plan,
                            interpret=interpret)

    plan, downgraded = _run_plan(extras, epilogue=epilogue, plan=plan)
    if downgraded:
        _obs_counter_inc("decode_plan_downgrade_total", epilogue=epilogue,
                         reason="vmem_broadcast_budget")
    if plan.path == "pallas" and plan.fused:
        return eplib.fused_decode(
            operands, extras, format=format, epilogue=epilogue,
            block_size=block_size, differential=differential,
            block_tile=plan.block_tile, chunk_width=plan.chunk,
            interpret=interpret)
    if plan.path == "jnp" and plan.fused:
        return _jnp_fused(operands, extras, format=format, epilogue=epilogue,
                          block_size=block_size, differential=differential,
                          chunk_width=plan.chunk)
    # unfused: decode grid, then the epilogue as a second dispatch
    grid = _decode_grid(operands, format=format, block_size=block_size,
                        differential=differential, plan=plan,
                        interpret=interpret)
    return _apply_only(grid, operands["counts"], extras, epilogue=epilogue)


# ---------------------------------------------------------------------------
# sharded block-parallel execution (shard_map over the block dimension)
# ---------------------------------------------------------------------------
def operand_mesh_axes(operands: dict):
    """``(mesh, block_axes)`` when every operand's block dim is sharded over
    a >1-device mesh axis with ``NamedSharding``; ``None`` otherwise.

    Tracers (operands seen under an enclosing ``jit``) have no concrete
    sharding — detection then returns ``None`` and the single-device body
    runs, which GSPMD partitions as usual.
    """
    mesh = None
    axes = None
    for v in operands.values():
        try:
            sh = v.sharding
        except Exception:
            return None
        if not isinstance(sh, NamedSharding):
            return None
        spec = tuple(sh.spec) + (None,) * (v.ndim - len(sh.spec))
        a = spec[0]
        a = (a,) if isinstance(a, str) else tuple(a or ())
        if any(x is not None for x in spec[1:]):
            return None  # only block-dim sharding is block-parallel-safe
        if mesh is None:
            mesh, axes = sh.mesh, a
        elif sh.mesh != mesh or a != axes:
            return None
    if mesh is None or not axes:
        return None
    n_shards = 1
    for name in axes:
        n_shards *= mesh.shape[name]
    return (mesh, axes) if n_shards > 1 else None


@functools.lru_cache(maxsize=128)
def _build_sharded_fn(mesh, axes: tuple, format: str, epilogue: str,
                      block_size: int, differential: bool, plan: DecodePlan,
                      interpret: bool | None, multi_query: bool,
                      extra_keys: tuple = ()):
    """jit(shard_map(execute-body)) for one (mesh, workload) — cached so
    repeated serving calls reuse one trace. Exposed for tests (the compiled
    HLO must contain no cross-device collectives). ``extra_keys`` is the
    actual epilogue-operand key set for this call (epilogues with optional
    operands, e.g. the format-tagged weight streams, vary it)."""
    ep = eplib.get_epilogue(epilogue)
    spec_block = P(axes, None)
    in_operands = {k: spec_block for k in eplib.FORMAT_OPERANDS[format]}
    in_operands.update(counts=P(axes), bases=P(axes))
    in_extras = {k: (spec_block if k in ep.tiled_extras else P())
                 for k in extra_keys}
    if epilogue == "dot_score":
        out_specs = (spec_block,
                     P(None, axes, None) if multi_query else spec_block)
    elif epilogue == "checksum":
        # (decoded grid, per-block checksum column) — both block-leading
        out_specs = (spec_block, spec_block)
    else:
        # stream / bag_sum / adjacency_rebase / membership / bm25_accum:
        # one [nb, ·] output whose leading dim is the block dim
        out_specs = spec_block

    body = functools.partial(
        _execute, format=format, epilogue=epilogue, block_size=block_size,
        differential=differential, plan=plan, interpret=interpret)
    return jax.jit(jax.shard_map(
        lambda operands, extras: body(operands, extras),
        mesh=mesh, in_specs=(in_operands, in_extras), out_specs=out_specs,
        check_vma=False))


# ---------------------------------------------------------------------------
# per-signature launch cache
# ---------------------------------------------------------------------------
# Everything decode() resolves before it launches is a pure function of the
# call's signature (see _signature), so a repeat call runs a stored entry.
LAUNCH_CACHE_SIZE = 256
_LAUNCHES: OrderedDict = OrderedDict()
_LAUNCHES_LOCK = threading.Lock()
_GENERATION = 0  # bumped whenever the autotune cache is (re)loaded


@dataclass(frozen=True)
class _Launch:
    """One cache entry: no arrays, only what the signature fixed."""

    fn: Callable  # takes the format leaves, then the extras, in key order
    attrs: dict  # the ``decode`` span's attributes


def _invalidate_launches():
    global _GENERATION
    with _LAUNCHES_LOCK:
        _GENERATION += 1
        _LAUNCHES.clear()


def _concrete(x) -> bool:
    return isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)


def _signature(operands, format, block_size, differential, epilogue,
               epilogue_operands, plan, interpret):
    """``(key, leaves)`` of a call the launch cache can serve, else None.

    The key holds everything prepare reads: the static aux, each format
    leaf's and each extra's shape, dtype and sharding, the epilogue, the
    plan argument, ``interpret``, the autotune cache's path and generation.
    Served only when every leaf and extra is a concrete ``jax.Array``:
    numpy input and tracers under an enclosing ``jit`` take the full path.
    """
    if type(operands) is CompressedIntArray:
        arr_format = operands.format
        if format is None:
            format = arr_format
        elif format != arr_format:
            return None
        if block_size is None:
            block_size = operands.block_size
        if differential is None:
            differential = operands.differential
        names = FORMAT_LEAVES.get(format)
        if names is None:
            return None
        leaves = tuple(getattr(operands, n) for n in names)
    elif type(operands) is dict:
        names = FORMAT_LEAVES.get(format)
        if names is None or block_size is None or differential is None:
            return None
        try:
            leaves = tuple(operands[n] for n in names)
        except KeyError:
            return None
    else:
        return None
    extras = epilogue_operands or {}
    if type(extras) is not dict:
        return None
    leaves += tuple(extras.values())
    if not all(map(_concrete, leaves)):
        return None
    if not (plan is None or type(plan) is str or type(plan) is DecodePlan):
        return None
    key = (_GENERATION, cache_path(), format, block_size, differential,
           epilogue, plan, interpret, tuple(extras),
           tuple((x.shape, x.dtype, x.sharding) for x in leaves))
    return key, leaves


def _store(key, entry: _Launch):
    with _LAUNCHES_LOCK:
        if key[0] != _GENERATION:
            return  # resolved against an autotune cache since replaced
        _LAUNCHES[key] = entry
        while len(_LAUNCHES) > LAUNCH_CACHE_SIZE:
            _LAUNCHES.popitem(last=False)


def _lookup(key) -> _Launch | None:
    entry = _LAUNCHES.get(key)
    if entry is not None:
        try:
            _LAUNCHES.move_to_end(key)
        except KeyError:  # evicted meanwhile by another thread
            pass
    return entry


def _normalized(fmt_keys: tuple, leaves: tuple) -> dict:
    """The format operands with ``[nb, 1]`` counts/bases made ``[nb]``: no
    op at all for ``[nb]`` metadata, and none per call inside a program."""
    operands = dict(zip(fmt_keys, leaves))
    nb = leaves[0].shape[0]
    operands["counts"] = normalize_block_meta("counts", operands["counts"],
                                              nb)
    operands["bases"] = normalize_block_meta("bases", operands["bases"], nb)
    return operands


def _resolve(operands, format, block_size, differential, epilogue,
             epilogue_operands, plan, interpret) -> tuple[_Launch, tuple]:
    """The full prepare: unwrap the operands, check them, resolve the plan,
    detect the mesh. Returns the call's entry and its leaves (the format
    operands, then the extras)."""
    if isinstance(operands, CompressedIntArray):
        arr = operands
        operands = arr.device_operands()
        format = arr.format if format is None else format
        block_size = arr.block_size if block_size is None else block_size
        differential = (arr.differential if differential is None
                        else differential)
    if format is None or block_size is None or differential is None:
        raise ValueError(
            "format=/block_size=/differential= are required when "
            "operands are a raw dict (pass a CompressedIntArray to "
            "omit them)")
    if format not in eplib.FORMAT_OPERANDS:
        raise ValueError(f"unknown format {format!r}; expected one "
                         f"of {tuple(eplib.FORMAT_OPERANDS)}")
    ep = eplib.get_epilogue(epilogue)
    extras = dict(epilogue_operands or {})
    ep.check(differential, extras)
    force_sharded = plan == "sharded"
    p = resolve_plan("auto" if force_sharded else plan, format=format,
                     epilogue=epilogue, block_size=block_size)

    fmt_keys = FORMAT_LEAVES[format]
    missing = [k for k in fmt_keys if k not in operands]
    if missing:
        raise ValueError(f"format {format!r} operands missing {missing}")
    nb = operands[fmt_keys[0]].shape[0]
    operands = {k: operands[k] for k in fmt_keys}
    operands["counts"] = normalize_block_meta("counts", operands["counts"],
                                              nb)
    operands["bases"] = normalize_block_meta("bases", operands["bases"], nb)

    mesh_axes = operand_mesh_axes(operands)
    extra_keys = tuple(extras)
    if mesh_axes is not None:
        mesh, axes = mesh_axes
        q = extras["query"] if epilogue == "dot_score" else None
        multi_query = bool(q is not None and q.size // q.shape[-1] > 1)
        sharded = _build_sharded_fn(mesh, axes, format, epilogue, block_size,
                                    differential, p, interpret, multi_query,
                                    tuple(sorted(extras)))
        n = len(fmt_keys)

        def fn(*leaves):
            return sharded(_normalized(fmt_keys, leaves[:n]),
                           dict(zip(extra_keys, leaves[n:])))
    elif force_sharded:
        raise ValueError(
            "plan='sharded' requires operands whose block dimension "
            "is sharded over a >1-device mesh axis — use "
            "CompressedIntArray.shard(mesh, axis=...) first")
    else:
        run, downgraded = _run_plan(extras, epilogue=epilogue, plan=p)
        fn = _program(fmt_keys, extra_keys, format, block_size, differential,
                      epilogue, run, downgraded, interpret)
    attrs = dict(format=format, plan=p.label, epilogue=epilogue,
                 blocks=int(nb), chunk=p.chunk, sharded=mesh_axes is not None)
    return _Launch(fn, attrs), (*operands.values(), *extras.values())


@functools.lru_cache(maxsize=128)
def _program(fmt_keys: tuple, extra_keys: tuple, format: str,
             block_size: int, differential: bool, epilogue: str,
             run: DecodePlan, downgraded: bool, interpret: bool | None):
    """One callable over the leaves, then the extras, with everything
    static bound in. A single-program plan is one jit of the whole call,
    metadata normalisation included; an unfused plan keeps its two
    programs. Cached by statics alone, so the entries of every shape share
    one jit (and its compiled executables), also across evictions."""
    n = len(fmt_keys)
    static = dict(format=format, block_size=block_size,
                  differential=differential, interpret=interpret)
    if epilogue == "stream" or (run.fused and run.path in ("pallas", "jnp")):
        def call(*leaves):
            return _execute(_normalized(fmt_keys, leaves[:n]),
                            dict(zip(extra_keys, leaves[n:])),
                            epilogue=epilogue, plan=run, **static)
        call.__name__ = f"decode_{format}_{epilogue}"
        return jax.jit(call)

    def grid_call(*leaves):
        return _decode_grid(_normalized(fmt_keys, leaves), plan=run,
                            **static)
    grid_call.__name__ = f"decode_{format}_grid"
    grid = jax.jit(grid_call)
    counts_at = fmt_keys.index("counts")

    def fn(*leaves):
        if downgraded:
            _obs_counter_inc("decode_plan_downgrade_total",
                             epilogue=epilogue,
                             reason="vmem_broadcast_budget")
        return _apply_only(grid(*leaves[:n]), leaves[counts_at],
                           dict(zip(extra_keys, leaves[n:])),
                           epilogue=epilogue)
    return fn


def decode(
    operands,  # CompressedIntArray, or device_operands()-style dict
    *,
    format: str | None = None,
    block_size: int | None = None,
    differential: bool | None = None,
    epilogue: str = "stream",
    epilogue_operands: dict | None = None,
    plan: DecodePlan | str | None = "auto",
    interpret: bool | None = None,
):
    """Decode a blocked compressed stream, optionally fused into a consumer.

    ``operands`` is either a ``CompressedIntArray`` (format/block_size/
    differential come from its static aux data) or the raw operand dict
    (``payload`` | ``control``/``data`` + ``counts``/``bases``), in which
    case the three metadata kwargs are required.

    Returns the epilogue's output: the ``uint32 [n_blocks, block_size]``
    grid for ``epilogue="stream"``, ``[n_blocks, d]`` bag sums for
    ``"bag_sum"``, ``(ids, scores)`` for ``"dot_score"`` (float32 scores
    ``[n_blocks, block_size]`` for one query, ``[n_queries, n_blocks,
    block_size]`` for a query matrix), rebased edge ids for
    ``"adjacency_rebase"``.

    When the operands' block dimension is sharded over a >1-device mesh
    axis (``CompressedIntArray.shard``), the plan runs per shard under
    ``shard_map`` — block-parallel, no cross-device decode traffic.
    ``plan="sharded"`` forces that path and raises if operands aren't
    sharded.

    A call on concrete device arrays whose signature was seen before runs
    the launch cache's entry (module docstring): one lookup, one program
    call. Counted by ``decode_fastpath_total{result=hit|miss|bypass}``.

    Traced as one ``decode`` span over the whole call with two children:
    ``decode.prepare`` (the cache key and lookup; on a miss or a bypass,
    operand unwrapping, checks, plan resolution, mesh detection) and
    ``decode.launch`` (the call into the jitted program, up to its return
    of the not-yet-ready output).
    """
    with _obs_trace("decode") as span:
        with _obs_trace("decode.prepare"):
            call = (operands, format, block_size, differential, epilogue,
                    epilogue_operands, plan, interpret)
            sig = _signature(*call)
            if sig is None:
                result = "bypass"
                entry, leaves = _resolve(*call)
            else:
                key, leaves = sig
                entry = _lookup(key)
                result = "hit"
                if entry is None:
                    result = "miss"
                    entry, _ = _resolve(*call)
            if span:
                attrs = entry.attrs
                span.set(**attrs)
                _obs_counter_inc("decode_calls_total", plan=attrs["plan"],
                                 format=attrs["format"], epilogue=epilogue)
                _obs_counter_inc("decode_fastpath_total", result=result)
        with _obs_trace("decode.launch"):
            out = entry.fn(*leaves)
        if result == "miss":
            _store(key, entry)
        return out


# ---------------------------------------------------------------------------
# measured autotune
# ---------------------------------------------------------------------------
def _time_call(fn, *, reps: int, warmup: int) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn())
    del out
    return (time.perf_counter() - t0) / reps


def _synthetic_workload(format: str, *, n_blocks: int, block_size: int,
                        vocab: int, d: int, seed: int):
    from repro.core.compressed_array import CompressedIntArray

    rng = np.random.default_rng(seed)
    n = n_blocks * block_size
    values = np.sort(rng.integers(0, vocab, size=n)).astype(np.uint64)
    arr = CompressedIntArray.encode(values, format=format,
                                    block_size=block_size, differential=True)
    operands = arr.device_operands()
    nb = arr.n_blocks
    probe = jnp.asarray(np.sort(rng.choice(vocab, size=min(128, vocab),
                                           replace=False))
                        .astype(np.int32)[None, :])
    # aligned per-posting weight stream (quantized impacts): same block
    # layout as the main array, non-differential, values < 2^8
    impacts = rng.integers(1, 256, size=n).astype(np.uint64)
    imp_arr = CompressedIntArray.encode(impacts, format=format,
                                        block_size=block_size,
                                        differential=False)
    w_ops = {f"w_{k}": v for k, v in imp_arr.device_operands().items()
             if k in ("payload", "control", "data", "widths")}
    extras = {
        "bag_sum": {"table": jnp.asarray(
            rng.standard_normal((vocab, d)).astype(np.float32))},
        "dot_score": {"table": jnp.asarray(
            rng.standard_normal((vocab, d)).astype(np.float32)),
            "query": jnp.asarray(
                rng.standard_normal((1, d)).astype(np.float32))},
        "adjacency_rebase": {"edge_base": jnp.asarray(
            rng.integers(0, vocab, (nb, block_size)).astype(np.int32))},
        "membership": {"probe": probe},
        "bm25_accum": {"probe": probe,
                       "impact": jnp.asarray([[7]], jnp.int32)},
        "membership_rows": {"probe": jnp.asarray(
            rng.integers(0, vocab, (nb, 1)).astype(np.int32))},
        "bm25_accum_rows": {"probe": jnp.asarray(
            rng.integers(0, vocab, (nb, 1)).astype(np.int32)),
            "impact": jnp.asarray([[7]], jnp.int32)},
        "bm25_weighted": {"probe": probe, **w_ops},
        "bm25_weighted_rows": {"probe": jnp.asarray(
            rng.integers(0, vocab, (nb, 1)).astype(np.int32)), **w_ops},
        "stream": {},
        "checksum": {},
    }
    return operands, extras, arr.bits_per_int


def autotune(
    *,
    formats=("vbyte", "streamvbyte", "binpack"),
    epilogue_names=("stream", "bag_sum", "dot_score", "adjacency_rebase",
                    "membership", "bm25_accum", "membership_rows",
                    "bm25_accum_rows", "bm25_weighted",
                    "bm25_weighted_rows", "checksum"),
    block_size: int = 128,
    n_blocks: int = 64,
    vocab: int = 4096,
    d: int = 64,
    reps: int = 5,
    warmup: int = 2,
    include_pallas: bool | None = None,
    cache_file: str | None = None,
    seed: int = 0,
) -> dict:
    """Measure candidate plans per (format, epilogue) and persist the best.

    On CPU the Pallas candidates run in interpret mode (orders of magnitude
    off their Mosaic speed), so they are excluded unless ``include_pallas``
    is forced — the CPU cache then records the jnp fused-vs-unfused choice,
    and a TPU run of the same function writes its own keys.
    """
    backend = jax.default_backend()
    if include_pallas is None:
        include_pallas = backend == "tpu"
    cache_file = cache_file or cache_path()
    cache = dict(load_cache(cache_file))

    for fmt in formats:
        operands, extras_by_ep, bits = _synthetic_workload(
            fmt, n_blocks=n_blocks, block_size=block_size, vocab=vocab, d=d,
            seed=seed)
        for ep_name in epilogue_names:
            if ep_name == "stream":
                # no consumer: fused vs unfused is the same program — the
                # decoder path, block tile and banded chunk width are the
                # real degrees of freedom
                w0 = BANDED_CHUNK.get(fmt)
                candidates = [DecodePlan("jnp", True),
                              DecodePlan("jnp", True, chunk=w0)]
                if fmt == "vbyte":
                    candidates.append(DecodePlan("ref", False))
                if include_pallas:
                    candidates += [DecodePlan("pallas", True, bt, chunk=w)
                                   for bt in (8, 16)
                                   for w in dict.fromkeys((None, 32, w0))]
                    # the banded cores' smaller one-hot/triangular VMEM
                    # footprint is what makes tiles past 8 blocks fit
                    candidates += [DecodePlan("pallas", True, 32, chunk=w0)]
            else:
                w0 = BANDED_CHUNK.get(fmt)
                candidates = [DecodePlan("jnp", True), DecodePlan("jnp", False),
                              DecodePlan("jnp", True, chunk=w0)]
                if include_pallas:
                    candidates += [DecodePlan("pallas", True, bt, chunk=w)
                                   for bt in (8, 16) for w in (None, w0)]
                    candidates += [DecodePlan("pallas", True, 32, chunk=w0),
                                   DecodePlan("pallas", False, 8)]
            # binpack has no chunk axis (BANDED_CHUNK has no width), which
            # collapses banded candidates onto their dense twins — dedupe
            candidates = list({c.label: c for c in candidates}.values())
            timings = {}
            for cand in candidates:
                fn = functools.partial(
                    decode, operands, format=fmt, block_size=block_size,
                    differential=True, epilogue=ep_name,
                    epilogue_operands=extras_by_ep[ep_name], plan=cand)
                timings[cand.label] = round(
                    _time_call(fn, reps=reps, warmup=warmup) * 1e3, 4)
            best = min(candidates, key=lambda c: timings[c.label])
            cache[cache_key(fmt, ep_name, block_size, backend)] = {
                "schema": CACHE_SCHEMA,
                "plan": asdict(best),
                "candidates_ms": timings,
                "backend": backend,
                "workload": {"n_blocks": n_blocks, "block_size": block_size,
                             "vocab": vocab, "d": d,
                             "bits_per_int": round(bits, 2)},
                "measured_at": time.strftime("%Y-%m-%d %H:%M:%S"),
            }

    os.makedirs(os.path.dirname(cache_file) or ".", exist_ok=True)
    with open(cache_file, "w") as f:
        json.dump(cache, f, indent=1, sort_keys=True)
    load_cache(cache_file, reload=True)
    return cache
