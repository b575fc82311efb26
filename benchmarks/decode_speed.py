"""Paper Fig. 2 reproduction: decode speed (million ints/s) by posting-list group.

ClueWeb-like synthetic posting lists grouped by length 2^K..2^{K+1}-1 (larger
K ⇒ smaller gaps ⇒ better compression ⇒ faster decode). Decoders compared:

  scalar   — Algorithm 1 as a jitted lax.while_loop (byte-serial, the
             conventional-decoder baseline of §V)
  masked   — the vectorized Masked-VByte adaptation (jitted, XLA-CPU SIMD)
  svb      — the vectorized Stream-VByte decoder on the same values encoded
             in the control-stream format (no continuation-bit recurrence)
  kernel   — the Pallas kernels in interpret mode (correctness path on CPU;
             their wall time is NOT meaningful — reported for completeness)

Both on-device formats are reported side by side per group: bits/int and
decode rate, so the compression-vs-throughput trade (docs/formats.md) is
visible in one table.

The paper reports 2-4× scalar→vectorized on x86; the same branch-free
restructuring yields the speedup here through XLA-CPU vectorization.
Includes the §V "decode to L1 buffer" experiment (--buffered): decoding in
4096-int blocks vs one full-stream decode.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.compressed_array import CompressedIntArray
from repro.core.vbyte import encode as venc
from repro.core.vbyte import masked as vmask
from repro.core.vbyte import ref as vref
from repro.data.synthetic import CLUEWEB_DOCS


def _bench(fn, *args, reps=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps, out


FORMATS = ("vbyte", "streamvbyte", "binpack")


def _format_decoder(fmt):
    """The jitted vectorized jnp decoder for one format."""
    if fmt == "vbyte":
        from repro.core.vbyte.masked import decode_blocked
    elif fmt == "streamvbyte":
        from repro.core.vbyte.stream_masked import decode_blocked
    else:
        from repro.core.vbyte.binpack_masked import decode_blocked
    return decode_blocked


def run(groups=(14, 16, 18, 20), n_ints: int = 1 << 18, reps: int = 8,
        universe: int = CLUEWEB_DOCS):
    rng = np.random.default_rng(7)
    rows = []
    for k in groups:
        # one long synthetic list with the gap statistics of group K:
        # list length 2^K over the 50M-doc universe => mean gap U / 2^K
        ids = np.sort(rng.choice(universe, size=n_ints, replace=False)).astype(np.uint64)
        scale = universe / (1 << k)  # rescale gaps to the group's statistics
        gaps = venc.delta_encode(ids)
        gaps = np.maximum((gaps.astype(np.float64) * scale / gaps.mean()), 1).astype(np.uint64)
        values = np.cumsum(gaps)
        n = len(values)

        # scalar Algorithm-1 (jitted while_loop) on the same data as a stream
        stream = venc.encode_stream(venc.delta_encode(values))
        sdata = jnp.asarray(np.concatenate([stream, np.zeros(8, np.uint8)]))
        scalar = jax.jit(lambda d: vref.decode_stream_scalar_jax(
            d, n, differential=True, nbytes=len(stream))[0])
        t_scalar, _ = _bench(scalar, sdata, reps=max(2, reps // 2), warmup=2)

        row = {"group_K": k, "scalar_mis": round(n / t_scalar / 1e6, 1),
               "formats": {}}
        for fmt in FORMATS:
            arr = CompressedIntArray.encode(values, format=fmt,
                                            differential=True)
            ops = arr.device_operands()
            dec = _format_decoder(fmt)
            t, _ = _bench(
                lambda: dec(**ops, block_size=128, differential=True),
                reps=reps, warmup=3)
            row["formats"][fmt] = {
                "bits_per_int": round(arr.bits_per_int, 2),
                "mis": round(n / t / 1e6, 1),
                "speedup_vs_scalar": round(t_scalar / t, 2),
            }
        rows.append(row)
    return rows


def _bench_interleaved(fns: dict, reps: int, warmup: int = 3) -> dict:
    """Min wall time per labelled thunk, rounds interleaved.

    Interleaving + min-of-samples instead of back-to-back means: the
    container's background load drifts on the scale of one measurement
    block, which otherwise swamps few-percent effects; the minimum is the
    standard noise-robust estimate of a computation's true cost.
    """
    for fn in fns.values():
        for _ in range(warmup):
            jax.block_until_ready(fn())
    samples = {k: [] for k in fns}
    for _ in range(reps):
        for k, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            samples[k].append(time.perf_counter() - t0)
    return {k: min(v) for k, v in samples.items()}


def run_fused(n_ints: int = 1 << 18, d: int = 8, vocab: int = 1 << 16,
              reps: int = 10) -> list[dict]:
    """Fused decode→consume epilogues vs the unfused two-dispatch chain.

    For each format and each fused workload (bag-sum embedding bag,
    dot-score retrieval, adjacency rebase), times the dispatch layer's
    ``fused`` plan (decode + consumer in ONE executable — on TPU the Pallas
    epilogue, on this CPU proxy a single XLA program where the decoded grid
    never crosses a dispatch boundary) against the ``unfused`` plan (decode
    the [n_blocks, 128] grid, then the same consumer as a second dispatch —
    the shape of every call site before the dispatch layer). Outputs are
    bit-identical by construction (same epilogue body); only the wall time
    differs.

    The default ``d=8`` keeps the consumer's table-gather traffic comparable
    to the decoded-stream round trip being removed; at large ``d`` the
    (path-independent) gather dominates both sides and the CPU proxy reads
    as noise. On TPU the fused margin widens with ``d`` instead, because the
    gathered [n, d] matrix also stays in VMEM (see docs/kernels.md).
    """
    from repro.kernels.vbyte_decode import dispatch

    rng = np.random.default_rng(11)
    values = np.sort(rng.integers(0, vocab, size=n_ints)).astype(np.uint64)
    table = jnp.asarray(rng.standard_normal((vocab, d)).astype(np.float32))
    query = jnp.asarray(rng.standard_normal((1, d)).astype(np.float32))

    rows = []
    for fmt in FORMATS:
        arr = CompressedIntArray.encode(values, format=fmt, differential=True)
        ops = arr.device_operands()
        nb = arr.n_blocks
        extras = {
            "bag_sum": {"table": table},
            "dot_score": {"table": table, "query": query},
            "adjacency_rebase": {"edge_base": jnp.asarray(
                rng.integers(0, vocab, (nb, 128)).astype(np.int32))},
        }
        def legacy_bag(eops=extras["bag_sum"]):
            # the pre-dispatch consumer chain for compressed bags: decode to a
            # host-visible id array (CompressedIntArray.decode returns numpy —
            # the decoded stream's full round trip), re-upload, gather+sum
            ids = jnp.asarray(arr.decode(plan="jnp"))
            grid = jnp.zeros(nb * 128, jnp.uint32).at[: ids.shape[0]].set(ids)
            from repro.kernels.vbyte_decode.dispatch import _apply_only

            return _apply_only(grid.reshape(nb, 128), ops["counts"], eops,
                               epilogue="bag_sum")

        for ep, eops in extras.items():
            fns = {
                plan: (lambda plan=plan, ep=ep, eops=eops: dispatch.decode(
                    ops, format=fmt, block_size=128, differential=True,
                    epilogue=ep, epilogue_operands=eops, plan=plan))
                for plan in ("fused", "unfused")
            }
            if ep == "bag_sum":
                fns["legacy_host"] = legacy_bag
            times = _bench_interleaved(fns, reps)
            row = {
                "format": fmt,
                "epilogue": ep,
                "n_ints": n_ints,
                "d": d,
                "reps": reps,
                "bits_per_int": round(arr.bits_per_int, 2),
                "fused_mis": round(arr.n / times["fused"] / 1e6, 1),
                "unfused_mis": round(arr.n / times["unfused"] / 1e6, 1),
                "fused_speedup": round(times["unfused"] / times["fused"], 2),
            }
            if ep == "bag_sum":
                row["legacy_host_mis"] = round(
                    arr.n / times["legacy_host"] / 1e6, 1)
                row["fused_speedup_vs_legacy"] = round(
                    times["legacy_host"] / times["fused"], 2)
            rows.append(row)
    return rows


def run_decode_cores(n_ints: int = 1 << 18, reps: int = 8,
                     chunk_widths=(32, 64, 128), block_size: int = 128,
                     interpret_blocks: int = 64) -> list[dict]:
    """Dense vs banded decode-tile cores on the jnp grid + cost model.

    The tracked decode-kernel perf trajectory (``--only decode``): for each
    format and chunk width the SAME tile-core code that runs inside the
    Pallas kernels is jitted over the full ``[n_blocks, S]`` grid (pure
    jnp — XLA-CPU here, XLA-TPU on device), timed against the unchunked
    core (``chunk_width=None``: compaction for vbyte, dense for the other
    formats), and paired with the modeled routing MACs / VMEM bytes of one
    ``[8, S]`` kernel tile (``banded.routing_cost``, whose ``W=None`` row
    models the dense core, not vbyte's compaction). Pallas interpret-mode
    rows are appended at
    a tiny size for coverage and tagged ``interpret: true`` — interpret
    wall time is a correctness artifact, not a perf number, and
    ``benchmarks/report.py`` excludes those rows from headline tables.
    """
    from repro.kernels.vbyte_decode import banded, ops
    from repro.kernels.vbyte_decode.binpack_kernel import binpack_decode_tile
    from repro.kernels.vbyte_decode.kernel import decode_tile, prefix_sum_tile
    from repro.kernels.vbyte_decode.stream_kernel import stream_decode_tile

    rng = np.random.default_rng(5)
    # sorted sample of the 50M-doc universe: dense low-width gap blocks
    # (block max width ~13-14 bits) — the binpack-favourable regime the
    # scoreboard tracks binpack tiles/sec ≥ streamvbyte on
    values = np.sort(rng.integers(0, CLUEWEB_DOCS, size=n_ints)).astype(np.uint64)
    B = block_size
    rows = []
    for fmt in FORMATS:
        arr = CompressedIntArray.encode(values, format=fmt, block_size=B,
                                        differential=True)
        od = arr.device_operands()
        counts2 = jnp.asarray(np.asarray(od["counts"]).reshape(-1, 1)
                              .astype(np.int32))
        bases2 = jax.lax.bitcast_convert_type(
            jnp.asarray(np.asarray(od["bases"]).reshape(-1, 1)
                        .astype(np.uint32)), jnp.int32)
        nb = arr.n_blocks
        if fmt == "vbyte":
            S = od["payload"].shape[1]
            fmt_args = (jnp.asarray(od["payload"]),)

            def make(core_w):
                @jax.jit
                def f(payload, counts, bases):
                    out, valid = decode_tile(payload, counts, block_size=B,
                                             chunk_width=core_w)
                    return prefix_sum_tile(out, valid, bases)
                return lambda: f(*fmt_args, counts2, bases2)
        elif fmt == "streamvbyte":
            S = od["data"].shape[1]
            fmt_args = (jnp.asarray(od["control"]), jnp.asarray(od["data"]))

            def make(core_w):
                @jax.jit
                def f(control, data, counts, bases):
                    out, valid = stream_decode_tile(control, data, counts,
                                                    block_size=B,
                                                    chunk_width=core_w)
                    return prefix_sum_tile(out, valid, bases)
                return lambda: f(*fmt_args, counts2, bases2)
        else:
            S = od["data"].shape[1]
            fmt_args = (jnp.asarray(np.asarray(od["widths"])
                                    .reshape(-1, 1).astype(np.uint8)),
                        jnp.asarray(od["data"]))

            def make(core_w):
                @jax.jit
                def f(w8, data, counts, bases):
                    out, valid = binpack_decode_tile(w8, data, counts,
                                                     block_size=B,
                                                     chunk_width=core_w)
                    return prefix_sum_tile(out, valid, bases)
                return lambda: f(*fmt_args, counts2, bases2)

        # binpack has no length scan — the chunk axis doesn't exist, so
        # only the dense core is measured for it
        widths = [None] + ([] if fmt == "binpack"
                           else [w for w in chunk_widths if w <= B])
        times = _bench_interleaved(
            {str(w): make(w) for w in widths}, reps)
        t_dense = times["None"]
        for w in widths:
            cost = banded.routing_cost(fmt, S=S, B=B, W=w, T=8)
            rows.append({
                "format": fmt,
                "path": "jnp-grid-core",
                "interpret": False,
                "chunk_width": w,
                "n_ints": n_ints,
                "blocks": nb,
                "stride": S,
                "block_size": B,
                "bits_per_int": round(arr.bits_per_int, 2),
                "tiles_per_s": round(nb / 8 / times[str(w)], 1),
                "mis": round(arr.n / times[str(w)] / 1e6, 1),
                "speedup_vs_dense": round(t_dense / times[str(w)], 2),
                "modeled_per_tile": {
                    "mxu_macs": cost["mxu_total"],
                    "vpu_ops": cost["vpu_total"],
                    "vmem_bytes": cost["vmem_total"],
                    "mac_reduction_vs_dense": (
                        round(banded.routing_reduction(fmt, S=S, B=B, W=w), 2)
                        if w else 1.0),
                },
            })

        # interpret-mode Pallas coverage rows (tiny size, tagged): the wall
        # time proves nothing about the kernel — keep it out of headlines
        ib = min(interpret_blocks, nb)
        small = {k: jnp.asarray(np.asarray(v)[:ib]) for k, v in od.items()}
        interp_widths = ((None,) if fmt == "binpack"
                         else (None, 64 if B >= 64 else 8))
        for w in interp_widths:
            if fmt == "vbyte":
                fn = lambda w=w: ops.vbyte_decode_blocked(
                    **small, block_size=B, differential=True, chunk_width=w,
                    interpret=True)
            elif fmt == "streamvbyte":
                fn = lambda w=w: ops.stream_vbyte_decode_blocked(
                    **small, block_size=B, differential=True, chunk_width=w,
                    interpret=True)
            else:
                fn = lambda w=w: ops.binpack_decode_blocked(
                    **small, block_size=B, differential=True, chunk_width=w,
                    interpret=True)
            t, _ = _bench(fn, reps=2, warmup=1)
            rows.append({
                "format": fmt,
                "path": "pallas-interpret",
                "interpret": True,
                "chunk_width": w,
                "blocks": ib,
                "stride": S,
                "block_size": B,
                "tiles_per_s": round(ib / 8 / t, 2),
                "mis": round(ib * B / t / 1e6, 2),
            })
    return rows


def tpu_projection(bits_per_int: float = 16.9) -> dict:
    """Roofline projection of the Pallas kernel on the TPU v5e target.

    The blocked decode is memory-bound (payload read + uint32 write; all
    mask/shuffle math runs at VPU/MXU rates far above the byte stream).
    Upper bound: HBM_bw / (payload + output bytes per int). The scalar
    decoder's bound is the loop-carried byte dependency (~1 byte / 4 cycles
    at best on a scalar core) — the same asymmetry the paper measures as
    its 2-4x, but widened by TPU's vector width.
    """
    hbm = 819e9
    bytes_per_int = bits_per_int / 8 + 4.0  # compressed read + u32 write
    vec_bound = hbm / bytes_per_int
    scalar_bound = 940e6 * 8 / (bits_per_int / 8)  # ~1 byte/4cyc @ ~1.7GHz scalar core
    return {
        "assumed_bits_per_int": bits_per_int,
        "kernel_bound_gis": round(vec_bound / 1e9, 1),
        "scalar_core_bound_gis": round(scalar_bound / 1e9, 2),
        "projected_speedup": round(vec_bound / scalar_bound, 1),
        "note": "kernel is HBM-bound; VPU mask math + MXU one-hot shuffle are "
                "not the bottleneck (see EXPERIMENTS.md §Perf kernel roofline)",
    }


def run_buffered(n_ints: int = 1 << 18, reps: int = 5):
    """§V last ¶: full-stream decode vs decode-to-cache-sized-buffer."""
    rng = np.random.default_rng(3)
    ids = np.sort(rng.choice(CLUEWEB_DOCS, size=n_ints, replace=False)).astype(np.uint64)
    arr = CompressedIntArray.encode(ids, differential=True)
    ops = arr.device_operands()
    from repro.core.vbyte.masked import decode_blocked

    t_full, _ = _bench(lambda: decode_blocked(**ops, block_size=128,
                                              differential=True), reps=reps)
    # buffered: decode in 32768-int (256-block) cache-resident chunks
    nb = ops["payload"].shape[0]
    chunk = 256
    def buffered():
        outs = []
        for i in range(0, nb, chunk):
            outs.append(decode_blocked(
                payload=ops["payload"][i:i + chunk],
                counts=ops["counts"][i:i + chunk],
                bases=ops["bases"][i:i + chunk],
                block_size=128, differential=True))
        return outs[-1]
    t_buf, _ = _bench(buffered, reps=max(2, reps // 2))
    return {"full_stream_mis": round(n_ints / t_full / 1e6, 1),
            "buffered_mis": round(n_ints / t_buf / 1e6, 1),
            "note": "paper sees ~15% penalty decoding the full stream to RAM vs "
                    "an L1 buffer; the CPU-XLA proxy adds per-call dispatch "
                    "overhead to the buffered path, so the effect is reported, "
                    "not reproduced, on this backend"}


if __name__ == "__main__":
    for r in run():
        print(r)
    print(run_buffered())
