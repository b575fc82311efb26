"""Chip smoke: drive the compressed-index search path once on a TPU.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: the sharded search path only

Phase A decodes 2^24 seeded docids per format (vbyte, streamvbyte,
binpack) through ``dispatch.decode(plan="auto")`` and compares the result
bit for bit with the input. Phase B builds an inverted index over the
ClueWeb09-B docid universe (50M docs, the paper's §V collection), serves
queries in all five modes through ``SearchEngine.search`` and compares
every answer with a plain numpy reference of the same semantics. With
``--chips 4`` the same index is sharded over a 4-device ``data`` mesh and
only that served path runs.

This is a smoke, not a benchmark: the times it prints are single
wall-clock readings, compile included where marked. It writes no tracked
file. It needs a TPU and exits non-zero without one; it never runs a
kernel in interpret mode. The last line of its output is one JSON object,
``{"ok": true, "device": {...}}``; any failed phase exits non-zero before
printing it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import reduce

PHASE_A_INTS = 1 << 24
PHASE_A_FORMATS = ("vbyte", "streamvbyte", "binpack")
GROUP_KS = (10, 12, 14, 16, 18, 20, 22)  # list lengths in [2^K, 2^(K+1))
LISTS_PER_GROUP = 8
MODES = ("and", "or", "topk", "topk_driver", "topk_maxscore")
TOP_K = 10
N_QUERIES = 25  # five per mode
TERMS_PER_QUERY = (1, 2, 3, 5)


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phase A: every format's decode kernel at real size
# ---------------------------------------------------------------------------
def seeded_docids(rng, n):
    """Sorted uint32 docids whose d-gaps span every encoded length: mostly
    1-2 byte gaps, a few hundred 4-byte ones and four 5-byte (≥ 2^28)
    ones, summing to < 2^32."""
    import numpy as np

    gaps = rng.geometric(1 / 100, size=n).astype(np.int64)
    big = rng.choice(n, size=204, replace=False)
    gaps[big[:200]] = rng.integers(1 << 21, 1 << 22, size=200)
    gaps[big[200:]] = rng.integers(1 << 28, (1 << 28) + (1 << 20), size=4)
    gaps[0] = 0
    docids = np.cumsum(gaps)
    assert docids[-1] < 1 << 32
    return docids.astype(np.uint32)


def phase_a(seed):
    import numpy as np

    import jax

    from repro.core import CompressedIntArray
    from repro.kernels.vbyte_decode import dispatch, ops

    decode_fns = {"vbyte": ops.vbyte_decode_blocked,
                  "streamvbyte": ops.stream_vbyte_decode_blocked,
                  "binpack": ops.binpack_decode_blocked}
    rng = np.random.default_rng(seed)
    docids = seeded_docids(rng, PHASE_A_INTS)
    for fmt in PHASE_A_FORMATS:
        t0 = time.perf_counter()
        arr = CompressedIntArray.encode(docids, format=fmt, block_size=128,
                                        differential=True)
        t_enc = time.perf_counter() - t0
        plan = dispatch.resolve_plan("auto", format=fmt,
                                     block_size=arr.block_size)
        check(plan.path == "pallas",
              f"{fmt}: plan 'auto' resolved to {plan.label}, not a Pallas "
              "plan")
        operands = arr.device_operands()
        # only Stream VByte's cores take a chunk width
        chunk = {} if plan.chunk is None else {"chunk_width": plan.chunk}
        t0 = time.perf_counter()
        hlo = decode_fns[fmt].lower(
            **operands, block_size=arr.block_size, differential=True,
            block_tile=plan.block_tile, **chunk).compile().as_text()
        t_compile = time.perf_counter() - t0
        check("tpu_custom_call" in hlo,
              f"{fmt}: the decode HLO holds no tpu_custom_call (no Mosaic "
              "kernel)")
        out = jax.block_until_ready(dispatch.decode(arr, plan="auto"))
        t0 = time.perf_counter()
        out = jax.block_until_ready(dispatch.decode(arr, plan="auto"))
        t_dec = time.perf_counter() - t0
        grid = np.asarray(out)
        mask = (np.arange(arr.block_size)[None, :]
                < np.asarray(arr.counts)[:, None])
        got = grid[mask]
        n_bad = int(np.count_nonzero(got != docids)) \
            if got.shape == docids.shape else -1
        check(n_bad == 0,
              f"{fmt}: decode differs from the input at {n_bad} of "
              f"{docids.size} positions (shape {got.shape})")
        log(f"phase A {fmt:>11}: plan={plan.label} tpu_custom_call=yes "
            f"{docids.size} ints bit-exact, {arr.bits_per_int:.2f} bits/int, "
            f"{arr.n_blocks} blocks; encode {t_enc:.2f} s (host), compile "
            f"{t_compile:.2f} s, one decode call {t_dec * 1e3:.2f} ms "
            "(smoke timing, not a benchmark)")


# ---------------------------------------------------------------------------
# phase B: the served search path
# ---------------------------------------------------------------------------
def build_corpus(seed):
    import numpy as np

    from repro.data.synthetic import (CLUEWEB_DOCS, posting_list_group,
                                      posting_tfs)
    from repro.index import build_index

    rng = np.random.default_rng(seed)
    lists, tfs = {}, {}
    for k in GROUP_KS:
        for lst in posting_list_group(rng, k, LISTS_PER_GROUP,
                                      universe=CLUEWEB_DOCS):
            t = len(lists)
            lists[t] = lst
            tfs[t] = posting_tfs(rng, len(lst))
    t0 = time.perf_counter()
    index = build_index(lists, tfs=tfs, n_docs=CLUEWEB_DOCS)
    log(f"phase B index: {index.n_terms} terms, {index.n_postings} "
        f"postings over {CLUEWEB_DOCS} docs, {index.bits_per_int:.2f} "
        f"bits/int, built in {time.perf_counter() - t0:.1f} s (host)")
    return index, lists, tfs, rng


def reference(index, lists, tfs, mode, terms):
    """Plain numpy answer with SearchEngine.search's semantics."""
    import numpy as np

    from repro.index import quantize_impacts

    if mode == "and":
        return reduce(np.intersect1d, [lists[t] for t in terms])
    if mode == "or":
        return np.unique(np.concatenate([lists[t] for t in terms]))
    docs = np.concatenate([lists[t] for t in terms]).astype(np.int64)
    imps = np.concatenate([
        quantize_impacts(index.impact(t), tfs[t], index.impact_bits)
        for t in terms]).astype(np.int64)
    cand, inv = np.unique(docs, return_inverse=True)
    # float64 sums of small ints are exact far beyond any score here
    scores = np.bincount(inv, weights=imps).astype(np.int64)
    if mode == "topk_driver":  # docs of terms[0], scored over all terms
        keep = np.isin(cand, lists[terms[0]])
        cand, scores = cand[keep], scores[keep]
    order = np.lexsort((cand, -scores))[:TOP_K]
    return cand[order].astype(np.uint32), scores[order].astype(np.int32)


def same_answer(got, want):
    import numpy as np

    if isinstance(want, tuple):
        return (isinstance(got, tuple)
                and all(np.array_equal(np.asarray(g), w)
                        for g, w in zip(got, want)))
    return np.array_equal(np.asarray(got), want)


def check_sharded_layout(engine, mesh):
    """Each device holds its own share of every term's blocks, and the
    sharded decode compiles to per-device kernels with no collective."""
    from repro.kernels.vbyte_decode import dispatch

    tp = max(engine.index.terms.values(), key=lambda tp: tp.df)
    operands = tp.arr.device_operands()
    n_dev = mesh.devices.size
    for name, leaf in operands.items():
        rows = {s.device.id: s.data.shape[0] for s in leaf.addressable_shards}
        check(len(rows) == n_dev
              and set(rows.values()) == {leaf.shape[0] // n_dev},
              f"term {tp.term} leaf {name}: blocks per device {rows}, "
              f"expected {leaf.shape[0] // n_dev} on each of {n_dev}")
    plan = dispatch.resolve_plan("auto", format=tp.arr.format,
                                 block_size=tp.arr.block_size)
    fn = dispatch._build_sharded_fn(
        mesh, ("data",), tp.arr.format, "stream", tp.arr.block_size,
        tp.arr.differential, plan, None, False)
    hlo = fn.lower(operands, {}).compile().as_text()
    found = [c for c in ("all-reduce", "all-gather", "all-to-all",
                         "collective-permute", "reduce-scatter") if c in hlo]
    check("tpu_custom_call" in hlo and not found,
          f"sharded decode HLO: tpu_custom_call="
          f"{'tpu_custom_call' in hlo}, collectives={found}")
    log(f"phase B sharded layout: term {tp.term} ({tp.df} postings) holds "
        f"{tp.arr.n_blocks // n_dev} blocks on each of {n_dev} devices; "
        f"sharded decode ({plan.label}) has a Mosaic kernel and no "
        "collective")


def phase_b(seed, devices):
    import numpy as np

    import jax

    from repro import obs
    from repro.launch.serve import SearchEngine, search_queries

    index, lists, tfs, rng = build_corpus(seed)
    mesh = None
    if len(devices) > 1:
        mesh = jax.sharding.Mesh(np.array(devices), ("data",))
    t0 = time.perf_counter()
    engine = SearchEngine(index, mesh=mesh, top_k=TOP_K)
    log(f"phase B engine on {len(devices)} device(s), "
        f"{'sharded over a data mesh' if mesh else 'no mesh'}: set-up "
        f"{time.perf_counter() - t0:.1f} s")
    if mesh is not None:
        check_sharded_layout(engine, mesh)
    queries = search_queries(rng, index, N_QUERIES,
                             terms_per_query=TERMS_PER_QUERY, modes=MODES)
    tele = obs.Telemetry()
    n_checked = {m: 0 for m in MODES}
    with obs.install(tele):
        t0 = time.perf_counter()
        for mode in MODES:  # first query per mode: compile included
            first = next(q for q in queries if q[0] == mode)
            got = engine.search(first[1], mode)
            check(same_answer(got, reference(index, lists, tfs, *first)),
                  f"{mode} {first[1]}: answer differs from the reference")
        log(f"phase B warmup (compile included): {len(MODES)} queries in "
            f"{time.perf_counter() - t0:.1f} s")
        t_serve = 0.0
        for i, (mode, terms) in enumerate(queries):
            t0 = time.perf_counter()
            got = engine.search(terms, mode)
            dt = time.perf_counter() - t0
            t_serve += dt
            want = reference(index, lists, tfs, mode, terms)
            check(same_answer(got, want),
                  f"query {i} {mode} {terms}: answer differs from the "
                  "reference")
            n_checked[mode] += 1
            log(f"  query {i:>3} {mode:>13} terms={terms} "
                f"{dt * 1e3:.1f} ms (matches reference)")
    check(all(n_checked.values()), f"a mode went unserved: {n_checked}")
    snap = tele.registry.snapshot()["metrics"]
    plans = sorted({k for k in snap if k.startswith("decode_calls_total")})
    downgrades = {k: v for k, v in snap.items()
                  if k.startswith("decode_plan_downgrade_total")}
    for k in plans:
        log(f"  {k} = {snap[k]}")
    check(plans and all("plan=pallas" in k for k in plans),
          f"a served decode ran off the Pallas kernels: {plans}")
    log(f"phase B size-based plan downgrades: {downgrades or 'none'}")
    log(f"phase B served {len(queries)} queries ({n_checked}) in "
        f"{t_serve:.1f} s wall, every answer equal to the numpy reference "
        "(smoke timing, not a benchmark)")
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"phase B {d}: peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use', 'not reported')}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded search path on a 4-chip "
                         "data mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: the repro package is missing (expected src/repro "
              "next to this script)", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: no TPU present (JAX backend is {backend!r}); "
              "this smoke runs only on a TPU and does not fall back",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    attached = jax.devices()
    if len(attached) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, {len(attached)} attached", file=sys.stderr)
        return 1
    devices = attached[:args.chips]
    log(f"devices: {devices} (of {len(attached)} attached)")
    try:
        if args.chips == 1:
            phase_a(args.seed)
        phase_b(args.seed + 1, devices)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
