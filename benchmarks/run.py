"""Benchmark harness entry point: ``python -m benchmarks.run``.

One benchmark per paper table/figure (+ framework-level extensions):
  decode             — dense vs banded chunked-scatter decode-tile cores:
                       tiles/sec + modeled routing MACs/VMEM per plan
                       (interpret-mode rows tagged, excluded from headlines)
  decode_speed       — Fig. 2 (scalar vs masked mis, by posting-list group)
  buffered           — §V last ¶ (decode-to-L1-buffer vs full stream)
  compression_ratio  — §V bits/int by group + blocked-layout overhead
  integrations       — compression of the framework's real id streams
  kernel_check       — Pallas kernel + fused-epilogue parity sweep
                       (+ sharded-vs-single-device parity when >1 device)
  fused              — fused vs unfused decode→consume epilogues (+ autotune)
  serving            — sharded decode throughput + ServingEngine QPS/latency
                       at 1/2/8 forced host devices (subprocess per count)
  index              — inverted-index queries/sec + decoded-ints/sec per
                       length group: AND/OR/top-k, fused vs unfused vs the
                       decode-then-intersect baseline, 1/2/8 devices
  roofline           — table from the dry-run artifacts (if present)
  robustness         — validated vs unvalidated decode throughput, plus
                       retry/quarantine/degraded rates from a flaky
                       workload through the hardened SearchEngine
                       (quick mode gates checksum overhead < 15%)
  ingestion          — streaming LiveIndex: adds/sec + WAL append latency
                       (fsync on/off), recovery time vs WAL length, merge
                       cost, and query p50/p99 during an active merge vs
                       quiescent (asserted bit-identical)

Results are written as machine-readable JSON (``--json``, default
``experiments/benchmarks.json``) so the perf trajectory is tracked across
PRs instead of being lost in stdout.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def bench_kernel_check(quick: bool = False):
    import jax.numpy as jnp

    from repro.core.compressed_array import CompressedIntArray
    from repro.kernels.vbyte_decode import (dispatch, vbyte_decode_blocked,
                                            vbyte_decode_blocked_ref)

    rng = np.random.default_rng(0)
    checked = 0
    sizes = (1000,) if quick else (128, 1000, 4096)
    for n in sizes:
        for diff in (False, True):
            vals = (np.sort(rng.integers(0, 2**31, n)) if diff
                    else rng.integers(0, 2**32, n)).astype(np.uint64)
            arr = CompressedIntArray.encode(vals, differential=diff)
            ops = arr.device_operands()
            a = vbyte_decode_blocked(**ops, block_size=128, differential=diff)
            b = vbyte_decode_blocked_ref(**ops, block_size=128, differential=diff)
            assert np.array_equal(np.asarray(a), np.asarray(b))
            checked += 1
            for fmt in ("streamvbyte", "binpack"):
                other = CompressedIntArray.encode(vals, format=fmt,
                                                  differential=diff)
                assert np.array_equal(other.decode(plan="kernel"),
                                      other.decode_scalar_oracle()), fmt
                checked += 1

    # banded-vs-dense parity across (chunk W, block_tile, stride_multiple)
    # combos: the chunked scatter must be a pure perf knob — identical
    # uint32 grids for both formats at every geometry
    from repro.kernels.vbyte_decode.dispatch import DecodePlan

    combos = ((32, 8, 128),) if quick else (
        (32, 8, 128), (64, 16, 8), (128, 8, 64), (16, 4, 128))
    bits = rng.integers(1, 33, size=700)
    mixed = (rng.integers(0, 2**63, 700, dtype=np.uint64)
             % (1 << bits.astype(np.uint64))).astype(np.uint64)
    for W, bt, sm in combos:
        for fmt in ("vbyte", "streamvbyte"):
            arr = CompressedIntArray.encode(mixed, format=fmt,
                                            stride_multiple=sm)
            ops = arr.device_operands()
            dense = dispatch.decode(ops, format=fmt, block_size=128,
                                    differential=False,
                                    plan=DecodePlan("pallas", True, bt))
            band = dispatch.decode(ops, format=fmt, block_size=128,
                                   differential=False,
                                   plan=DecodePlan("pallas", True, bt,
                                                   chunk=W))
            assert np.array_equal(np.asarray(dense), np.asarray(band)), \
                (fmt, W, bt, sm)
            checked += 1

    # fused epilogue parity: Pallas-fused == jnp-fused == unfused reference
    vals = np.sort(rng.integers(0, 4096, 640)).astype(np.uint64)
    table = jnp.asarray(rng.standard_normal((4096, 16)).astype(np.float32))
    query = jnp.asarray(rng.standard_normal((1, 16)).astype(np.float32))
    for fmt in ("vbyte", "streamvbyte", "binpack"):
        arr = CompressedIntArray.encode(vals, format=fmt, differential=True)
        ops = arr.device_operands()
        eb = jnp.asarray(rng.integers(0, 4096, (arr.n_blocks, 128))
                         .astype(np.int32))
        for ep, eops in (("bag_sum", {"table": table}),
                         ("dot_score", {"table": table, "query": query}),
                         ("adjacency_rebase", {"edge_base": eb})):
            outs = []
            for plan in ("kernel", "jnp", "unfused"):
                o = dispatch.decode(ops, format=fmt, block_size=128,
                                    differential=True, epilogue=ep,
                                    epilogue_operands=eops, plan=plan)
                outs.append([np.asarray(x) for x in
                             (o if isinstance(o, tuple) else (o,))])
            for other in outs[1:]:
                assert all(np.array_equal(x, y)
                           for x, y in zip(outs[0], other)), (fmt, ep)
            checked += 1

    # sharded parity: block-parallel shard_map decode == single-device,
    # exercised whenever the process has >1 device (the CI `sharded` job
    # forces 8 host devices)
    import jax

    sharded_cases = 0
    if len(jax.devices()) > 1:
        mesh = jax.make_mesh((len(jax.devices()),), ("data",))
        for fmt in ("vbyte", "streamvbyte", "binpack"):
            arr = CompressedIntArray.encode(vals, format=fmt,
                                            differential=True)
            sh = arr.shard(mesh)
            assert np.array_equal(sh.decode(), arr.decode()), fmt
            ids_r, sc_r = dispatch.decode(
                arr, epilogue="dot_score",
                epilogue_operands={"table": table, "query": query},
                plan="jnp")
            ids_s, sc_s = dispatch.decode(
                sh, epilogue="dot_score",
                epilogue_operands={"table": table, "query": query})
            assert np.array_equal(np.asarray(ids_r),
                                  np.asarray(ids_s)[: arr.n_blocks]), fmt
            assert np.array_equal(np.asarray(sc_r),
                                  np.asarray(sc_s)[: arr.n_blocks]), fmt
            sharded_cases += 2
            checked += 2
    return {"kernel_vs_oracle_cases": checked, "all_equal": True,
            "formats": ["vbyte", "streamvbyte", "binpack"],
            "fused_epilogues": ["bag_sum", "dot_score", "adjacency_rebase"],
            "sharded_parity_cases": sharded_cases,
            "devices": len(jax.devices())}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="decode|decode_speed|compression|kernel|fused|"
                         "serving|index|roofline|robustness|ingestion")
    ap.add_argument("--json", default=None,
                    help="output path (default experiments/benchmarks.json; "
                         "--quick runs write the untracked -quick variant so "
                         "tiny-size noise never overwrites the tracked "
                         "cross-PR trajectory)")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.json is None:
        args.json = ("experiments/benchmarks-quick.json" if args.quick
                     else "experiments/benchmarks.json")

    results = {}
    t0 = time.time()

    def want(name):
        return args.only in (None, name)

    if want("decode"):
        from benchmarks import decode_speed

        # 2^16 (not 2^18): the dense core's grid-level one-hot is
        # O(n·stride·4B) — ~170 MB here, unmanageable at 2^18 on CPU
        n = 1 << 14 if args.quick else 1 << 16
        print("== decode-tile cores: dense vs banded chunked scatter ==")
        rows = decode_speed.run_decode_cores(
            n_ints=n, reps=3 if args.quick else 8,
            interpret_blocks=16 if args.quick else 64)
        for r in rows:
            w = r["chunk_width"]
            tag = " [interpret]" if r["interpret"] else ""
            model = r.get("modeled_per_tile")
            m = (f"  macs/tile={model['mxu_macs']:>8} "
                 f"({model['mac_reduction_vs_dense']}x) "
                 f"vmem={model['vmem_bytes'] >> 10}KiB"
                 if model else "")
            print(f"  {r['format']:>11} W={str(w):>4}{tag} "
                  f"tiles/s={r['tiles_per_s']:>8} mis={r['mis']:>7}"
                  + (f" speedup={r['speedup_vs_dense']}x" if "speedup_vs_dense" in r else "")
                  + m)
        results["decode_kernel"] = rows

    if want("decode_speed"):
        from benchmarks import decode_speed

        n = 1 << 16 if args.quick else 1 << 18
        print("== decode speed by posting-list group (paper Fig. 2) ==")
        rows = decode_speed.run(n_ints=n)
        for r in rows:
            per = "  ".join(
                f"{f}={d['mis']:>8} mis ({d['bits_per_int']}b/i, "
                f"{d['speedup_vs_scalar']}x)"
                for f, d in r["formats"].items())
            print(f"  K={r['group_K']:>2} scalar={r['scalar_mis']:>7} mis  "
                  + per)
        results["decode_speed"] = rows
        print("== buffered vs full-stream decode (paper §V) ==")
        b = decode_speed.run_buffered(n_ints=n)
        print(f"  {b}")
        results["buffered"] = b
        proj = decode_speed.tpu_projection()
        print(f"== TPU v5e kernel roofline projection ==\n  {proj}")
        results["tpu_projection"] = proj

    if want("compression"):
        from benchmarks import compression_ratio

        print("== compression by group (paper §V) ==")
        rows = (compression_ratio.run(groups=(10, 12, 14, 16, 18),
                                      lists_per_group=2)
                if args.quick else compression_ratio.run())
        for r in rows:
            per = " ".join(
                f"{f}={d['bits_per_int']:>5}b/i ({d['ratio_vs_u32']}x)"
                for f, d in r["formats"].items())
            print(f"  K={r['group_K']:>2} {per} "
                  f"overhead={r['block_overhead']}")
        results["compression_ratio"] = rows
        print("== posting-list index compression (bits/int vs paper 8..16) ==")
        idx_rows = compression_ratio.run_posting_index(
            lists_per_group=2 if args.quick else 4)
        for r in idx_rows:
            per = " ".join(f"{f}={b:>5}" for f, b in r["formats"].items())
            print(f"  K={r['group_K']:>2} bits/int: {per}")
            assert r["formats"]["auto"] <= r["formats"]["vbyte"] + 1e-9, \
                f"DP-partitioned index lost to uniform vbyte at K={r['group_K']}"
        results["posting_index"] = idx_rows
        integ = compression_ratio.run_integrations()
        print(f"== framework id-stream compression ==\n  {integ}")
        results["integrations"] = integ

    if want("kernel"):
        print("== pallas kernel + fused-epilogue parity sweep ==")
        results["kernel_check"] = bench_kernel_check(quick=args.quick)
        print(f"  {results['kernel_check']}")

    if want("fused"):
        from benchmarks import decode_speed

        n = 1 << 14 if args.quick else 1 << 18
        print("== fused vs unfused decode→consume epilogues ==")
        rows = decode_speed.run_fused(n_ints=n,
                                      reps=4 if args.quick else 10)
        for r in rows:
            extra = (f"  legacy_host={r['legacy_host_mis']} mis "
                     f"({r['fused_speedup_vs_legacy']}x)"
                     if "legacy_host_mis" in r else "")
            print(f"  {r['format']:>11}/{r['epilogue']:<16} "
                  f"fused={r['fused_mis']:>6} mis  "
                  f"unfused={r['unfused_mis']:>6} mis  "
                  f"speedup={r['fused_speedup']}x{extra}")
        results["fused"] = rows
        from repro.kernels.vbyte_decode import dispatch

        # quick runs measure tiny sizes — keep their noisy plans out of the
        # tracked cache that plan="auto" consults
        cache_file = ("experiments/autotune-quick.json" if args.quick
                      else dispatch.cache_path())
        print(f"== autotune: measuring dispatch plans -> {cache_file} ==")
        cache = dispatch.autotune(
            n_blocks=8 if args.quick else 64,
            reps=2 if args.quick else 5,
            cache_file=cache_file)
        picks = {k: v["plan"] for k, v in cache.items()}
        results["autotune"] = picks
        print(f"  {len(picks)} workload keys cached")

    if want("serving"):
        from benchmarks import serving

        print("== sharded serving: decode throughput + engine QPS/latency ==")
        rows = serving.run(quick=args.quick)
        for r in rows:
            if "error" in r:
                print(f"  devices={r['devices']}: FAILED\n{r['error']}")
                continue
            eng = r["engine"]
            dec = {d["format"]: d for d in r["decode"]}
            vb = dec["vbyte"]
            sharded = (f" sharded={vb['sharded_mis']} Mis"
                       if "sharded_mis" in vb else "")
            print(f"  devices={r['devices']}: vbyte decode "
                  f"single={vb['single_device_mis']} Mis{sharded}  "
                  f"engine {eng['qps']} QPS p50={eng['p50_ms']}ms "
                  f"p99={eng['p99_ms']}ms")
            if "obs_overhead" in r:
                ov = r["obs_overhead"]
                print(f"    telemetry: null-path "
                      f"{ov['null_path_overhead_pct']}% of p50 "
                      f"({ov['sites_per_query']} sites/query @ "
                      f"{ov['null_site_us']}us)  instrumented-on "
                      f"{ov['overhead_pct']:+.2f}% "
                      f"(p50 {ov['null_p50_ms']} -> "
                      f"{ov['instrumented_p50_ms']} ms)")
        assert not any("error" in r for r in rows), "serving bench failed"
        ov = next((r["obs_overhead"] for r in rows if "obs_overhead" in r),
                  None)
        # the observability fast-path contract (docs/observability.md):
        # with no registry installed the instrumentation sites must cost
        # < 3% of serving p50, and a full capture (every span of every
        # request traced — the worst case, not the default) must stay
        # small too
        assert ov is not None, "serving bench measured no telemetry overhead"
        assert ov["null_path_overhead_pct"] < 3.0, \
            f"null-path cost {ov['null_path_overhead_pct']}% >= 3% budget"
        assert ov["overhead_pct"] < 15.0, \
            f"instrumented-on overhead {ov['overhead_pct']}% >= 15%"
        results["serving"] = rows

    if want("index"):
        from benchmarks import index_query

        print("== inverted-index queries: AND/OR/top-k, fused vs unfused ==")
        counts = (1, 2) if args.quick else (1, 2, 8)
        rows = index_query.run(device_counts=counts, quick=args.quick)
        for r in rows:
            if "error" in r:
                print(f"  devices={r['devices']}: FAILED\n{r['error']}")
                continue
            if "engine" in r:
                eng = r["engine"]
                print(f"  devices={r['devices']}: engine {eng['qps']} QPS "
                      f"p50={eng['p50_ms']}ms p99={eng['p99_ms']}ms")
                continue
            for g in r["groups"]:
                if g["mode"] == "and_baseline":
                    print(f"  K={g['group_K']:>2} {g['format']:>11} "
                          f"and_baseline qps={g['qps']:>8} "
                          f"(fused {g['fused_speedup_vs_baseline']}x)")
                else:
                    extra = ""
                    if g.get("pruned_block_rate"):
                        extra += (f" pruned={g['pruned_block_rate']}"
                                  f" (impacts {g['pruned_impact_rate']})")
                    if "maxscore_speedup_vs_taat" in g:
                        extra += (f" vs_taat="
                                  f"{g['maxscore_speedup_vs_taat']}x")
                    print(f"  K={g['group_K']:>2} {g['format']:>11} "
                          f"{g['mode']:>13}/{g['plan']:<7} qps={g['qps']:>8} "
                          f"decoded={g['decoded_mis']:>7} Mis "
                          f"skip={g['block_skip_rate']}" + extra)
        assert not any("error" in r for r in rows), "index bench failed"
        results["index_query"] = rows

    if want("robustness"):
        from benchmarks import robustness

        print("== robustness: validation overhead + degraded-serving rates ==")
        rob = robustness.run(quick=args.quick)
        for r in rob["decode"]:
            print(f"  {r['format']:>11} unvalidated={r['unvalidated_mis']:>7}"
                  f" Mis  validated={r['validated_mis']:>7} Mis "
                  f"(in-pass overhead {r['checksum_overhead']:+.1%}, "
                  f"host verify {r['host_verify_overhead']:+.1%})")
        srv = rob["serving"]
        print(f"  flaky workload: {srv['qps']} QPS, "
              f"retry rate {srv['retry_rate']}, quarantined blocks "
              f"{srv['quarantined_block_rate']}, degraded rate "
              f"{srv['degraded_rate']}")
        results["robustness"] = rob

    if want("ingestion"):
        from benchmarks import ingestion

        print("== streaming ingestion: WAL, recovery, merge-time queries ==")
        ing = ingestion.run(quick=args.quick)
        for key, label in (("ingest_fsync", "fsync"),
                           ("ingest_nofsync", "no-fsync")):
            r = ing[key]
            print(f"  ingest [{label:>8}]: {r['ops_per_s']:>7} ops/s  "
                  f"append p50={r['p50_us']}us p99={r['p99_us']}us")
        for r in ing["recovery"]:
            print(f"  recovery: {r['wal_ops']:>6} WAL ops in "
                  f"{r['recovery_ms']:>8}ms ({r['ops_per_s']} ops/s)")
        print(f"  merge: {ing['merge']['merge_s']}s for "
              f"{ing['merge']['n_postings']} postings "
              f"({ing['merge']['bits_per_int']} bits/int)")
        for key, label in (("query_quiescent", "quiescent"),
                           ("query_during_merge", "mid-merge"),
                           ("query_post_merge", "post-merge")):
            r = ing[key]
            print(f"  query [{label:>10}]: p50={r['p50_us']}us "
                  f"p99={r['p99_us']}us")
        results["ingestion"] = ing

    if want("roofline"):
        from benchmarks import roofline

        rows = roofline.run()
        results["roofline_cells"] = len(rows)
        print(f"== roofline table: {len(rows)} dry-run cells "
              "(see EXPERIMENTS.md §Roofline) ==")

    results["wall_s"] = round(time.time() - t0, 1)
    import os
    os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
    # merge into the existing file so partial (--only) runs accumulate and
    # the perf trajectory survives across invocations/PRs
    try:
        with open(args.json) as f:
            merged = json.load(f)
    except (OSError, ValueError):
        merged = {}
    merged.update(results)
    merged["updated_at"] = time.strftime("%Y-%m-%d %H:%M:%S")
    with open(args.json, "w") as f:
        json.dump(merged, f, indent=1)
    print(f"done in {results['wall_s']}s -> {args.json}")


if __name__ == "__main__":
    main()
