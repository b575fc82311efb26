"""Inverted-index query benchmarks: queries/sec and decoded-ints/sec per
posting-list length group K, AND vs OR vs top-k, fused (membership /
bm25_accum epilogues + skip-table pruning) vs unfused, and the
decode-then-intersect baseline the fused path must beat (decode every
term's full posting list to host, ``np.intersect1d`` the results — the
query shape every call site would write without the index subsystem).

Like benchmarks/serving.py, multi-device rows need their own process (jax
locks the host-platform device count at first init), so :func:`run` spawns
``python -m benchmarks.index_query --devices N`` per count. Single-device
processes measure the per-group table; multi-device processes measure the
sharded ``SearchEngine`` workload (block-parallel ``shard_map`` decode,
per-shard score partials merged on host).
"""
from __future__ import annotations

import time


def _bench_queries(engine, queries, *, plan, use_skip, reps=3):
    """Time one query workload (best of ``reps`` passes — shared-host
    noise swamps single small samples); returns a stats row with qps,
    p50/p99 per-query latency (same percentile semantics as the serving
    engine: ``repro.obs.stats``), decoded-Mints/s, and the skip /
    threshold-pruned block rates."""
    from repro.index import QueryStats
    from repro.obs.stats import percentile

    engine.plan = plan
    engine.use_skip = use_skip
    for mode, terms in queries:  # compile every query's shapes (steady state)
        engine.search(terms, mode)
    wall = float("inf")
    best_lat = []
    for _ in range(reps):
        st = QueryStats()
        lat = []
        t0 = time.perf_counter()
        for mode, terms in queries:
            q0 = time.perf_counter()
            engine.search(terms, mode, stats=st)
            lat.append(time.perf_counter() - q0)
        w = time.perf_counter() - t0
        if w < wall:
            wall, best_lat = w, lat
    total = st.blocks_decoded + st.blocks_skipped + st.blocks_pruned
    postings = st.ints_decoded + st.postings_pruned
    lat_ms = [s * 1e3 for s in best_lat]
    return {
        "qps": round(len(queries) / wall, 2),
        "p50_ms": round(percentile(lat_ms, 50), 3),
        "p99_ms": round(percentile(lat_ms, 99), 3),
        "decoded_mis": round(st.ints_decoded / wall / 1e6, 3),
        "block_skip_rate": (round(st.blocks_skipped / total, 3)
                            if total else 0.0),
        "pruned_block_rate": (round(st.blocks_pruned / total, 3)
                              if total else 0.0),
        "pruned_impact_rate": (round(st.postings_pruned / postings, 3)
                               if postings else 0.0),
    }


def _measure(quick: bool) -> dict:
    import numpy as np

    import jax

    from repro.data.synthetic import (posting_list, posting_list_group,
                                      posting_tfs)
    from repro.index import build_index
    from repro.launch.serve import SearchEngine, search_queries

    n_dev = len(jax.devices())
    rng = np.random.default_rng(3)
    universe = 1 << 22

    if n_dev > 1:
        # sharded engine workload: one group, mixed query modes
        k = 8 if quick else 10
        lists = posting_list_group(rng, k, 8, universe=universe)
        tfs = [posting_tfs(rng, len(v)) for v in lists]
        index = build_index(lists, tfs=tfs, n_docs=universe)
        mesh = jax.make_mesh((n_dev,), ("data",))
        engine = SearchEngine(index, mesh=mesh)
        qs = search_queries(rng, index, 8 if quick else 24)
        engine.warmup(qs)  # steady-state timing: compile every shape first
        stats = engine.run_workload(qs)
        return {"devices": n_dev, "engine": stats}

    # default groups reach K=18 (262k..524k-int lists): block-level pruning
    # needs lists much longer than the probe set before it can pay off —
    # at K ≤ 8 a whole list is 1..4 blocks and the baseline's single tiny
    # decode is unbeatable
    groups = (6, 14) if quick else (10, 12, 14, 16, 18)
    n_lists = 4 if quick else 6
    n_queries = 6 if quick else 12
    # quick needs K=14 for the maxscore pruning smoke: pruning is strict
    # (a block tying θ must be decoded — its docs can tie-and-win on
    # docid), and the 8-bit quantizer ceilings any list shorter than
    # K≈13 at the same 255 the rare saturated terms push θ to, erasing
    # the selective gap. At K=14 the group lists' saturated block maxima
    # sit strictly under θ, so the long list is genuinely probed-or-
    # pruned. Shrink the block size (and probe/strip width below) so
    # quick lists still span many DAAT strips
    block_size = 32 if quick else 128
    probe_width = 128 if quick else 512
    rows = []
    for k in groups:
        lists = dict(enumerate(
            posting_list_group(rng, k, n_lists, universe=universe)))
        # rare "title" terms: the selective drivers of realistic AND
        # queries (the small side of small-vs-large intersection)
        rare_ids = list(range(1000, 1003))
        for t in rare_ids:
            lists[t] = posting_list(rng, int(rng.integers(96, 192)),
                                    universe=universe)
        # skewed per-posting term frequencies: the impact variance that
        # gives MaxScore's block-max threshold something to prune
        tfs = {t: posting_tfs(rng, len(v)) for t, v in lists.items()}
        for fmt in ("vbyte", "streamvbyte"):
            index = build_index(lists, tfs=tfs, format=fmt,
                                block_size=block_size, n_docs=universe)
            engine = SearchEngine(index, probe_width=probe_width)
            group_ids = sorted(t for t in index.terms if t < 1000)
            # one shared term mix for the scored modes so the
            # maxscore-vs-TAAT headline is apples-to-apples. Selective
            # rare-driver queries (two title terms + one body term) are
            # MaxScore's target shape: the rare terms' saturated impacts
            # push θ past the heavy term's bound after a handful of
            # blocks, so the long list is probed at the candidates and
            # otherwise never decoded. TAAT decodes it in full either way.
            scored_terms = [[int(t) for t in
                            rng.choice(rare_ids, 2, replace=False)]
                            + [int(rng.choice(group_ids))]
                            for _ in range(n_queries)]
            qs = {
                # AND: rare driver ∧ long group list — the shape where
                # skip-gather + fused membership replace a full decode
                "and": [("and", [int(rng.choice(rare_ids)),
                                 int(rng.choice(group_ids))])
                        for _ in range(n_queries)],
                "or": [("or", [int(t) for t in
                               rng.choice(group_ids, 2, replace=False)])
                       for _ in range(n_queries)],
                "topk": [("topk", t) for t in scored_terms],
                # block-max pruned top-k: bit-identical results to "topk",
                # but blocks/probes under the threshold never decode
                "topk_maxscore": [("topk_maxscore", t)
                                  for t in scored_terms],
                # required-term DAAT: rare driver scored against long
                # optional terms through the fused bm25 epilogues
                "topk_driver": [("topk_driver", [int(rng.choice(rare_ids))]
                                 + [int(t) for t in
                                    rng.choice(group_ids, 2, replace=False)])
                                for _ in range(n_queries)],
            }
            for mode, queries in qs.items():
                for plan, fused in (("fused", True), ("unfused", False)):
                    row = _bench_queries(
                        engine, queries, plan=plan, use_skip=True)
                    rows.append({"group_K": k, "format": fmt, "mode": mode,
                                 "plan": plan, **row})
            # the tentpole headline: pruned top-k vs exhaustive TAAT on
            # the same queries, same index, same (fused) plan
            ms = next(r for r in rows
                      if r["group_K"] == k and r["format"] == fmt
                      and r["mode"] == "topk_maxscore"
                      and r["plan"] == "fused")
            taat = next(r for r in rows
                        if r["group_K"] == k and r["format"] == fmt
                        and r["mode"] == "topk" and r["plan"] == "fused")
            ms["maxscore_speedup_vs_taat"] = (
                round(ms["qps"] / taat["qps"], 2) if taat["qps"] else 0.0)
            # decode-then-intersect baseline for the AND workload: decode
            # every term's full list to host, intersect with numpy
            def _baseline(queries=qs["and"], index=index):
                for _, terms in queries:
                    docs = [index.terms[t].arr.decode(plan="jnp")
                            for t in terms]
                    out = docs[0]
                    for d in docs[1:]:
                        out = np.intersect1d(out, d)
            _baseline()  # compile
            wall = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                _baseline()
                wall = min(wall, time.perf_counter() - t0)
            base_qps = round(n_queries / wall, 2)
            fused_qps = next(r["qps"] for r in rows
                             if r["group_K"] == k and r["format"] == fmt
                             and r["mode"] == "and" and r["plan"] == "fused")
            rows.append({"group_K": k, "format": fmt, "mode": "and_baseline",
                         "plan": "decode_then_intersect", "qps": base_qps,
                         "fused_speedup_vs_baseline":
                             round(fused_qps / base_qps, 2)})
    if quick:
        # CI smoke contract: the skewed synthetic workload must actually
        # exercise block-max pruning, not just fall through to TAAT
        assert any(r["mode"] == "topk_maxscore"
                   and r.get("pruned_block_rate", 0) > 0 for r in rows), \
            "maxscore quick benchmark pruned no blocks — threshold " \
            "pruning is not engaging on the skewed workload"
    return {"devices": 1, "groups": rows}


def run(device_counts=(1, 2, 8), *, quick: bool = False) -> list[dict]:
    """Per-device-count query sweep (subprocess per count on the CPU)."""
    from benchmarks.serving import sweep_device_counts

    return sweep_device_counts("benchmarks.index_query", device_counts,
                               _measure, quick=quick)


if __name__ == "__main__":
    from benchmarks.serving import sweep_main

    sweep_main(run, _measure)
