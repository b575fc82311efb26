"""Sharded block-parallel decode: bit-exact parity with the single-device
path for both formats (plain, differential, ragged, count=0 blocks, fused
epilogues), no cross-device collectives in the compiled decode, and the
ServingEngine over a multi-device mesh.

These tests need >1 device; CI runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see the `sharded`
job). On a single-device run they skip.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import CompressedIntArray
from repro.kernels.vbyte_decode import dispatch
from repro.kernels.vbyte_decode.dispatch import DecodePlan

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >1 device; run under "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

FMTS = ["vbyte", "streamvbyte", "binpack"]
B = 32  # block size


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((len(jax.devices()),), ("data",))


def _tuple(x):
    return x if isinstance(x, tuple) else (x,)


# ---------------------------------------------------------------------------
# stream decode parity
# ---------------------------------------------------------------------------
@multi_device
@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("differential", [False, True])
# 2*B+7: ragged tail; B-1: single partial block; 40*B+3: blocks ≫ devices
@pytest.mark.parametrize("n", [B - 1, 2 * B + 7, 40 * B + 3])
def test_sharded_stream_parity(rng, mesh, fmt, differential, n):
    vals = np.sort(rng.integers(0, 2**20, n)).astype(np.uint64)
    if not differential:
        vals = rng.integers(0, 2**32, n).astype(np.uint64)
    arr = CompressedIntArray.encode(vals, format=fmt, block_size=B,
                                    differential=differential)
    ref = np.asarray(arr.decode_blocked(plan="jnp"))
    sh = arr.shard(mesh)
    assert sh.n_blocks % len(jax.devices()) == 0  # padded to divide the mesh
    out = np.asarray(dispatch.decode(sh, plan="sharded"))
    np.testing.assert_array_equal(out[: arr.n_blocks], ref)
    assert not out[arr.n_blocks:].any()  # padding blocks decode to nothing
    # the flat decode (and the auto-selected path) agree too
    np.testing.assert_array_equal(sh.decode(), vals.astype(np.uint32))


@multi_device
@pytest.mark.parametrize("fmt", FMTS)
def test_sharded_ragged_with_empty_bags(rng, mesh, fmt):
    """Ragged layout: count=0 bags interleaved; sharded == single-device."""
    lists = [np.sort(rng.choice(np.arange(1, 500), size=k, replace=False))
             .astype(np.uint64)
             for k in rng.integers(0, B + 1, size=11)]
    lists[2] = np.zeros(0, np.uint64)
    lists[10] = np.zeros(0, np.uint64)
    arr = CompressedIntArray.encode_ragged(lists, format=fmt, block_size=B,
                                           differential=True)
    sh = arr.shard(mesh)
    np.testing.assert_array_equal(sh.decode(), arr.decode())
    ref = np.asarray(arr.decode_blocked(plan="jnp"))
    out = np.asarray(sh.decode_blocked())
    np.testing.assert_array_equal(out[: arr.n_blocks], ref)


@multi_device
def test_plan_sharded_requires_sharded_operands(rng):
    arr, _ = CompressedIntArray.encode(
        np.arange(100, dtype=np.uint64)), None
    with pytest.raises(ValueError, match="requires operands"):
        dispatch.decode(arr, plan="sharded")


# ---------------------------------------------------------------------------
# fused epilogue parity
# ---------------------------------------------------------------------------
@multi_device
@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("plan", ["jnp", "kernel"])
def test_sharded_fused_epilogues_parity(rng, mesh, fmt, plan):
    vals = np.sort(rng.integers(0, 512, 10 * B + 9)).astype(np.uint64)
    table = jnp.asarray(rng.standard_normal((512, 16)).astype(np.float32))
    q1 = jnp.asarray(rng.standard_normal((1, 16)).astype(np.float32))
    q4 = jnp.asarray(rng.standard_normal((4, 16)).astype(np.float32))
    arr = CompressedIntArray.encode(vals, format=fmt, block_size=B,
                                    differential=True)
    sh = arr.shard(mesh)
    nb = arr.n_blocks
    eb = jnp.asarray(rng.integers(0, 512, (sh.n_blocks, B)).astype(np.int32))
    cases = [
        ("bag_sum", {"table": table}, {"table": table}),
        ("dot_score", {"table": table, "query": q1}, None),
        ("dot_score", {"table": table, "query": q4}, None),  # microbatched
        ("adjacency_rebase", {"edge_base": eb}, {"edge_base": eb[:nb]}),
    ]
    for ep, eops, ref_eops in cases:
        ref = dispatch.decode(arr, epilogue=ep,
                              epilogue_operands=ref_eops or eops, plan=plan)
        out = dispatch.decode(sh, epilogue=ep, epilogue_operands=eops,
                              plan=plan)
        for r, o in zip(_tuple(ref), _tuple(out)):
            r, o = np.asarray(r), np.asarray(o)
            if r.ndim == 3:  # dot_score's [n_queries, n_blocks, B] scores
                o = o[:, : r.shape[1]]
            np.testing.assert_array_equal(r, o[: r.shape[0]],
                                          err_msg=f"{fmt}/{ep}/{plan}")


@multi_device
def test_multi_query_dot_score_equals_per_query(rng, mesh):
    """The [b, d] query microbatch scores == b single-query passes."""
    vals = np.sort(rng.integers(0, 256, 4 * B)).astype(np.uint64)
    table = jnp.asarray(rng.standard_normal((256, 8)).astype(np.float32))
    qs = jnp.asarray(rng.standard_normal((3, 8)).astype(np.float32))
    sh = CompressedIntArray.encode(vals, block_size=B,
                                   differential=True).shard(mesh)
    ids_b, scores_b = dispatch.decode(
        sh, epilogue="dot_score",
        epilogue_operands={"table": table, "query": qs})
    assert scores_b.ndim == 3  # [3, nb, B]
    for j in range(3):
        ids_1, scores_1 = dispatch.decode(
            sh, epilogue="dot_score",
            epilogue_operands={"table": table, "query": qs[j:j + 1]})
        np.testing.assert_array_equal(np.asarray(ids_b), np.asarray(ids_1))
        np.testing.assert_array_equal(np.asarray(scores_b)[j],
                                      np.asarray(scores_1))


# ---------------------------------------------------------------------------
# no cross-device decode traffic
# ---------------------------------------------------------------------------
@multi_device
@pytest.mark.parametrize("fmt", FMTS)
def test_sharded_decode_compiles_without_collectives(rng, mesh, fmt):
    """The whole point of block-parallel decode: the compiled program moves
    no decoded (or compressed) bytes between devices."""
    vals = np.sort(rng.integers(0, 2**18, 16 * B)).astype(np.uint64)
    sh = CompressedIntArray.encode(vals, format=fmt, block_size=B,
                                   differential=True).shard(mesh)
    fn = dispatch._build_sharded_fn(
        mesh, ("data",), fmt, "stream", B, True, DecodePlan("jnp", True),
        None, False)
    txt = fn.lower(sh.device_operands(), {}).compile().as_text()
    for coll in ("all-reduce", "all-gather", "collective-permute",
                 "all-to-all", "reduce-scatter"):
        assert coll not in txt, f"{fmt} sharded decode emitted {coll}"


# ---------------------------------------------------------------------------
# the serving engine on a mesh
# ---------------------------------------------------------------------------
@multi_device
def test_serving_engine_matches_direct_scoring(rng, mesh):
    from repro.launch.serve import ServingEngine
    from repro.models import recsys
    from repro.models.registry import reduced_config

    cfg = reduced_config("two-tower-retrieval")
    params = recsys.init_params(jax.random.PRNGKey(0), cfg)
    cands = np.sort(rng.choice(np.arange(1, cfg.n_items), 300,
                               replace=False)).astype(np.uint64)
    corpus = CompressedIntArray.encode(cands, differential=True)
    engine = ServingEngine(params, cfg, corpus, mesh=mesh, top_k=5)
    engine.warmup()

    uid = jnp.asarray([7, 3], jnp.int32)
    hist = jnp.asarray(rng.integers(1, cfg.n_items, (2, cfg.seq_len)),
                       jnp.int32)
    top_s, top_i = engine.retrieve(uid, hist)
    assert top_s.shape == (2, 5) and top_i.shape == (2, 5)
    top_i, top_s = np.asarray(top_i), np.asarray(top_s)
    assert np.all(np.isin(top_i, cands))  # pad slots masked out
    assert np.all(np.diff(top_s, axis=1) <= 1e-6)  # descending

    # direct reference: same user vectors against the same item table, in
    # the engine's compute dtype (bf16 gathers/dots, like the epilogue)
    from repro.kernels.vbyte_decode.gather_kernel import (gather_rows,
                                                          score_rows)

    u = engine._user_fn(params, uid, hist)  # [2, d] bf16
    vecs = gather_rows(engine.item_table, jnp.asarray(cands.astype(np.int32)),
                       cfg.embed_dim)
    direct = np.asarray(score_rows(vecs, u)).T
    for r in range(2):
        order = np.argsort(-direct[:, r], kind="stable")[:5]
        np.testing.assert_allclose(top_s[r], direct[order, r],
                                   rtol=1e-6, atol=1e-6)

    stats = engine.run_workload(
        [(1, rng.integers(1, cfg.n_items, cfg.seq_len).astype(np.int32))
         for _ in range(9)],
        max_batch=16)  # above the largest bucket: must clamp, not crash
    assert stats["n_requests"] == 9 and stats["qps"] > 0
    assert stats["p99_ms"] >= stats["p50_ms"] > 0
    assert stats["n_devices"] == len(jax.devices())


@multi_device
def test_engine_embedding_bag_endpoint(rng, mesh):
    from repro.launch.serve import ServingEngine
    from repro.models import recsys
    from repro.models.registry import reduced_config
    from repro.nn.embedding_bag import bag_from_padded

    cfg = reduced_config("two-tower-retrieval")
    params = recsys.init_params(jax.random.PRNGKey(0), cfg)
    corpus = CompressedIntArray.encode(
        np.arange(1, 200, dtype=np.uint64), differential=True)
    engine = ServingEngine(params, cfg, corpus, mesh=mesh)
    bags = [np.sort(rng.choice(np.arange(1, cfg.n_items), size=k,
                               replace=False))
            for k in (4, 1, cfg.seq_len)]
    out = np.asarray(engine.embed_bags(bags))
    assert out.shape == (3, cfg.id_dim)
    padded = np.zeros((3, cfg.seq_len), np.int32)
    for i, l in enumerate(bags):
        padded[i, : len(l)] = l
    ref = np.asarray(bag_from_padded(
        params["item_id_emb"]["emb"], jnp.asarray(padded), mode="mean",
        dtype=engine.dtype))
    np.testing.assert_allclose(out, ref, rtol=1e-2, atol=1e-2)
