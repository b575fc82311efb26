"""Two-tower retrieval served from several compressed corpora, against the
plain reference (``models.recsys_ref``), and the fused ``dot_score`` kernel
that reads its table from HBM, against the unfused path.

Tolerances. The kernel and the unfused path compute the same float32 sums
of the same products, in another order where the table is bfloat16, so
their scores agree to float32 rounding (``KERNEL_TOL``); ids are integers
and match exactly. The engine computes both towers in its compute dtype
and the reference in float32 from the same stored weights, so a score
differs by the towers' rounding: ``ENGINE_TOL[dtype]``. In bfloat16 that is
about three times the largest difference of six seeds at this config
(0.0026); in float32 only the order of the sums differs (1.2e-7).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import CompressedIntArray
from repro.kernels.vbyte_decode import dispatch
from repro.kernels.vbyte_decode.dispatch import DecodePlan
from repro.kernels.vbyte_decode.gather_kernel import row_table
from repro.launch.serve import ServingEngine, build_item_table
from repro.models import recsys, recsys_ref
from repro.models.registry import reduced_config

KERNEL_TOL = 1e-5
ENGINE_TOL = {jnp.float32: 1e-6, jnp.bfloat16: 8e-3}
TOP_K = 20


@pytest.fixture(scope="module")
def cfg():
    return dataclasses.replace(reduced_config("two-tower-retrieval"),
                               n_items=3000, n_users=400, id_dim=32,
                               embed_dim=64, mlp_dims=(128, 64))


@pytest.fixture(scope="module")
def params(cfg):
    return recsys.init_params(jax.random.PRNGKey(11), cfg)


def _corpora(rng, cfg, sizes):
    lists = [np.sort(rng.choice(np.arange(1, cfg.n_items), n, replace=False))
             for n in sizes]
    return lists, [CompressedIntArray.encode(c.astype(np.uint64),
                                             differential=True)
                   for c in lists]


def test_reference_decodes_the_program_encoding(rng):
    ids = np.sort(rng.choice(2**31, 1000, replace=False)).astype(np.uint64)
    arr = CompressedIntArray.encode(ids, differential=True)
    ops = {k: np.asarray(v) for k, v in arr.device_operands().items()}
    out = recsys_ref.vbyte_decode_blocked(ops["payload"], ops["counts"],
                                          ops["bases"])
    np.testing.assert_array_equal(out, ids.astype(np.uint32))
    gaps = np.diff(ids, prepend=np.uint64(0))
    plain = CompressedIntArray.encode(gaps, differential=False)
    ops = {k: np.asarray(v) for k, v in plain.device_operands().items()}
    np.testing.assert_array_equal(
        recsys_ref.vbyte_decode_blocked(ops["payload"], ops["counts"],
                                        ops["bases"], differential=False),
        gaps.astype(np.uint32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b", [1, 8])
def test_engine_retrieve_matches_reference(rng, cfg, params, b, dtype):
    lists, corpora = _corpora(rng, cfg, (700, 1100))
    engine = ServingEngine(params, cfg, corpora, top_k=TOP_K, buckets=(b,),
                           dtype=dtype)
    stored = jax.tree.map(lambda x: x.astype(dtype), params)
    uid = jnp.asarray(rng.integers(1, cfg.n_users, b), jnp.int32)
    hist = rng.integers(0, cfg.n_items, (b, cfg.seq_len)).astype(np.int32)
    hist[:, -3:] = 0  # padded histories
    hist = jnp.asarray(hist)
    tol = ENGINE_TOL[dtype]
    for j, cands in enumerate(lists):
        top_s, top_i = map(np.asarray, engine.retrieve(uid, hist, corpus=j))
        assert top_s.shape == top_i.shape == (b, TOP_K)
        assert np.isin(top_i, cands).all()  # from this corpus, no pad id
        ref = recsys_ref.scores(stored, uid, hist, cands)
        at = np.take_along_axis(ref, np.searchsorted(cands, top_i), axis=1)
        assert np.abs(top_s - at).max() <= tol
        for r in range(b):  # nothing clearly better was left out
            better = cands[ref[r] > top_s[r, -1] + tol]
            assert np.isin(better, top_i[r]).all()
        want_s, want_i = recsys_ref.top_k(ref, cands, TOP_K)
        if dtype == jnp.float32:
            np.testing.assert_allclose(top_s, want_s, atol=tol)


@pytest.mark.parametrize("chunk_rows", [256, 1000, 1 << 18])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunked_table_build_matches_one_shot(cfg, params, chunk_rows, dtype):
    ids = jnp.arange(cfg.vocab_rows, dtype=jnp.int32)
    stored = jax.tree.map(lambda x: x.astype(dtype), params)
    one_shot = row_table(jax.jit(lambda p: recsys.item_tower(
        p, ids, cfg, dtype=dtype))(stored).astype(dtype))
    chunked = build_item_table(stored, cfg, dtype=dtype,
                               chunk_rows=chunk_rows)
    np.testing.assert_array_equal(np.asarray(chunked), np.asarray(one_shot))


@pytest.mark.parametrize("fmt,chunk", [("vbyte", None), ("vbyte", 32),
                                       ("streamvbyte", 32),
                                       ("binpack", None)])
@pytest.mark.parametrize("n_queries", [1, 8])
@pytest.mark.parametrize("table_dtype,form", [(jnp.float32, "dense"),
                                              (jnp.bfloat16, "dense"),
                                              (jnp.bfloat16, "rows")])
def test_hbm_gather_kernel_matches_unfused(rng, fmt, chunk, n_queries,
                                           table_dtype, form):
    V, d = 3000, 32
    cands = np.sort(rng.choice(np.arange(1, V), 900, replace=False))
    arr = CompressedIntArray.encode(cands.astype(np.uint64), format=fmt,
                                    differential=True)
    table = jnp.asarray(rng.standard_normal((V, d)), table_dtype)
    query = jnp.asarray(rng.standard_normal((n_queries, d)), table_dtype)
    extras = {"table": row_table(table) if form == "rows" else table,
              "query": query}
    got = dispatch.decode(arr, epilogue="dot_score", epilogue_operands=extras,
                          plan=DecodePlan("pallas", True, 8, chunk=chunk))
    want = dispatch.decode(arr, epilogue="dot_score",
                           epilogue_operands={"table": table, "query": query},
                           plan="unfused")
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert got[1].shape == want[1].shape
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=KERNEL_TOL, atol=KERNEL_TOL)
    flat = np.asarray(got[0]).reshape(-1)
    np.testing.assert_array_equal(flat[:cands.size], cands)


@pytest.mark.parametrize("epilogue,downgrades", [("dot_score", 0),
                                                 ("bag_sum", 1)])
def test_dot_score_never_downgrades_above_the_vmem_budget(rng, epilogue,
                                                          downgrades):
    V, d = 8192, 256  # a float32 table of 8 MiB, twice the budget
    assert V * d * 4 > dispatch.VMEM_BROADCAST_BUDGET
    cands = np.sort(rng.choice(np.arange(1, V), 500, replace=False))
    arr = CompressedIntArray.encode(cands.astype(np.uint64),
                                    differential=True)
    table = jnp.asarray(rng.standard_normal((V, d)), jnp.float32)
    extras = {"table": table}
    if epilogue == "dot_score":
        extras["query"] = jnp.asarray(rng.standard_normal((1, d)),
                                      jnp.float32)
    tele = obs.Telemetry()
    with obs.install(tele):
        jax.block_until_ready(dispatch.decode(
            arr, epilogue=epilogue, epilogue_operands=extras,
            plan=DecodePlan("pallas", True, 8)))
    counters = tele.registry.snapshot()["metrics"]
    taken = sum(v["value"] for k, v in counters.items()
                if k.startswith("decode_plan_downgrade_total")
                and f"epilogue={epilogue}," in k)
    assert taken == downgrades, counters


def test_retrieve_spans_and_counters(rng, cfg, params):
    lists, corpora = _corpora(rng, cfg, (300, 500))
    engine = ServingEngine(params, cfg, corpora, top_k=5, buckets=(2,))
    uid = jnp.asarray([3, 9], jnp.int32)
    hist = jnp.asarray(rng.integers(1, cfg.n_items, (2, cfg.seq_len)),
                       jnp.int32)
    tele = obs.Telemetry()
    with obs.install(tele):
        for j in (0, 1, 1):
            jax.block_until_ready(engine.retrieve(uid, hist, corpus=j))
    spans = [s for s in tele.tracer.spans if s["type"] == "span"]
    by_id = {s["span_id"]: s for s in spans}
    tops = [s for s in spans if s["name"] == "retrieve"]
    assert [s["attrs"]["corpus"] for s in tops] == [0, 1, 1]
    for child in ("retrieve.user_tower", "retrieve.score", "retrieve.topk"):
        kids = [s for s in spans if s["name"] == child]
        assert len(kids) == 3
        assert all(by_id[s["parent_id"]]["name"] == "retrieve" for s in kids)
    assert all(by_id[s["parent_id"]]["name"] == "retrieve.score"
               for s in spans if s["name"] == "decode")
    counters = tele.registry.snapshot()["metrics"]
    assert counters["retrieval_candidates_total"]["value"] == 300 + 2 * 500
    assert counters["dot_score_rows_fetched_total"]["value"] == 128 * sum(
        c.n_blocks for c in (corpora[0], corpora[1], corpora[1]))


def test_hbm_gather_kernel_under_the_tpu_interpreter(rng):
    # the TPU interpreter models DMAs, SMEM and semaphores as the chip
    # does (interpret=True runs DMAs as plain copies): two tiles, so one
    # tile's rows are fetched while the other's are scored
    from jax import lax
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.vbyte_decode import epilogues
    from repro.kernels.vbyte_decode.gather_kernel import dot_score_pallas

    V, d, B = 2000, 256, 32
    cands = np.sort(rng.choice(np.arange(1, V), 400, replace=False))
    arr = CompressedIntArray.encode(cands.astype(np.uint64), block_size=B,
                                    differential=True)
    ops = arr.device_operands()
    pad = (-arr.n_blocks) % 8
    payload = jnp.pad(ops["payload"], ((0, pad), (0, 0)))
    counts = jnp.pad(ops["counts"].reshape(-1), (0, pad))[:, None]
    bases = jnp.pad(lax.bitcast_convert_type(
        ops["bases"].reshape(-1).astype(jnp.uint32), jnp.int32), (0, pad))
    table = jnp.asarray(rng.standard_normal((V, d)), jnp.bfloat16)
    query = jnp.asarray(rng.standard_normal((8, d)), jnp.bfloat16)
    decode = epilogues._tile_decoder("vbyte", block_size=B, differential=True,
                                     chunk_width=None)
    ids, scores = dot_score_pallas(
        decode, (payload,), counts.astype(jnp.int32), bases[:, None],
        row_table(table), query, block_size=B, block_tile=8,
        name="dot_score_tpu_interpreter",
        interpret=pltpu.InterpretParams())
    flat = np.asarray(ids).reshape(-1)
    assert (arr.n_blocks + pad) // 8 == 2
    np.testing.assert_array_equal(flat[:cands.size], cands)
    assert not flat[cands.size:].any()
    want = (np.asarray(table, np.float32)[flat]
            @ np.asarray(query, np.float32).T)
    np.testing.assert_allclose(np.asarray(scores).T, want, rtol=KERNEL_TOL,
                               atol=KERNEL_TOL)


@pytest.mark.parametrize("layout", ["near", "far", "mixed"])
def test_hbm_gather_kernel_fetch_modes(rng, layout):
    # a tile whose ids lie within span_rows fetches their span whole, one
    # whose ids lie farther apart fetches each id's group of rows; under
    # the TPU interpreter, with the span at its least (8 rows a candidate)
    from jax import lax
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.vbyte_decode import epilogues
    from repro.kernels.vbyte_decode.gather_kernel import dot_score_pallas

    V, d, B, T = 40000, 32, 32, 8
    near = np.sort(rng.choice(np.arange(1, 2000), 600, replace=False))
    far = np.sort(rng.choice(np.arange(2000, V), 600, replace=False))
    cands = {"near": near, "far": far,
             "mixed": np.concatenate([near[:300], far[300:]])}[layout]
    arr = CompressedIntArray.encode(cands.astype(np.uint64), block_size=B,
                                    differential=True)
    ops = arr.device_operands()
    pad = (-arr.n_blocks) % T
    payload = jnp.pad(ops["payload"], ((0, pad), (0, 0)))
    counts = jnp.pad(ops["counts"].reshape(-1), (0, pad))[:, None]
    bases = jnp.pad(lax.bitcast_convert_type(
        ops["bases"].reshape(-1).astype(jnp.uint32), jnp.int32), (0, pad))
    table = jnp.asarray(rng.standard_normal((V, d)), jnp.bfloat16)
    query = jnp.asarray(rng.standard_normal((2, d)), jnp.bfloat16)
    decode = epilogues._tile_decoder("vbyte", block_size=B, differential=True,
                                     chunk_width=None)
    ids, scores = dot_score_pallas(
        decode, (payload,), counts.astype(jnp.int32), bases[:, None],
        row_table(table), query, block_size=B, block_tile=T,
        span_rows=8 * T * B, name="dot_score_fetch_modes",
        interpret=pltpu.InterpretParams())
    flat = np.asarray(ids).reshape(-1)
    np.testing.assert_array_equal(flat[:cands.size], cands)
    want = (np.asarray(table, np.float32)[flat]
            @ np.asarray(query, np.float32).T)
    np.testing.assert_allclose(np.asarray(scores).T, want, rtol=KERNEL_TOL,
                               atol=KERNEL_TOL)
