"""Training infra: optimizer, checkpointing, fault tolerance, grad compression,
embedding bag, data pipeline, neighbor sampler."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.data.pipeline import CompressedTokenPipeline
from repro.data.sampler import CSRGraph, NeighborSampler
from repro.data.synthetic import random_graph, token_stream
from repro.ft import StragglerDetector, plan_mesh, reshard_plan
from repro.nn.embedding_bag import bag_from_padded, embedding_bag
from repro.train.grad_compress import (compress_grads_with_ef, compressed_psum,
                                       dequantize, init_ef_state, quantize)
from repro.train.optimizer import (OptimizerConfig, adamw_update, init_opt_state,
                                   lr_schedule)


# -- optimizer ----------------------------------------------------------------
def test_adamw_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    opt = init_opt_state(params)
    cfg = OptimizerConfig(peak_lr=0.3, warmup_steps=1, total_steps=200,
                          weight_decay=0.0, grad_clip=10.0)
    for _ in range(150):
        g = {"w": 2 * params["w"]}
        params, opt, m = adamw_update(params, g, opt, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.2
    assert int(opt["step"]) == 150


def test_grad_clip_applied():
    params = {"w": jnp.zeros(3)}
    opt = init_opt_state(params)
    cfg = OptimizerConfig(grad_clip=1.0, peak_lr=1.0, warmup_steps=0)
    _, _, m = adamw_update(params, {"w": jnp.full(3, 100.0)}, opt, cfg)
    assert float(m["grad_norm"]) > 100.0  # reported pre-clip


def test_lr_schedule_shape():
    cfg = OptimizerConfig(peak_lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_frac=0.1)
    assert float(lr_schedule(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(lr_schedule(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(lr_schedule(cfg, jnp.int32(100))) == pytest.approx(0.1)


# -- checkpoint ---------------------------------------------------------------
def test_checkpoint_roundtrip(tmp_path):
    state = {
        "params": {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                   "emb": jnp.ones((4, 2), jnp.bfloat16)},
        "steps": jnp.arange(1000, dtype=jnp.int32),  # vbyte-compressed leaf
        "neg": jnp.array([-5, 3, -1], jnp.int32),  # zigzag path
    }
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(3, state)
    mgr.save(7, state)
    restored, step = mgr.restore_latest(state)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_prune_and_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    state = {"x": jnp.ones(10)}
    for s in (1, 2, 3, 4):
        mgr.save(s, state, async_=True)
    mgr.wait()
    assert mgr.steps() == [3, 4]


def test_checkpoint_atomicity_no_partial_dirs(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"x": jnp.ones(3)})
    assert all(not d.startswith(".tmp") for d in os.listdir(tmp_path))


# -- fault tolerance ----------------------------------------------------------
def test_straggler_detection():
    det = StragglerDetector(slow_factor=2.0, dead_factor=5.0)
    clocks = {"host0": 0.0, "host1": 0.0, "host2": 0.0}
    for step in range(10):
        for h in clocks:
            dt = 3.0 if h == "host2" and step >= 5 else 1.0  # host2 slows down
            clocks[h] += dt
            det.heartbeat(h, step, now=clocks[h])
    assert det.stragglers(now=max(clocks.values())).get("host2") == "slow"
    # host1 goes silent
    t = max(clocks.values())
    for step in range(10, 14):
        t += 1.0
        det.heartbeat("host0", step, now=t)
        det.heartbeat("host2", step, now=t)
    assert det.stragglers(now=t + 10).get("host1") == "dead"


def test_plan_mesh_degraded():
    full = plan_mesh(512)
    assert full.shape == (2, 16, 16) and full.axis_names[0] == "pod"
    degraded = plan_mesh(512 - 16)  # lost a host of 16 chips
    assert degraded.n_chips <= 496 and degraded.shape[-1] == 16
    assert plan_mesh(256).shape == (16, 16)
    with pytest.raises(ValueError):
        plan_mesh(8)


def test_reshard_plan_covers_exactly():
    for dim, old, new in [(64, 16, 8), (64, 8, 16), (96, 16, 12), (128, 4, 4)]:
        plan = reshard_plan(dim, old, new)
        covered = []
        news = [(i * -(-dim // new), min((i + 1) * -(-dim // new), dim))
                for i in range(new)]
        for (lo, hi), srcs in zip(news, plan):
            olds = [(s * -(-dim // old), min((s + 1) * -(-dim // old), dim))
                    for s in range(old)]
            got = sorted((olds[s][0] + a, olds[s][0] + b) for s, a, b in srcs)
            total = sum(b - a for a, b in got)
            assert total == hi - lo, (dim, old, new)
            covered.extend(got)
        assert sum(b - a for a, b in covered) == dim


# -- grad compression ----------------------------------------------------------
def test_quantize_error_bound(rng):
    x = jnp.asarray(rng.standard_normal(1000), jnp.float32)
    q, s = quantize(x)
    err = np.abs(np.asarray(dequantize(q, s) - x))
    assert err.max() <= float(s) * 0.5 + 1e-7


def test_error_feedback_preserves_signal():
    g = {"w": jnp.full((100,), 1e-4)}  # tiny grads: quantizer would zero them
    ef = init_ef_state(g)
    total = np.zeros(100, np.float32)
    for _ in range(50):
        deq, ef = compress_grads_with_ef(g, ef)
        total += np.asarray(deq["w"])
    # with EF the accumulated update approaches the true sum
    np.testing.assert_allclose(total.mean(), 50 * 1e-4, rtol=0.05)


def test_compressed_psum_single_device():
    mesh = Mesh(np.array(jax.devices()[:1]), ("d",))
    x = jnp.asarray(np.random.default_rng(0).standard_normal(64), jnp.float32)
    f = jax.shard_map(lambda v: compressed_psum(v, "d"), mesh=mesh,
                      in_specs=P(), out_specs=P())
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x), atol=2e-2)


# -- embedding bag -------------------------------------------------------------
def test_embedding_bag_matches_numpy(rng):
    table = rng.standard_normal((50, 8), dtype=np.float32)
    ids = rng.integers(0, 50, 40).astype(np.int32)
    segs = np.sort(rng.integers(0, 6, 40)).astype(np.int32)
    out = embedding_bag(jnp.asarray(table), jnp.asarray(ids), jnp.asarray(segs),
                        6, mode="sum", dtype=jnp.float32)
    ref = np.zeros((6, 8), np.float32)
    np.add.at(ref, segs, table[ids])
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)


def test_bag_from_padded_ignores_pad(rng):
    table = rng.standard_normal((20, 4), dtype=np.float32)
    ids = np.array([[1, 2, 0, 0], [3, 0, 0, 0]], np.int32)
    out = bag_from_padded(jnp.asarray(table), jnp.asarray(ids), mode="sum",
                          dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out)[0], table[1] + table[2], atol=1e-6)
    np.testing.assert_allclose(np.asarray(out)[1], table[3], atol=1e-6)


# -- data pipeline -------------------------------------------------------------
def test_token_pipeline_roundtrip(rng):
    toks = token_stream(rng, 4096, 1000)
    pipe = CompressedTokenPipeline(toks, batch=4, seq_len=63, plan="kernel")
    b0 = pipe.get_batch(0)
    assert b0["tokens"].shape == (4, 64)
    np.testing.assert_array_equal(np.asarray(b0["tokens"]).reshape(-1),
                                  toks[:256].astype(np.int32))
    assert pipe.compression_ratio() > 1.5  # zipf tokens are small ints


# -- neighbor sampler ----------------------------------------------------------
def test_neighbor_sampler(rng):
    g = random_graph(rng, 500, 5000, 4, 3)
    csr = CSRGraph.from_edges(g["edge_src"], g["edge_dst"], 500)
    samp = NeighborSampler(csr, fanouts=(5, 3))
    seeds = rng.choice(500, 32, replace=False)
    out = samp.sample(seeds, rng)
    e_cap = samp.edge_capacity(32)
    assert out["edge_src"].shape == (e_cap,)
    assert out["edge_valid"].sum() <= e_cap
    n_valid = int(out["edge_valid"].sum())
    # every sampled edge must exist in the CSR (dst row contains src)
    node_ids = out["node_ids"]
    for i in rng.choice(n_valid, size=min(50, n_valid), replace=False):
        s, d = node_ids[out["edge_src"][i]], node_ids[out["edge_dst"][i]]
        row = csr.indices[csr.indptr[d]:csr.indptr[d + 1]]
        assert s in row
    assert set(out["seed_ids"].tolist()) <= set(range(len(node_ids)))


# -- compile cache placement ----------------------------------------------------
@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        if from_env:  # JAX reads the variable itself; nothing else is set
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == prev
        else:  # a fixed, gitignored directory at the checkout's root
            assert got == compile_cache.DEFAULT_CACHE_DIR
            assert jax.config.jax_compilation_cache_dir == got
            assert os.path.basename(got) == ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
