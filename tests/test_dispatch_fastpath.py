"""Dispatch layer: the per-signature launch cache of ``dispatch.decode``.

A repeat call on concrete device arrays of a signature seen before runs
the stored entry (a hit); the first call of a signature resolves it in
full and stores the entry (a miss); a call whose leaves are not all
concrete ``jax.Array``s (numpy, tracers under ``jit``) takes the full path
and stores nothing (a bypass)."""
import gc
import os
import sys
import threading
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import CompressedIntArray
from repro.kernels.vbyte_decode import dispatch
from repro.kernels.vbyte_decode.dispatch import DecodePlan

VOCAB, D = 512, 8


def _on_device(arr):
    """``arr`` with every leaf a committed device array."""
    ops = {k: jax.device_put(v) for k, v in arr.device_operands().items()}
    return CompressedIntArray.from_operands(
        ops, format=arr.format, block_size=arr.block_size,
        differential=arr.differential, n=arr.n)


def _on_host(arr):
    ops = {k: np.asarray(v) for k, v in arr.device_operands().items()}
    return CompressedIntArray.from_operands(
        ops, format=arr.format, block_size=arr.block_size,
        differential=arr.differential, n=arr.n)


def _list(seed, n=300, *, fmt="vbyte", block_size=32):
    vals = np.sort(np.random.default_rng(seed).integers(0, VOCAB, n))
    return vals, _on_device(CompressedIntArray.encode(
        vals.astype(np.uint64), format=fmt, block_size=block_size,
        differential=True))


def _extras(epilogue, seed=0, *, d=D, queries=1):
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.standard_normal((VOCAB, d)).astype(np.float32))
    if epilogue == "bag_sum":
        return {"table": table}
    if epilogue == "dot_score":
        return {"table": table, "query": jnp.asarray(
            rng.standard_normal((queries, d)).astype(np.float32))}
    return {}


def _results(tele):
    counters = tele.registry.snapshot()["metrics"]
    return {r: counters.get(f"decode_fastpath_total{{result={r}}}",
                            {"value": 0})["value"]
            for r in ("hit", "miss", "bypass")}


@pytest.fixture
def tele():
    dispatch._LAUNCHES.clear()
    t = obs.Telemetry()
    with obs.install(t):
        yield t
    dispatch._LAUNCHES.clear()


def _assert_same(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("epilogue", ["stream", "checksum", "dot_score",
                                      "bag_sum"])
@pytest.mark.parametrize("fmt", ["vbyte", "streamvbyte", "binpack"])
def test_hit_is_bit_identical_to_the_miss(tele, fmt, epilogue):
    _, arr = _list(1, fmt=fmt)
    extras = _extras(epilogue)
    kw = dict(epilogue=epilogue, epilogue_operands=extras)
    miss = dispatch.decode(arr, **kw)
    hit = dispatch.decode(arr, **kw)
    assert _results(tele) == {"hit": 1, "miss": 1, "bypass": 0}
    _assert_same(miss, hit)
    # and both are what the full path gives for the same list on the host
    _assert_same(hit, dispatch.decode(_on_host(arr), **kw))
    assert _results(tele)["bypass"] == 1


@pytest.mark.parametrize("epilogue", ["stream", "bag_sum"])
def test_pallas_hit_is_bit_identical_to_the_miss(tele, epilogue):
    vals, arr = _list(2)
    kw = dict(epilogue=epilogue, epilogue_operands=_extras(epilogue),
              plan="kernel")
    miss = dispatch.decode(arr, **kw)
    _assert_same(miss, dispatch.decode(arr, **kw))
    _assert_same(miss, dispatch.decode(_on_host(arr), **kw))
    assert _results(tele) == {"hit": 1, "miss": 1, "bypass": 1}
    if epilogue == "stream":
        np.testing.assert_array_equal(
            np.asarray(miss).reshape(-1)[: vals.size], vals)


def test_a_new_list_of_a_seen_signature_hits(tele):
    vals_a, a = _list(3)
    vals_b, b = _list(4)
    assert a.payload.shape == b.payload.shape
    out_a = dispatch.decode(a)
    out_b = dispatch.decode(b)
    assert _results(tele) == {"hit": 1, "miss": 1, "bypass": 0}
    np.testing.assert_array_equal(np.asarray(out_a).reshape(-1)[:300],
                                  vals_a)
    np.testing.assert_array_equal(np.asarray(out_b).reshape(-1)[:300],
                                  vals_b)


def _variants():
    """(name, call) pairs whose signatures all differ from each other."""
    _, base = _list(5)
    _, longer = _list(5, n=600)
    bases_i32 = CompressedIntArray.from_operands(
        {"payload": base.payload, "counts": base.counts,
         "bases": base.bases.astype(jnp.int32)},
        format="vbyte", block_size=32, differential=True, n=base.n)
    column = dict(base.device_operands(), counts=base.counts[:, None])
    raw = dict(format="vbyte", block_size=32, differential=True)
    probe = jnp.asarray([[3, 5, 7, -1]], jnp.int32)
    _, w_vbyte = _list(6)
    _, w_svb = _list(6, fmt="streamvbyte")
    return [
        ("base", lambda: dispatch.decode(base)),
        ("shape", lambda: dispatch.decode(longer)),
        ("dtype", lambda: dispatch.decode(bases_i32)),
        ("meta_column", lambda: dispatch.decode(column, **raw)),
        ("plan", lambda: dispatch.decode(base, plan="jnp")),
        ("plan_object", lambda: dispatch.decode(
            base, plan=DecodePlan("jnp", False))),
        ("epilogue", lambda: dispatch.decode(base, epilogue="checksum")),
        ("extras", lambda: dispatch.decode(
            base, epilogue="bag_sum", epilogue_operands=_extras("bag_sum"))),
        ("extras_shape", lambda: dispatch.decode(
            base, epilogue="bag_sum",
            epilogue_operands=_extras("bag_sum", d=16))),
        ("extras_keys", lambda: dispatch.decode(
            base, epilogue="bm25_weighted",
            epilogue_operands={"probe": probe,
                               "w_payload": w_vbyte.payload})),
        ("extras_optional", lambda: dispatch.decode(
            base, epilogue="bm25_weighted",
            epilogue_operands={"probe": probe, "w_control": w_svb.control,
                               "w_data": w_svb.data})),
        ("queries", lambda: dispatch.decode(
            base, epilogue="dot_score",
            epilogue_operands=_extras("dot_score", queries=3))),
    ]


def test_key_separates_every_part_of_the_signature(tele):
    variants = _variants()
    firsts = {name: call() for name, call in variants}
    assert _results(tele) == {"hit": 0, "miss": len(variants), "bypass": 0}
    for name, call in variants:  # each has an entry of its own
        _assert_same(firsts[name], call())
    assert _results(tele) == {"hit": len(variants), "miss": len(variants),
                              "bypass": 0}
    assert len(dispatch._LAUNCHES) == len(variants)


def test_column_metadata_hit_matches_flat_metadata(tele):
    vals, arr = _list(7)
    raw = dict(format="vbyte", block_size=32, differential=True)
    column = dict(arr.device_operands(), counts=arr.counts[:, None],
                  bases=arr.bases[:, None])
    for _ in range(2):
        out = dispatch.decode(column, **raw)
        np.testing.assert_array_equal(np.asarray(out).reshape(-1)[:300],
                                      vals)
    assert _results(tele) == {"hit": 1, "miss": 1, "bypass": 0}


def test_tracer_under_jit_bypasses_and_decodes(tele):
    vals, arr = _list(8)
    f = jax.jit(lambda a: dispatch.decode(a, plan="jnp"))
    out = f(arr)
    np.testing.assert_array_equal(np.asarray(out).reshape(-1)[:300], vals)
    assert _results(tele) == {"hit": 0, "miss": 0, "bypass": 1}
    assert len(dispatch._LAUNCHES) == 0


def test_numpy_leaves_bypass(tele):
    vals, arr = _list(9)
    for _ in range(2):
        out = dispatch.decode(_on_host(arr))
        np.testing.assert_array_equal(np.asarray(out).reshape(-1)[:300],
                                      vals)
    assert _results(tele) == {"hit": 0, "miss": 0, "bypass": 2}
    assert len(dispatch._LAUNCHES) == 0


def test_numpy_extra_bypasses(tele):
    _, arr = _list(10)
    table = np.asarray(_extras("bag_sum")["table"])
    for _ in range(2):
        dispatch.decode(arr, epilogue="bag_sum",
                        epilogue_operands={"table": table})
    assert _results(tele) == {"hit": 0, "miss": 0, "bypass": 2}


def _malformed():
    _, arr = _list(14)
    short = dict(arr.device_operands(), counts=arr.counts[:-1])
    no_bases = {k: v for k, v in arr.device_operands().items()
                if k != "bases"}
    raw = dict(format="vbyte", block_size=32, differential=True)
    return [
        ("counts", ValueError, "counts must have shape",
         lambda: dispatch.decode(short, **raw)),
        ("missing", ValueError, "missing",
         lambda: dispatch.decode(no_bases, **raw)),
        ("epilogue", ValueError, "unknown epilogue",
         lambda: dispatch.decode(arr, epilogue="frobnicate")),
        ("extras", ValueError, "missing \\['table'\\]",
         lambda: dispatch.decode(arr, epilogue="bag_sum")),
        ("format", ValueError, "unknown format",
         lambda: dispatch.decode(arr.device_operands(), format="lz4",
                                 block_size=32, differential=True)),
        ("sharded", ValueError, "requires operands whose block dimension",
         lambda: dispatch.decode(arr, plan="sharded")),
        ("launch", ValueError, "only exists for format='vbyte'",
         lambda: dispatch.decode(_list(15, fmt="streamvbyte")[1],
                                 plan=DecodePlan("ref", False))),
    ]


@pytest.mark.parametrize("case", range(7))
def test_malformed_call_raises_every_time_and_stores_nothing(tele, case):
    name, exc, match, call = _malformed()[case]
    for _ in range(3):
        with pytest.raises(exc, match=match):
            call()
    assert len(dispatch._LAUNCHES) == 0, name
    assert _results(tele)["hit"] == 0


def test_an_entry_keeps_no_array_alive(tele):
    _, arr = _list(16)
    extras = _extras("dot_score")
    dispatch.decode(arr, epilogue="dot_score", epilogue_operands=extras)
    jax.block_until_ready(dispatch.decode(arr, epilogue="dot_score",
                                          epilogue_operands=extras))
    assert len(dispatch._LAUNCHES) == 1
    refs = [weakref.ref(arr), weakref.ref(arr.payload),
            weakref.ref(arr.counts), weakref.ref(extras["table"])]
    del arr, extras
    gc.collect()
    assert [r() for r in refs] == [None] * len(refs)
    assert len(dispatch._LAUNCHES) == 1


def test_the_cache_is_a_bounded_lru(tele, monkeypatch):
    monkeypatch.setattr(dispatch, "LAUNCH_CACHE_SIZE", 2)
    lists = [_list(17, n=n)[1] for n in (40, 80, 120)]
    for a in lists:
        dispatch.decode(a)
    assert len(dispatch._LAUNCHES) == 2
    dispatch.decode(lists[2])  # newest: kept
    dispatch.decode(lists[0])  # oldest: evicted, resolved again
    assert _results(tele) == {"hit": 1, "miss": 4, "bypass": 0}
    assert len(dispatch._LAUNCHES) == 2


def test_threads_sharing_the_cache_decode_correctly(tele, monkeypatch):
    """More threads than cores, a short switch interval and a cache smaller
    than the signatures in use, so hits, misses and evictions interleave."""
    monkeypatch.setattr(dispatch, "LAUNCH_CACHE_SIZE", 3)
    rounds = 12
    lists = [_list(21 + i, n=n) for i, n in enumerate((40, 80, 120, 160,
                                                      200, 240))]
    for _, a in lists:  # compile outside the threads
        dispatch.decode(a)
    errors, done = [], []

    def work(k):
        try:
            for r in range(rounds):
                vals, a = lists[(k + r) % len(lists)]
                got = np.asarray(dispatch.decode(a)).reshape(-1)[:vals.size]
                if not np.array_equal(got, vals):
                    errors.append((k, r))
            done.append(k)
        except Exception as e:  # reported by the assertion below
            errors.append(repr(e))

    n = os.cpu_count() + 4
    threads = [threading.Thread(target=work, args=(k,)) for k in range(n)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert errors == [] and sorted(done) == list(range(n))
    assert len(dispatch._LAUNCHES) <= 3
    r = _results(tele)
    assert r["hit"] + r["miss"] == len(lists) + rounds * n
    assert r["bypass"] == 0


def test_hit_keeps_spans_and_call_counter(tele):
    _, arr = _list(18)
    dispatch.decode(arr)
    first = [s for s in tele.tracer.spans if s["name"] == "decode"][0]
    dispatch.decode(arr)
    spans = [s for s in tele.tracer.spans if s["name"].startswith("decode")]
    assert [s["name"] for s in spans] == [
        "decode.prepare", "decode.launch", "decode"] * 2
    assert spans[-1]["attrs"] == first["attrs"]
    counters = tele.registry.snapshot()["metrics"]
    (calls,) = [v["value"] for k, v in counters.items()
                if k.startswith("decode_calls_total")]
    assert calls == 2


def test_counter_is_silent_without_telemetry():
    dispatch._LAUNCHES.clear()
    _, arr = _list(19)
    dispatch.decode(arr)
    dispatch.decode(arr)
    assert obs.installed() is None
    dispatch._LAUNCHES.clear()


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >1 device (XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4)")
@pytest.mark.parametrize("epilogue", ["stream", "bag_sum"])
def test_sharded_hit_runs_the_sharded_program(tele, epilogue):
    mesh = jax.make_mesh((len(jax.devices()),), ("data",))
    vals, arr = _list(20, n=1000)
    sh = arr.shard(mesh)
    kw = dict(epilogue=epilogue, epilogue_operands=_extras(epilogue))
    miss = dispatch.decode(sh, **kw)
    hit = dispatch.decode(sh, **kw)
    assert _results(tele) == {"hit": 1, "miss": 1, "bypass": 0}
    _assert_same(miss, hit)
    assert jax.tree_util.tree_leaves(hit)[0].sharding.spec[0] == "data"
    spans = [s for s in tele.tracer.spans if s["name"] == "decode"]
    assert [s["attrs"]["sharded"] for s in spans] == [True, True]
    want = dispatch.decode(arr, **kw)
    _assert_same(jax.tree_util.tree_map(lambda x: x[: arr.n_blocks], hit),
                 want)
