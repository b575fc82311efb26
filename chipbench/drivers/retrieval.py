"""Driver ``retrieval``: two-tower retrieval through ``ServingEngine.retrieve``.

Set-up, all of it from the seed:

* weights: the model's own initializers (``recsys.init_params``) under a
  key drawn from the seed, each floating parameter stored in the serving
  dtype as it is made, on the device;
* corpora: the traffic's ``corpora`` lists of the configuration's
  ``n_candidates`` sorted item ids, drawn uniformly without replacement
  from ids ``1..n_items`` by sorted-gap sampling, d-gap Masked VByte in
  128-int blocks (``gen.vbyte_blocked``, on the host, about a second),
  every list padded to the largest stride among them, so that one program
  serves them all; each is a ``CompressedIntArray`` on the device;
* requests: a pool of :data:`POOL_CALLS` microbatches of ``bucket``
  requests, user ids Zipf(``user_zipf_s``) over the users and histories of
  ``history`` item ids Zipf(``item_zipf_s``) over the items, id ``k`` the
  ``k``-th most popular; on the device, one array per microbatch.

Then the engine is built (its item table chunk by chunk) and every shape
is warmed up.

The window serves the pool's microbatches in turn, each against the next
list of a seeded round robin, through ``engine.retrieve``. The caller keeps
the traffic's ``in_flight`` calls issued: it issues the next call before it
waits, with ``block_until_ready``, for the oldest. The window closes when
the first whole pass over the lists that ends after ``seconds`` has
completed. It keeps the last output of :data:`CHECKED` lists drawn from
the seed, with the microbatch it answered; after the window each kept
answer is compared with ``models.recsys_ref``, computed on the device in
blocks of candidates, layer by layer: the user vectors, the stored item
rows of the returned ids, the scores against what the kernel read, and the
ids against the reference's ranking (:func:`check`).
"""
from __future__ import annotations

import functools
import sys
import time
from collections import deque
from dataclasses import dataclass

import numpy as np

from chipbench import gen, work, work_retrieval
from chipbench.harness import Window

SPAN_NAMES = ("retrieve",)
CHECKED = 4  # lists whose last answer the window keeps for the check
POOL_CALLS = 1024  # distinct microbatches of requests, served in turn
# Limits of the comparison (see check). The serving path runs both towers
# in bfloat16 (float32 accumulation) from bfloat16 weights, stores the item
# vectors in bfloat16 and scores them in float32; the reference runs the
# towers in float32 from the same weights. Each limit holds one layer and
# lies between the program's largest reading and that of a control one
# precision step below what the configuration states
# (controls_retrieval.py), both on a TPU v5e at the cell's size: the user
# tower (unit vectors) against float8 user-tower weights, the stored item
# table against a float8 table, and the scoring kernel against scores
# rounded to bfloat16.
USER_TOL = 1.6e-2  # |user vector - reference|, L2
ITEM_TOL = 1.4e-2  # |stored item vector - reference|, L2
SCORE_TOL = 1e-5  # |returned score - exact dot of what the kernel read|


@dataclass
class State:
    engine: object  # repro.launch.serve.ServingEngine
    params: dict  # the weights as made, which the reference reads
    entry: object  # what the window drives: engine.retrieve
    lists: list  # host operands (payload, counts, bases) per list
    n_ints: np.ndarray  # real ids per list
    stored: np.ndarray  # stored bytes per list (work.stored_bytes)
    call_bytes: np.ndarray  # bytes of work of one call against each list
    users: list  # [bucket] int32 user ids per microbatch, on the device
    hists: list  # [bucket, history] int32 per microbatch, on the device
    users_host: np.ndarray  # the same, on the host, for the check
    hists_host: np.ndarray
    order: np.ndarray  # seeded round-robin order of list indices
    sample: frozenset  # lists whose last answer the window keeps
    in_flight: int
    bucket: int
    held: dict  # sampled list -> (microbatch index, (scores, ids))


def zipf_ids(rng: np.random.Generator, n: int, size, s: float) -> np.ndarray:
    """Ids ``1..n`` with ``P(k)`` proportional to ``k^-s``, by inverting the
    exact CDF."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -s)
    u = rng.random(size) * cdf[-1]
    return np.minimum(np.searchsorted(cdf, u, side="right") + 1,
                      n).astype(np.int32)


def model_config(config: dict):
    from repro.models.recsys import RecSysConfig

    return RecSysConfig(
        name=config["name"], kind=config["kind"], n_items=config["n_items"],
        n_users=config["n_users"], embed_dim=config["embed_dim"],
        id_dim=config["id_dim"], seq_len=config["seq_len"],
        mlp_dims=tuple(config["mlp_dims"]),
        serve_candidates=config["n_candidates"])


def _lists(config, traffic, seed, log):
    """The corpora's host operands, padded to one stride."""
    n, items = int(config["n_candidates"]), int(config["n_items"])
    block = int(config["block_size"])
    made = []
    for j in range(int(traffic["corpora"])):
        ids = 1 + gen.sorted_gap_list(gen.rng_for(seed, 20, j), n, items)
        made.append(gen.vbyte_blocked(ids, block))
    stride = max(p.shape[1] for p, _, _ in made)
    log(f"{len(made)} lists of {n} ids, {made[0][0].shape[0]} blocks, "
        f"strides {sorted({p.shape[1] for p, _, _ in made})} -> {stride}")
    return [{"payload": np.pad(p, ((0, 0), (0, stride - p.shape[1]))),
             "counts": c, "bases": b} for p, c, b in made]


def _requests(config, traffic, seed):
    rng = gen.rng_for(seed, 21)
    b, L = int(traffic["bucket"]), int(traffic["history"])
    users = zipf_ids(rng, int(config["n_users"]), (POOL_CALLS, b),
                     float(traffic["user_zipf_s"]))
    hists = zipf_ids(rng, int(config["n_items"]), (POOL_CALLS, b, L),
                     float(traffic["item_zipf_s"]))
    return users, hists


def setup(config, traffic, seed, *, devices, log):
    import jax
    import jax.numpy as jnp

    from repro.core import CompressedIntArray
    from repro.launch.serve import ServingEngine
    from repro.models import recsys

    if config["format"] != "vbyte" or not config["differential"]:
        raise ValueError("this driver serves d-gap vbyte lists only")
    dev = devices[0]
    cfg = model_config(config)
    dtype = jnp.dtype(config["serving_dtype"])
    t0 = time.perf_counter()
    key = jax.random.PRNGKey(int(gen.rng_for(seed, 22).integers(2**31)))
    params = jax.block_until_ready(jax.jit(lambda k: jax.tree.map(
        lambda x: x.astype(dtype), recsys.init_params(k, cfg)))(key))
    log(f"weights in {time.perf_counter() - t0:.1f} s")

    t0 = time.perf_counter()
    lists = _lists(config, traffic, seed, log)
    n = int(config["n_candidates"])
    corpora = [CompressedIntArray.from_operands(
        {k: jax.device_put(v, dev) for k, v in ops.items()},
        format="vbyte", block_size=int(config["block_size"]),
        differential=True, n=n) for ops in lists]
    users, hists = _requests(config, traffic, seed)
    log(f"lists and requests in {time.perf_counter() - t0:.1f} s")

    t0 = time.perf_counter()
    engine = ServingEngine(params, cfg, corpora, top_k=int(traffic["top_k"]),
                           buckets=(int(traffic["bucket"]),),
                           plan=config["plan"], dtype=dtype)
    log(f"engine (item table) in {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    engine.warmup()
    dev_users = [jax.device_put(u, dev) for u in users]
    dev_hists = [jax.device_put(h, dev) for h in hists]
    jax.block_until_ready(engine.retrieve(dev_users[0], dev_hists[0]))
    log(f"warmed up in {time.perf_counter() - t0:.1f} s")

    stored = np.array([int(work.stored_bytes("vbyte", ops)) for ops in lists],
                      np.int64)
    n_ints = np.full(len(lists), n, np.int64)
    picks = gen.rng_for(seed, 23).permutation(len(lists))[:CHECKED]
    return State(
        engine=engine, params=params, entry=engine.retrieve, lists=lists,
        n_ints=n_ints,
        stored=stored,
        call_bytes=work_retrieval.bytes_of_work(
            stored, n_ints, d=cfg.embed_dim, n_queries=int(traffic["bucket"]),
            table_itemsize=dtype.itemsize),
        users=dev_users, hists=dev_hists, users_host=users, hists_host=hists,
        order=gen.rng_for(seed, 24).permutation(len(lists)),
        sample=frozenset(int(j) for j in picks),
        in_flight=int(traffic["in_flight"]), bucket=int(traffic["bucket"]),
        held={})


def window(state: State, seconds: float) -> Window:
    import jax

    n = ints = nbytes = failed = 0
    passes, pool = len(state.order), len(state.users)
    pending = deque()  # (list, microbatch, output) of the issued calls

    def complete(j, k, out):
        nonlocal ints, nbytes, failed
        try:
            jax.block_until_ready(out)
        except Exception as e:  # a failed call is counted, the loop goes on
            failed += 1
            print(f"retrieve against list {j} failed: {e!r}", file=sys.stderr)
            return
        if j in state.sample:
            state.held[j] = (k, out)
        ints += int(state.n_ints[j])
        nbytes += int(state.call_bytes[j])

    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        j, k = int(state.order[n % passes]), n % pool
        n += 1
        try:
            pending.append((j, k, state.entry(state.users[k], state.hists[k],
                                              corpus=j)))
        except Exception as e:
            failed += 1
            print(f"retrieve against list {j} failed: {e!r}", file=sys.stderr)
        while len(pending) >= state.in_flight:
            complete(*pending.popleft())
        if n % passes == 0 and time.perf_counter() >= deadline:
            while pending:
                complete(*pending.popleft())
            break
    return Window(seconds=time.perf_counter() - t0, attempted=n,
                  failed=failed, work={"calls": n - failed, "ints": ints,
                                       "bytes_of_work": nbytes})


def end_to_end(state: State, win: Window) -> dict:
    return {"decode_gint_s": win.work["ints"] / win.seconds / 1e9,
            "index_bits_per_int": work.bits_per_int(
                int(state.stored.sum()), int(state.n_ints.sum()))}


def footprint(state: State) -> dict:
    """Device bytes beside ``memory_peak_bytes``: the item-vector table, the
    id embedding tables, the towers' other weights, the lists as the format
    counts them and as the device holds them, the request pool, and the
    answers the window kept for the check."""
    import jax

    eng = state.engine
    emb = sum(eng.params[k]["emb"].nbytes for k in ("user_emb", "item_id_emb"))
    weights = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    return {"item_table": int(eng.item_table.nbytes),
            "id_embedding_tables": int(emb),
            "tower_weights": int(weights - emb),
            "lists_stored": int(state.stored.sum()),
            "lists_resident": sum(int(a.nbytes) for c in eng.corpora
                                  for a in c.device_operands().values()),
            "requests": sum(int(u.nbytes + h.nbytes)
                            for u, h in zip(state.users, state.hists)),
            "kept_for_check": sum(int(a.nbytes) for _, out in
                                  state.held.values() for a in out)}


def table_rows(table, ids, d: int):
    """Rows ``ids`` (int32, on the device) of the engine's item table as
    float32 ``[n, d]``, unpacked from the table's layout (``uint32 [V,
    w]``: a bfloat16 table holds column ``j`` in the low and column ``j +
    d/2`` in the high half of word ``j``; a float32 table keeps its
    bits)."""
    import jax
    import jax.numpy as jnp

    words = jnp.take(table, ids, axis=0, mode="clip")
    if words.shape[1] == d:
        return jax.lax.bitcast_convert_type(words, jnp.float32)
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(words << 16, jnp.float32),
         jax.lax.bitcast_convert_type(words & jnp.uint32(0xFFFF0000),
                                      jnp.float32)], axis=1)


@functools.lru_cache(maxsize=None)
def _block_readings(d: int):
    """jit of one block's readings: the largest L2 distance between a
    stored row and the reference's item vector, and float32 scores of the
    stored rows against the engine's user vectors, at full precision."""
    import jax
    import jax.numpy as jnp

    from repro.models import recsys_ref

    @jax.jit
    def one(params, table, users, ids):
        rows = table_rows(table, ids, d)
        err = jnp.max(jnp.linalg.norm(
            rows - recsys_ref.item_vectors(params, ids), axis=1))
        return err, jnp.dot(users, rows.T,
                            precision=jax.lax.Precision.HIGHEST)
    return one


def _layer_readings(params, table, users, cand_ids, d, block=1 << 16):
    """:func:`_block_readings` over every candidate, ``block`` at a time
    on the device: ``(largest row distance, [b, C] scores)``."""
    import jax.numpy as jnp

    one = _block_readings(d)
    cand = np.asarray(cand_ids).astype(np.int32)
    pad = (-cand.size) % block
    ids = np.concatenate([cand, np.full(pad, cand[0], np.int32)])
    errs, scores = zip(*(one(params, table, users, jnp.asarray(row))
                         for row in ids.reshape(-1, block)))
    return (float(max(errs)),
            np.concatenate([np.asarray(s) for s in scores], axis=1)[
                :, : cand.size])


def check(state: State, win: Window) -> dict:
    """The kept answers, list by list, layer by layer:

    * ``ids_not_in_list``: returned ids that are not candidates of the list
      the request was served against (exact, limit 0);
    * ``requests_unchecked``: requests of the sampled lists with no answer
      from the window (limit 0);
    * ``user_vec_err_max``: the largest L2 distance between the engine's
      user vector and the reference's (limit :data:`USER_TOL`);
    * ``item_vec_err_max``: the largest L2 distance between a candidate's
      row of the engine's item table and the reference's item vector, over
      every candidate of the list (limit :data:`ITEM_TOL`);
    * ``score_err_max``: the largest difference between a returned score
      and the full-precision dot product of the operands the scoring
      kernel read: that request's user vector and that id's stored row
      (limit :data:`SCORE_TOL`);
    * ``missed_ids``: candidates left out whose full-precision score from
      those operands beats the request's last returned score by more than
      :data:`SCORE_TOL` (limit 0): the decode and the top-k, exactly.

    So each layer is held to its own limit: the towers to the reference
    (``models.recsys_ref``), the scoring kernel and the top-k to the
    operands they were given."""
    import jax.numpy as jnp

    from repro.models import recsys_ref

    engine, params = state.engine, state.params
    d = engine.cfg.embed_dim
    not_listed = missed = 0
    user_err = item_err = score_err = 0.0
    for j, (k, (top_s, top_i)) in sorted(state.held.items()):
        ops = state.lists[j]
        cands = recsys_ref.vbyte_decode_blocked(
            ops["payload"], ops["counts"], ops["bases"])
        got_s = np.asarray(top_s)
        got_i = np.asarray(top_i)
        listed = np.isin(got_i, cands)
        not_listed += int((~listed).sum())

        users = engine.user_vectors(state.users[k], state.hists[k])
        ref_users = recsys_ref.user_vectors(
            params, jnp.asarray(state.users_host[k]),
            jnp.asarray(state.hists_host[k]))
        user_err = max(user_err, float(jnp.max(jnp.linalg.norm(
            users.astype(jnp.float32) - ref_users, axis=1))))

        err, exact = _layer_readings(params, engine.item_table,
                                     users.astype(jnp.float32), cands, d)
        item_err = max(item_err, err)
        pos = np.minimum(np.searchsorted(cands, got_i), cands.size - 1)
        diff = np.abs(got_s - np.take_along_axis(exact, pos, axis=1))
        score_err = max(score_err,
                        float(np.max(np.where(listed, diff, 0.0))))
        for r in range(got_s.shape[0]):
            better = cands[exact[r] > got_s[r, -1] + SCORE_TOL]
            missed += int((~np.isin(better, got_i[r])).sum())
    unchecked = (len(state.sample) - len(state.held)) * state.bucket
    return {"ids_not_in_list": (not_listed, 0),
            "requests_unchecked": (unchecked, 0),
            "user_vec_err_max": (user_err, USER_TOL),
            "item_vec_err_max": (item_err, ITEM_TOL),
            "score_err_max": (score_err, SCORE_TOL),
            "missed_ids": (missed, 0)}
