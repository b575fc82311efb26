"""Plain reference for two-tower retrieval over a compressed candidate list.

It shares no code with the path it checks: VByte is decoded in numpy, both
towers are written out here and run in float32 under full matmul precision
from the same stored weights (upcast as they are read), every candidate is
scored, and the top-k is exact. The semantics are those of
``models.recsys.user_tower`` / ``item_tower`` and
``launch.serve.ServingEngine.retrieve``:

* user vector: the user's id embedding beside the mean of its history's
  item id embeddings (id 0 is padding, left out of the mean), through the
  user MLP (ReLU between layers, none after the last), L2-normalized;
* item vector: the item's id embedding through the item MLP, normalized;
* score: their dot product; the result is the ``k`` best candidates.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def vbyte_decode_blocked(payload, counts, bases, *, differential: bool = True
                         ) -> np.ndarray:
    """The uint32 ids of a blocked Masked VByte stream, in numpy.

    ``payload`` is ``uint8 [n_blocks, stride]``; block ``b`` holds
    ``counts[b]`` integers, each 7 bits a byte, least significant group
    first, its last byte's high bit clear, and is padded with zero bytes.
    With ``differential`` the integers are gaps, and block ``b``'s ids are
    ``bases[b]`` plus their running sum, mod 2^32.
    """
    payload = np.asarray(payload, np.uint8)
    counts = np.asarray(counts).reshape(-1).astype(np.int64)
    bases = np.asarray(bases).reshape(-1).astype(np.uint64)
    nb, stride = payload.shape
    flat = payload.reshape(-1)
    end = flat < 0x80  # an integer's last byte (padding zeros end one each)
    which = np.cumsum(end) - end  # integer each byte belongs to
    first = np.concatenate([[0], np.flatnonzero(end)[:-1] + 1])
    shift = 7 * (np.arange(flat.size) - first[which])
    parts = (flat & 0x7F).astype(np.uint64) << shift.astype(np.uint64)
    values = np.zeros(int(end.sum()), np.uint64)
    np.add.at(values, which, parts)
    # each block's integers are the first counts[b] of its row
    row_start = np.concatenate([[0], np.cumsum(end.reshape(nb, stride)
                                               .sum(axis=1))[:-1]])
    pick = np.repeat(row_start, counts) + (
        np.arange(counts.sum()) - np.repeat(np.cumsum(counts) - counts,
                                            counts))
    out = values[pick]
    if differential:
        block = np.repeat(np.arange(nb), counts)
        run = np.cumsum(out)
        before = np.concatenate([np.zeros(1, np.uint64), run])[
            np.repeat(np.cumsum(counts) - counts, counts)]
        out = bases[block] + run - before
    return (out & 0xFFFFFFFF).astype(np.uint32)


def _mlp(layers, x):
    n = len(layers)
    for i in range(n):
        p = layers[f"layer_{i}"]
        x = x @ p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)
        if i < n - 1:
            x = jax.nn.relu(x)
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-6)


@jax.jit
def user_vectors(params, user_ids, hists):
    """``[b, d]`` float32 user vectors of ``[b]`` ids and ``[b, L]``
    histories."""
    with jax.default_matmul_precision("highest"):
        u = jnp.take(params["user_emb"]["emb"], user_ids, axis=0)
        h = jnp.take(params["item_id_emb"]["emb"], hists, axis=0)
        keep = (hists != 0)[..., None]
        bag = (jnp.where(keep, h.astype(jnp.float32), 0).sum(axis=1)
               / jnp.maximum(keep.sum(axis=1), 1))
        x = jnp.concatenate([u.astype(jnp.float32), bag], axis=-1)
        return _mlp(params["user_mlp"], x)


@jax.jit
def item_vectors(params, item_ids):
    """``[n, d]`` float32 item vectors of ``[n]`` ids."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["item_id_emb"]["emb"], item_ids, axis=0)
        return _mlp(params["item_mlp"], x.astype(jnp.float32))


@jax.jit
def _block_scores(params, users, item_ids):
    with jax.default_matmul_precision("highest"):
        return users @ item_vectors(params, item_ids).T


def scores(params, user_ids, hists, cand_ids, *, block: int = 1 << 16
           ) -> np.ndarray:
    """``[b, C]`` float32 scores of every candidate for every user, on the
    default device, ``block`` candidates at a time."""
    users = user_vectors(params, jnp.asarray(user_ids, jnp.int32),
                         jnp.asarray(hists, jnp.int32))
    cand = np.asarray(cand_ids).astype(np.int32)
    pad = (-cand.size) % block
    ids = np.concatenate([cand, np.zeros(pad, np.int32)]).reshape(-1, block)
    out = [np.asarray(_block_scores(params, users, jnp.asarray(row)))
           for row in ids]
    return np.concatenate(out, axis=1)[:, : cand.size]


def top_k(score_rows: np.ndarray, cand_ids, k: int):
    """Exact top-``k`` of each row: ``(scores [b, k], ids [b, k])``, best
    first, ties to the smaller id."""
    cand = np.asarray(cand_ids)
    order = np.lexsort((np.broadcast_to(cand, score_rows.shape),
                        -score_rows), axis=-1)[:, :k]
    return (np.take_along_axis(score_rows, order, axis=1),
            cand[order])


def retrieve(params, user_ids, hists, cand_ids, k: int):
    """The reference answer of one microbatch against one candidate list."""
    return top_k(scores(params, user_ids, hists, cand_ids), cand_ids, k)
