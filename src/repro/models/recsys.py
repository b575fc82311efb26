"""RecSys family: SASRec, BERT4Rec, BST, two-tower retrieval.

Huge row-sharded embedding tables + sequence encoders + small MLPs
(taxonomy §B.6). Id streams (user histories, retrieval candidate lists) are
VByte posting lists decoded on device; the retrieval_cand serve step decodes
a 1M-candidate compressed list *inside* the jitted graph.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.api import constrain
from repro.nn import attention as attn
from repro.nn import layers as nn
from repro.nn.layers import accum_dtype
from repro.nn.embedding_bag import bag_from_padded

DP = ("pod", "data")
TP = "model"


@dataclass(frozen=True)
class RecSysConfig:
    name: str
    kind: str  # "sasrec" | "bert4rec" | "bst" | "two_tower"
    n_items: int
    embed_dim: int
    seq_len: int
    n_blocks: int = 2
    n_heads: int = 1
    mlp_dims: tuple[int, ...] = ()
    n_users: int = 0  # two-tower
    id_dim: int = 128  # two-tower id embedding width
    n_mask: int = 0  # bert4rec masked positions per sequence
    n_negatives: int = 1024  # sampled-softmax shared negatives
    serve_candidates: int = 4096
    # serving-time embedding-table layout: "row" (baseline: row-sharded, every
    # gather pays an all-reduce) | "replicated" (bf16 tables fit at inference;
    # gathers + top-k go shard-local — §Perf retrieval hillclimb) | "column"
    serve_table_mode: str = "row"
    extras: dict[str, Any] = field(default_factory=dict)

    @property
    def vocab_rows(self) -> int:
        # +2: padding id 0 is reserved, bert4rec adds a [MASK] row at the end;
        # rounded to a multiple of 512 so row-sharding divides every mesh
        return -(-(self.n_items + 2) // 512) * 512

    @property
    def user_rows(self) -> int:
        return -(-(self.n_users + 2) // 512) * 512

    def param_count(self) -> int:
        d = self.embed_dim
        if self.kind == "two_tower":
            n = self.user_rows * self.id_dim + self.vocab_rows * self.id_dim
            dims_u = (self.id_dim * 2,) + self.mlp_dims
            dims_i = (self.id_dim,) + self.mlp_dims
            n += sum(a * b + b for a, b in zip(dims_u[:-1], dims_u[1:]))
            n += sum(a * b + b for a, b in zip(dims_i[:-1], dims_i[1:]))
            return n
        n = self.vocab_rows * d + (self.seq_len + 1) * d
        per_block = 4 * d * d + 2 * (d * d + d) + 4 * d  # attn + pw-ffn + norms
        n += self.n_blocks * per_block
        if self.kind == "bst":
            dims = ((self.seq_len + 1) * d,) + self.mlp_dims + (1,)
            n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        return n

    def dense_flops_per_example(self) -> int:
        """Approx fwd FLOPs per scored example (roofline MODEL_FLOPS basis)."""
        d = self.embed_dim
        if self.kind == "two_tower":
            dims_u = (self.id_dim * 2,) + self.mlp_dims
            dims_i = (self.id_dim,) + self.mlp_dims
            mm = sum(a * b for a, b in zip(dims_u[:-1], dims_u[1:]))
            mm += sum(a * b for a, b in zip(dims_i[:-1], dims_i[1:]))
            return 2 * mm
        L = self.seq_len + (1 if self.kind == "bst" else 0)
        per_block = 2 * L * (6 * d * d) + 2 * 2 * L * L * d  # proj+ffn, qk+pv
        n = self.n_blocks * per_block
        if self.kind == "bst":
            dims = (L * d,) + self.mlp_dims + (1,)
            n += 2 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
        return n


# ----------------------------------------------------------------------------
# shared sequence encoder (pre-LN transformer blocks over item embeddings)
# ----------------------------------------------------------------------------
def _block_init(key, d: int):
    kq, kk, kv, ko, k1, k2 = jax.random.split(key, 6)
    return {
        "ln1": nn.layernorm_init(d),
        "attn": {
            "wq": nn.dense_init(kq, d, d),
            "wk": nn.dense_init(kk, d, d),
            "wv": nn.dense_init(kv, d, d),
            "wo": nn.dense_init(ko, d, d),
        },
        "ln2": nn.layernorm_init(d),
        "ffn": {
            "w1": {**nn.dense_init(k1, d, d), "b": jnp.zeros((d,), jnp.float32)},
            "w2": {**nn.dense_init(k2, d, d), "b": jnp.zeros((d,), jnp.float32)},
        },
    }


def _encode_seq(blocks, x, cfg: RecSysConfig, *, causal: bool, dtype):
    B, L, d = x.shape
    H = cfg.n_heads
    dh = d // H
    qc = kc = max(16, 1 << (L - 1).bit_length())  # whole seq in one chunk
    for i in range(cfg.n_blocks):
        blk = blocks[f"block_{i}"]
        h = nn.layernorm(blk["ln1"], x, dtype=dtype)
        q = nn.dense(blk["attn"]["wq"], h, dtype=dtype).reshape(B, L, H, dh)
        k = nn.dense(blk["attn"]["wk"], h, dtype=dtype).reshape(B, L, H, dh)
        v = nn.dense(blk["attn"]["wv"], h, dtype=dtype).reshape(B, L, H, dh)
        o = attn.flash_attention(q, k, v, causal=causal, q_chunk=min(qc, L),
                                 kv_chunk=min(kc, L), dtype=dtype)
        x = x + nn.dense(blk["attn"]["wo"], o.reshape(B, L, d), dtype=dtype)
        h = nn.layernorm(blk["ln2"], x, dtype=dtype)
        f = blk["ffn"]
        h = jax.nn.relu(h @ f["w1"]["w"].astype(dtype) + f["w1"]["b"].astype(dtype))
        h = h @ f["w2"]["w"].astype(dtype) + f["w2"]["b"].astype(dtype)
        x = x + h
        x = constrain(x, DP, None, None)
    return x


def init_params(key, cfg: RecSysConfig):
    ki, kp, kb, ku, km, kt = jax.random.split(key, 6)
    d = cfg.embed_dim
    if cfg.kind == "two_tower":  # no sequence encoder: bag + towers only
        return {
            "user_emb": nn.embedding_init(ku, cfg.user_rows, cfg.id_dim),
            "item_id_emb": nn.embedding_init(km, cfg.vocab_rows, cfg.id_dim),
            "user_mlp": nn.mlp_init(ku, (cfg.id_dim * 2,) + cfg.mlp_dims),
            "item_mlp": nn.mlp_init(km, (cfg.id_dim,) + cfg.mlp_dims),
        }
    params = {
        "item_emb": nn.embedding_init(ki, cfg.vocab_rows, d),
        "pos_emb": nn.embedding_init(kp, cfg.seq_len + 1, d),
        "blocks": {
            f"block_{i}": _block_init(k, d)
            for i, k in enumerate(jax.random.split(kb, cfg.n_blocks))
        },
        "final_ln": nn.layernorm_init(d),
    }
    if cfg.kind == "bst":
        params["mlp"] = nn.mlp_init(kt, ((cfg.seq_len + 1) * d,) + cfg.mlp_dims + (1,))
    return params


def _seq_repr(params, hist, cfg: RecSysConfig, *, causal: bool, dtype):
    """hist [B, L] -> hidden [B, L, d] with positional embeddings."""
    B, L = hist.shape
    x = nn.embedding_lookup(params["item_emb"], hist, dtype=dtype)
    x = x + nn.embedding_lookup(params["pos_emb"],
                                jnp.arange(L, dtype=jnp.int32)[None], dtype=dtype)
    x = constrain(x, DP, None, None)
    x = _encode_seq(params["blocks"], x, cfg, causal=causal, dtype=dtype)
    return nn.layernorm(params["final_ln"], x, dtype=dtype)


def _item_scores(params, h, item_ids, dtype):
    """h [..., d] · emb[item_ids] [..., C, d] -> [..., C] (dot-product head)."""
    vecs = nn.embedding_lookup(params["item_emb"], item_ids, dtype=dtype)
    return jnp.einsum("...d,...cd->...c", h, vecs, preferred_element_type=accum_dtype())


# ----------------------------------------------------------------------------
# losses (train_step targets)
# ----------------------------------------------------------------------------
def loss_fn(params, batch, cfg: RecSysConfig, *, dtype=nn.DEFAULT_COMPUTE_DTYPE):
    if cfg.kind == "sasrec":
        return _sasrec_loss(params, batch, cfg, dtype)
    if cfg.kind == "bert4rec":
        return _bert4rec_loss(params, batch, cfg, dtype)
    if cfg.kind == "bst":
        return _bst_loss(params, batch, cfg, dtype)
    if cfg.kind == "two_tower":
        return _two_tower_loss(params, batch, cfg, dtype)
    raise ValueError(cfg.kind)


def _sasrec_loss(params, batch, cfg, dtype):
    """Next-item binary CE with one sampled negative per step (SASRec §3.5)."""
    hist = batch["hist"]  # [B, L+1]
    neg = batch["neg"]  # [B, L]
    inputs, pos = hist[:, :-1], hist[:, 1:]
    h = _seq_repr(params, inputs, cfg, causal=True, dtype=dtype)
    pos_s = _item_scores(params, h, pos[..., None], dtype)[..., 0]
    neg_s = _item_scores(params, h, neg[..., None], dtype)[..., 0]
    valid = pos != 0
    lp = jax.nn.log_sigmoid(pos_s)
    ln = jax.nn.log_sigmoid(-neg_s)
    loss = -jnp.where(valid, lp + ln, 0.0).sum() / jnp.maximum(valid.sum(), 1)
    auc_proxy = jnp.where(valid, (pos_s > neg_s), False).sum() / jnp.maximum(valid.sum(), 1)
    return loss, {"pairwise_acc": auc_proxy}


def _bert4rec_loss(params, batch, cfg, dtype):
    """Masked-item sampled softmax with shared negatives (+ target in slot 0)."""
    hist = batch["hist"]  # [B, L] with [MASK]=n_items+1 at masked slots
    mask_pos = batch["mask_pos"]  # [B, M]
    targets = batch["targets"]  # [B, M]
    negatives = batch["negatives"]  # [Nneg]
    h = _seq_repr(params, hist, cfg, causal=False, dtype=dtype)
    hm = jnp.take_along_axis(h, mask_pos[..., None], axis=1)  # [B, M, d]
    pos_s = _item_scores(params, hm, targets[..., None], dtype)[..., 0]  # [B, M]
    neg_v = nn.embedding_lookup(params["item_emb"], negatives, dtype=dtype)  # [N, d]
    neg_s = jnp.einsum("bmd,nd->bmn", hm, neg_v, preferred_element_type=accum_dtype())
    logits = jnp.concatenate([pos_s[..., None], neg_s], axis=-1)  # [B, M, 1+N]
    valid = targets != 0
    nll = jax.nn.logsumexp(logits, -1) - logits[..., 0]
    loss = jnp.where(valid, nll, 0.0).sum() / jnp.maximum(valid.sum(), 1)
    hit = logits[..., 0] >= logits.max(-1)
    return loss, {"hit_at_1": jnp.where(valid, hit, False).sum() / jnp.maximum(valid.sum(), 1)}


def _bst_loss(params, batch, cfg, dtype):
    """CTR binary cross-entropy (BST: transformer over history + target item)."""
    logit = bst_forward(params, batch["hist"], batch["target"], cfg, dtype=dtype)
    label = batch["label"].astype(jnp.float32)
    loss = -jnp.mean(label * jax.nn.log_sigmoid(logit)
                     + (1 - label) * jax.nn.log_sigmoid(-logit))
    acc = jnp.mean((logit > 0) == (label > 0.5))
    return loss, {"accuracy": acc}


def bst_forward(params, hist, target, cfg: RecSysConfig, *, dtype=nn.DEFAULT_COMPUTE_DTYPE):
    seq = jnp.concatenate([hist, target[:, None]], axis=1)  # [B, L+1]
    h = _seq_repr(params, seq, cfg, causal=False, dtype=dtype)
    B = h.shape[0]
    flat = h.reshape(B, -1)
    return nn.mlp(params["mlp"], flat, act=jax.nn.leaky_relu, dtype=dtype)[:, 0].astype(jnp.float32)


def user_tower(params, user_id, hist, cfg: RecSysConfig, *, dtype=nn.DEFAULT_COMPUTE_DTYPE):
    u = nn.embedding_lookup(params["user_emb"], user_id, dtype=dtype)  # [B, id_dim]
    bag = bag_from_padded(params["item_id_emb"]["emb"], hist, mode="mean", dtype=dtype)
    x = jnp.concatenate([u, bag], axis=-1)
    v = nn.mlp(params["user_mlp"], x, final_act=False, dtype=dtype)
    return v / jnp.maximum(jnp.linalg.norm(v.astype(jnp.float32), axis=-1, keepdims=True), 1e-6).astype(dtype)


def user_tower_compressed(params, user_id, hists,
                          cfg: RecSysConfig, *,
                          plan="auto", dtype=nn.DEFAULT_COMPUTE_DTYPE):
    """User tower over compressed histories: fused one-pass embedding bag.

    ``hists`` is the ragged encoding of the batch's history bags —
    ``CompressedIntArray.encode_ragged(histories, block_size=seq_len)`` —
    one block per user (the array is a pytree; pass it straight through
    jit). The mean-bag is the decode kernel's ``bag_sum`` epilogue: history
    ids never round-trip through HBM between decode and gather (they do in
    ``user_tower``'s padded path). Matches ``user_tower`` exactly when the
    padded histories hold the same ids (pad id 0 excluded) and the bags
    were encoded with ``block_size == seq_len``.
    """
    from repro.nn.embedding_bag import embedding_bag_compressed

    u = nn.embedding_lookup(params["user_emb"], user_id, dtype=dtype)  # [B, id_dim]
    bag = embedding_bag_compressed(
        params["item_id_emb"]["emb"], hists, mode="mean", plan=plan,
        dtype=dtype)[: u.shape[0]]
    x = jnp.concatenate([u, bag.astype(dtype)], axis=-1)
    v = nn.mlp(params["user_mlp"], x, final_act=False, dtype=dtype)
    return v / jnp.maximum(jnp.linalg.norm(v.astype(jnp.float32), axis=-1, keepdims=True), 1e-6).astype(dtype)


def item_tower(params, item_ids, cfg: RecSysConfig, *, dtype=nn.DEFAULT_COMPUTE_DTYPE):
    x = nn.embedding_lookup(params["item_id_emb"], item_ids, dtype=dtype)
    v = nn.mlp(params["item_mlp"], x, final_act=False, dtype=dtype)
    return v / jnp.maximum(jnp.linalg.norm(v.astype(jnp.float32), axis=-1, keepdims=True), 1e-6).astype(dtype)


def _two_tower_loss(params, batch, cfg, dtype):
    """In-batch sampled softmax (Yi et al., RecSys'19), temperature-scaled."""
    u = user_tower(params, batch["user_id"], batch["hist"], cfg, dtype=dtype)
    i = item_tower(params, batch["item_id"], cfg, dtype=dtype)
    u = constrain(u, DP, None)
    i = constrain(i, DP, None)
    temp = 0.05
    logits = (u @ i.T).astype(jnp.float32) / temp  # [B, B]
    labels = jnp.arange(u.shape[0])
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    acc = jnp.mean(jnp.argmax(logits, -1) == labels)
    return loss, {"in_batch_top1": acc}


# ----------------------------------------------------------------------------
# serve steps
# ----------------------------------------------------------------------------
def serve_scores(params, batch, cfg: RecSysConfig, *, dtype=nn.DEFAULT_COMPUTE_DTYPE):
    """Online/bulk scoring against a candidate set (serve_p99 / serve_bulk)."""
    if cfg.kind == "bst":
        return bst_forward(params, batch["hist"], batch["target"], cfg, dtype=dtype)
    if cfg.kind == "two_tower":
        u = user_tower(params, batch["user_id"], batch["hist"], cfg, dtype=dtype)
        i = item_tower(params, batch["cands"], cfg, dtype=dtype)  # [C]
        return (u @ i.T).astype(jnp.float32)
    causal = cfg.kind == "sasrec"
    h = _seq_repr(params, batch["hist"], cfg, causal=causal, dtype=dtype)
    return _item_scores(params, h[:, -1], batch["cands"], dtype)  # [B, C]


def _cand_array(batch):
    """The compressed candidate list from a serve batch.

    The pytree-native form is ``batch["cands"]``: the ``CompressedIntArray``
    itself (either format — its static aux data carries format/block_size/
    differential). The legacy unpacked ``cand_payload``/``cand_control``…
    keys are still accepted with a ``DeprecationWarning``.
    """
    from repro.core.compressed_array import CompressedIntArray

    if "cands" in batch:
        return batch["cands"]
    import warnings

    warnings.warn(
        "cand_payload/cand_control/... batch keys are deprecated; pass the "
        "CompressedIntArray itself as batch['cands']", DeprecationWarning,
        stacklevel=3)
    import numpy as np

    def legacy_n(counts):
        try:  # the real count when concrete; capacity when traced (n is
            return int(np.asarray(counts).sum())  # unused by the serve path)
        except TypeError:
            return counts.shape[0] * 128
    if "cand_control" in batch:
        return CompressedIntArray.from_operands(
            {"control": batch["cand_control"], "data": batch["cand_data"],
             "counts": batch["cand_counts"], "bases": batch["cand_bases"]},
            format="streamvbyte", block_size=128, differential=True,
            n=legacy_n(batch["cand_counts"]))
    return CompressedIntArray.from_operands(
        {"payload": batch["cand_payload"], "counts": batch["cand_counts"],
         "bases": batch["cand_bases"]},
        format="vbyte", block_size=128, differential=True,
        n=legacy_n(batch["cand_counts"]))


def retrieval_scores_compressed(params, batch, cfg: RecSysConfig, *, top_k: int = 100,
                                plan="auto", use_kernel: bool | None = None,
                                dtype=nn.DEFAULT_COMPUTE_DTYPE):
    """retrieval_cand: score 1 query against a compressed candidate list.

    The sorted candidate id list (delta-coded, VByte or Stream VByte —
    ``batch["cands"]``, a ``CompressedIntArray``) is decoded *inside* the
    serving graph. For the dot-product heads (sasrec/bert4rec) the scoring
    itself is the decode kernel's ``dot_score`` epilogue: ids gather item
    vectors and dot against the query in VMEM, so the [C, d]
    candidate-vector matrix never materializes in HBM — only ids and scores
    come out. Tower/ranker heads (two_tower, bst) decode-then-score. (The
    resident-corpus serving loop lives one level up, in
    ``repro.launch.serve.ServingEngine``, which serves the two-tower path
    through the same fused ``dot_score`` epilogue against a precomputed
    item-vector table.)

    ``plan`` is the dispatch plan; ``use_kernel`` the deprecated legacy
    boolean alias. For VByte candidates off-TPU, ``"auto"`` resolves to the
    gather-lowered ``"ref"`` decoder for every kind: the scatter-based
    masked path emits a cross-shard scatter-add (an all-reduce of the
    [n_cand] id array) under GSPMD, while the searchsorted/gather lowering
    stays block-local (§Perf retrieval iteration 2).
    """
    from repro.kernels.vbyte_decode import dispatch

    cands_arr = _cand_array(batch)
    fmt = cands_arr.format
    if use_kernel is not None:
        from repro.core.compressed_array import warn_use_kernel

        plan = warn_use_kernel(use_kernel)
        if plan == "jnp" and fmt == "vbyte":
            plan = "ref"
    if (plan == "auto" and fmt == "vbyte"
            and dispatch.default_plan(fmt).path != "pallas"):
        # off-TPU, ALL kinds keep the block-local ref decode (dot-score
        # kinds run it unfused: ref grid + dot_score as a second dispatch)
        plan = "ref"

    if cfg.kind in ("sasrec", "bert4rec"):
        # one-pass fused path: decode → gather item vectors → dot, in-kernel
        h = _seq_repr(params, batch["hist"], cfg, causal=cfg.kind == "sasrec",
                      dtype=dtype)[:, -1]  # [1, d]
        table = params["item_emb"]["emb"].astype(dtype)
        ids, scores = dispatch.decode(
            cands_arr, epilogue="dot_score",
            epilogue_operands={"table": table, "query": h}, plan=plan)
        cands = constrain(ids.reshape(-1), ("pod", "data", "model"))
        scores = constrain(scores.reshape(-1), ("pod", "data", "model"))
    else:
        cands = dispatch.decode(cands_arr, plan=plan)
        cands = cands.reshape(-1).astype(jnp.int32)  # padded with 0 = pad row
        cands = constrain(cands, ("pod", "data", "model"))
        C = cands.shape[0]
        if cfg.kind == "two_tower":
            u = user_tower(params, batch["user_id"], batch["hist"], cfg, dtype=dtype)
            i = item_tower(params, cands, cfg, dtype=dtype)  # [C, v]
            scores = (i @ u[0]).astype(jnp.float32)
        else:  # bst: every candidate runs through the ranker with the history
            hist = jnp.broadcast_to(batch["hist"], (C, cfg.seq_len))
            scores = bst_forward(params, hist, cands, cfg, dtype=dtype)
    top_s, top_i = jax.lax.top_k(scores, top_k)
    return scores, (top_s, jnp.take(cands, top_i))
