"""Bytes of work of one fused ``dot_score`` call: decode a compressed
candidate list, fetch each candidate's item vector from the table in HBM,
score it against the microbatch's queries.

The least bytes such a call has to move, whatever the kernel:

* the list's ``work.stored_bytes``: its tight encoded payload and 8 bytes
  of ``counts``/``bases`` for each block that holds ids;
* for each real candidate, its table row (``d`` values of
  ``table_itemsize`` bytes), its id written out (4 bytes) and its score
  for each query (4 bytes each).

The kernel also reads the padded payload grid, fetches row 0 for padding
slots and writes padded ids and scores, so its bytes are at least these.
"""
from __future__ import annotations

# the fused dot_score kernel's op in the device trace: its stable pallas
# name (``<format>_fused_dot_score_<core>``) on a Mosaic custom call
DOT_SCORE_KERNEL = r'fused_dot_score\S* = .*custom_call_target="tpu_custom_call"'
ID_BYTES = 4  # int32 candidate id written out
SCORE_BYTES = 4  # float32 score per query


def bytes_of_work(stored, n_ids, *, d: int, n_queries: int,
                  table_itemsize: int = 2):
    """Least bytes of one call against a list of ``n_ids`` candidates whose
    ``work.stored_bytes`` are ``stored`` (scalars or arrays)."""
    per_id = d * table_itemsize + ID_BYTES + SCORE_BYTES * n_queries
    return stored + per_id * n_ids
