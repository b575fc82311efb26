"""Controls and planted faults for the retrieval cell's ``correct``.

Each is a ``patch(state)`` for :func:`chipbench.harness.run`: it changes
what the timed path serves, and the rest of the run, the comparison
included, is the benchmark's own.

* ``control``: the item table held in float8 (e4m3) in the scored path,
  one precision step below the bfloat16 that the configuration states:
  every value is rounded to float8 and stored back, and the same kernel
  scores against it. ``item_vec_err_max`` catches it.
* ``float8_user``: the user tower's MLP weights rounded to float8 (e4m3),
  one step below their bfloat16. ``user_vec_err_max`` catches it.
* ``bf16_scores``: the scores rounded to bfloat16, as a kernel that
  accumulated or stored its scores in bfloat16 would give them, one step
  below the float32 that the configuration states. ``score_err_max``
  catches it.
* ``altered``: the program's answer with every returned id moved to the
  next id.

Run on the chip at the cell's own size, with the program's own runs beside
them in one process::

    python3 chipbench/controls_retrieval.py \
        --workload retrieval.two_tower_2p20 --seeds 11,12 --seconds 10 \
        --patch none,control,float8_user,bf16_scores

Each line printed is one run: workload, patch, seed and its comparisons.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHUNK_ROWS = 1 << 18  # table rows rounded per step, in place


def float8_table(state):
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.vbyte_decode.gather_kernel import pack_rows, unpack_rows

    engine = state.engine
    table, d = engine.item_table, engine.cfg.embed_dim
    V = table.shape[0]
    chunk = min(CHUNK_ROWS, V)

    @functools.partial(jax.jit, donate_argnums=0)
    def rounded(t, start):
        v = unpack_rows(jax.lax.dynamic_slice_in_dim(t, start, chunk), d)
        v = v.astype(jnp.float8_e4m3fn).astype(v.dtype)
        return jax.lax.dynamic_update_slice(t, pack_rows(v), (start, 0))

    for start in range(0, V, chunk):
        table = rounded(table, min(start, V - chunk))
    engine.item_table = jax.block_until_ready(table)


def float8_user(state):
    import jax
    import jax.numpy as jnp

    engine = state.engine
    params = dict(engine.params)
    params["user_mlp"] = jax.tree.map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype),
        params["user_mlp"])
    engine.params = params


def bf16_scores(state):
    import jax.numpy as jnp

    inner = state.entry

    def entry(users, hists, *, corpus=0):
        top_s, top_i = inner(users, hists, corpus=corpus)
        return top_s.astype(jnp.bfloat16).astype(top_s.dtype), top_i
    state.entry = entry


def altered(state):
    inner = state.entry

    def entry(users, hists, *, corpus=0):
        top_s, top_i = inner(users, hists, corpus=corpus)
        return top_s, top_i + 1
    state.entry = entry


PATCHES = {"none": None, "control": float8_table, "float8_user": float8_user,
           "bf16_scores": bf16_scores, "altered": altered}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--patch", default="none,control",
                    help="comma-separated, from PATCHES")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness

    cell = harness.resolve(ROOT, args.workload)
    devices = harness.accelerator(cell.chips)
    for name in args.patch.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            r = harness.run(ROOT, args.workload, seed, args.seconds, False,
                            t_start=t0, devices=devices,
                            patch=PATCHES[name], log=lambda m: None)
            print(json.dumps({"workload": args.workload, "patch": name,
                              "seed": seed, "correct": r["correct"],
                              "attempted": r["attempted"],
                              "checks": r["checks"],
                              "metrics": r["metrics"],
                              "device": r["device"],
                              "footprint_bytes": r.get("footprint_bytes")}),
                  flush=True)
            del r
            gc.collect()  # the run's tables leave the device before the next
    return 0


if __name__ == "__main__":
    sys.exit(main())
