"""Controls and planted faults for the comparison that decides ``correct``.

Each is a ``patch(state)`` for :func:`chipbench.harness.run`: it puts
something else in the place of the timed path (the state's ``entry``), so
the rest of the run, the comparison included, is the benchmark's own.

* ``control``: the reference, computed one step below the precision the
  configuration states: each block's base plus the prefix sum of its gaps
  in float32, where the docids are 32-bit integers.
* ``altered``: the program's answer with one int changed where it is
  produced.
* ``half``: the second half of every list's blocks left out.

Run on the chip at a cell's own size, with the program's own runs beside
them in one process::

    python3 chipbench/controls.py --workload decode.long_lists \
        --seeds 11,12,13 --seconds 10 --patch none,control

Each line printed is one run: workload, patch, seed and its comparisons.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reference_decode(docids, bases, *, block: int, dtype):
    """The decode reference: each block's ``bases`` plus the prefix sum of
    its gaps, computed in ``dtype`` (uint32 gives the docids back)."""
    import jax.numpy as jnp

    d = docids.reshape(-1, block)
    prev = jnp.concatenate([bases[:, None], d[:, :-1]], axis=1)
    gaps = (d - prev).astype(dtype)
    return (bases.astype(dtype)[:, None]
            + jnp.cumsum(gaps, axis=1)).astype(jnp.uint32)


def _reference_entry(state, dtype):
    """An entry that answers each list from the generated docids through
    :func:`reference_decode`, compiled for every shape before the window."""
    import functools

    import jax
    import numpy as np

    run = jax.jit(functools.partial(reference_decode, dtype=dtype),
                  static_argnames=("block",))
    docids = {}
    for arr, d in zip(state.arrs, state.docids):
        full = np.full(arr.counts.size * arr.block_size, d[-1], np.uint32)
        full[:d.size] = d
        docids[id(arr)] = jax.device_put(full, arr.payload.sharding)

    def entry(arr, plan=None):
        return run(docids[id(arr)], arr.bases, block=arr.block_size)

    for arr in state.arrs:
        jax.block_until_ready(entry(arr))
    state.entry = entry


def decode_control(state):
    import jax.numpy as jnp

    _reference_entry(state, jnp.float32)


def decode_plain(state):
    """The reference at full integer precision: not a control, the check
    that the control fails for its precision alone."""
    import jax.numpy as jnp

    _reference_entry(state, jnp.uint32)


def decode_altered(state):
    inner = state.entry

    def entry(arr, plan=None):
        return inner(arr, plan=plan).at[0, 0].add(1)
    state.entry = entry


def decode_half(state):
    inner = state.entry

    def entry(arr, plan=None):
        out = inner(arr, plan=plan)
        return out.at[out.shape[0] // 2:].set(0)
    state.entry = entry


PATCHES = {"none": None, "control": decode_control, "plain": decode_plain,
           "altered": decode_altered, "half": decode_half}


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
                              "metrics": r["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
