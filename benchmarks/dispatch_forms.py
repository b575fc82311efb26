"""Host cost of one decode call, by the form the call takes.

One d-gap VByte list of length group K (default 12), built as the
``decode.short_lists`` benchmark cell builds its lists (``chipbench.gen``,
padded with count-0 blocks to a power of two), is decoded through each of:

  a  ``dispatch.decode(arr, plan="auto")``: the whole dispatch layer
  b  the jitted ``vbyte_decode_blocked(payload, counts, bases, **static)``
  c  the same program as an AOT executable (``jit(...).lower().compile()``)
     called with the three leaves
  d  a jit of a closure over the static arguments, called with the leaves
  e  a trivial jit (``x + 1`` on the output's shape): the floor of a launch

Each form gets ``--warm`` calls, then ``--issues`` timed calls driven as the
benchmark's window drives them: the next call is issued before the oldest of
``--in-flight`` outstanding outputs is waited for, and only the issue (the
call itself, up to its return of a not-yet-ready output) is timed. The forms
run in turn for ``--rounds`` rounds; the line per form gives the median over
rounds of the mean issue time, in us.

    PYTHONPATH=src:. python -m benchmarks.dispatch_forms --out forms.json

Times from a CPU run show the shape of the host path only, not its size on
a TPU host.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time
from collections import deque

import numpy as np

import jax

from chipbench import gen
from repro.core import CompressedIntArray
from repro.kernels.vbyte_decode import dispatch
from repro.kernels.vbyte_decode.ops import vbyte_decode_blocked


def make_list(k: int, *, seed: int = 7, universe: int = 50_000_000,
              template_seed: int = 1503073870, block: int = 128):
    """The middle list of length group ``k``, made as
    ``chipbench/drivers/decode.py`` makes its lists."""
    lens = gen.group_lengths(k, 256)
    i = len(lens) // 2
    template = 1 + gen.sorted_gap_list(gen.rng_for(template_seed, k, i),
                                       lens[i], universe - 1)
    d = gen.shuffled_list(template, gen.rng_for(seed, 2, k), block)
    payload, counts, bases = gen.vbyte_blocked(d, block)
    pad = (1 << (k + 1)) // block - counts.size
    ops = {"payload": np.pad(payload, ((0, pad), (0, 0))),
           "counts": np.pad(counts, (0, pad)),
           "bases": np.pad(bases, (0, pad))}
    dev = jax.devices()[0]
    arr = CompressedIntArray.from_operands(
        {n: jax.device_put(v, dev) for n, v in ops.items()},
        format="vbyte", block_size=block, differential=True, n=d.size)
    return arr, d


def issue_us(fn, *, warm: int, issues: int, in_flight: int) -> float:
    """Mean host time of one issue of ``fn()``, in us."""
    for _ in range(warm):
        jax.block_until_ready(fn())
    pending = deque()
    total = 0.0
    for _ in range(issues):
        t0 = time.perf_counter()
        out = fn()
        total += time.perf_counter() - t0
        pending.append(out)
        while len(pending) >= in_flight:
            jax.block_until_ready(pending.popleft())
    jax.block_until_ready(list(pending))
    return total / issues * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--k", type=int, default=12)
    ap.add_argument("--warm", type=int, default=20)
    ap.add_argument("--issues", type=int, default=2000)
    ap.add_argument("--in-flight", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    arr, docids = make_list(args.k)
    leaves = (arr.payload, arr.counts, arr.bases)
    p = dispatch.resolve_plan("auto", format="vbyte", epilogue="stream",
                              block_size=arr.block_size)
    static = dict(block_size=arr.block_size, differential=True,
                  block_tile=p.block_tile, chunk_width=p.chunk,
                  interpret=None)

    def body(payload, counts, bases):
        return vbyte_decode_blocked(payload, counts, bases, **static)

    closure = jax.jit(body)
    aot = jax.jit(body).lower(*leaves).compile()
    floor_in = jax.numpy.zeros((arr.n_blocks, arr.block_size), np.uint32)
    trivial = jax.jit(lambda x: x + 1)
    forms = {
        "a_dispatch_decode": lambda: dispatch.decode(arr, plan="auto"),
        "b_jit_static_kwargs": lambda: vbyte_decode_blocked(*leaves,
                                                            **static),
        "c_aot_executable": lambda: aot(*leaves),
        "d_jit_closure": lambda: closure(*leaves),
        "e_trivial_jit": lambda: trivial(floor_in),
    }
    for name, fn in forms.items():  # every form decodes the list exactly
        if name.startswith("e_"):
            continue
        got = np.asarray(fn()).reshape(-1)[:docids.size]
        if not np.array_equal(got, docids):
            raise SystemExit(f"{name} decoded the list wrongly")
    rounds = {name: [] for name in forms}
    for _ in range(args.rounds):
        for name, fn in forms.items():
            rounds[name].append(issue_us(fn, warm=args.warm,
                                         issues=args.issues,
                                         in_flight=args.in_flight))
    result = {
        "device": jax.devices()[0].device_kind,
        "plan": p.label, "k": args.k, "n_ints": int(docids.size),
        "blocks": arr.n_blocks, "stride": int(arr.payload.shape[1]),
        "issues": args.issues, "in_flight": args.in_flight,
        "issue_us_median": {n: statistics.median(v)
                            for n, v in rounds.items()},
        "issue_us_rounds": rounds,
    }
    line = json.dumps(result)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as fh:
            fh.write(line + "\n")
    print(line)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
