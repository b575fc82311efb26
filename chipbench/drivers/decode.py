"""Driver ``decode``: whole posting lists through ``dispatch.decode``.

Per list: a fixed length inside its group (``gen.group_lengths``), a
template of docids drawn by sorted-gap sampling below the configuration's
universe from the configuration's ``template_seed``, and the run's docids
with the template's gaps in the seed's order (``gen.shuffled_list``), so
every seed gets the same strides and shapes. The docids are d-gap coded
in 128-int blocks by ``gen.vbyte_blocked``, which writes the program's
host encoder's bytes (each list's stride its largest block rounded up to
128 bytes, as the index builder strides a list) at a few times its speed.
All of it runs on the host in set-up: about 30 s for 195M ints. Each list
is then its own ``CompressedIntArray`` on the device, padded with count-0
blocks to a power of two as ``index/query.py`` pads, so a length group
has one block count; every shape is warmed up before the window. The
docids stay on the host for the check.

The window decodes whole lists in a seeded round-robin order. The caller
keeps the traffic's ``in_flight`` calls issued: it issues the next decode
before it waits, with ``block_until_ready``, for the oldest. The window
closes when the first whole pass over the lists that ends after
``seconds`` has completed, so every seed's window holds the same lists
the same number of times. It keeps the last output of a sample of lists
drawn from the seed: one list of every operand shape, the longest list,
and others up to :data:`CHECKED`. After the window each kept output is
compared, on the host, with the docids that were encoded.
"""
from __future__ import annotations

import sys
import time
from collections import deque
from dataclasses import dataclass

import numpy as np

from chipbench import gen, work
from chipbench.harness import Window

SPAN_NAMES = ("decode",)
CHECKED = 12  # lists whose last output the window keeps for the check


@dataclass
class State:
    arrs: list  # CompressedIntArray per list, on the device
    docids: list  # uint32 docids per list, on the host
    lengths: np.ndarray  # real ints per list
    stored: np.ndarray  # stored bytes per list (work.stored_bytes)
    call_bytes: np.ndarray  # bytes of work of one decode of each list
    order: np.ndarray  # seeded round-robin order of list indices
    sample: frozenset  # lists whose last output the window keeps
    in_flight: int  # calls issued before the caller waits for the oldest
    plan: str
    entry: object  # what the window drives: dispatch.decode
    held: dict  # sampled list index -> its last output of the window


def _sample(seed: int, arrs, lengths) -> frozenset:
    """One list of every operand shape, the longest, then others up to
    :data:`CHECKED`, all drawn from the seed."""
    picks = gen.rng_for(seed, 4).permutation(len(arrs)).tolist()
    by_shape = {}
    for j in picks:
        by_shape.setdefault(arrs[j].payload.shape, j)
    chosen = {int(np.argmax(lengths)), *by_shape.values()}
    for j in picks:
        if len(chosen) >= CHECKED:
            break
        chosen.add(j)
    return frozenset(chosen)


def setup(config, traffic, seed, *, devices, log):
    import jax

    from repro.core import CompressedIntArray
    from repro.kernels.vbyte_decode import dispatch

    if config["format"] != "vbyte" or not config["differential"]:
        raise ValueError("this driver makes d-gap vbyte lists only")
    universe, block = int(config["universe"]), int(config["block_size"])
    arrs, docids, lengths, stored = [], [], [], []
    t0 = time.perf_counter()
    for k in traffic["groups"]:
        lens = gen.group_lengths(k, traffic["lists_per_group"])
        rng = gen.rng_for(seed, 2, k)
        padded = 1 << (k + 1)  # > every length of group k, a power of two
        strides = set()
        for i, n in enumerate(lens):
            template = 1 + gen.sorted_gap_list(
                gen.rng_for(config["template_seed"], k, i), n, universe - 1)
            d = gen.shuffled_list(template, rng, block)
            payload, counts, bases = gen.vbyte_blocked(d, block)
            stored.append(int(work.stored_bytes(
                "vbyte", {"payload": payload, "counts": counts})))
            pad = padded // block - counts.size
            ops = {"payload": np.pad(payload, ((0, pad), (0, 0))),
                   "counts": np.pad(counts, (0, pad)),
                   "bases": np.pad(bases, (0, pad))}
            arrs.append(CompressedIntArray.from_operands(
                {name: jax.device_put(v, devices[0])
                 for name, v in ops.items()},
                format="vbyte", block_size=block, differential=True, n=n))
            docids.append(d)
            lengths.append(n)
            strides.add(payload.shape[1])
        log(f"group K={k}: {len(lens)} lists, {padded // block} blocks "
            f"each, strides {sorted(strides)}")
    jax.block_until_ready([a.payload for a in arrs])
    log(f"made and encoded {len(arrs)} lists, {sum(lengths)} ints, in "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    for a in arrs:  # warm up every shape
        jax.block_until_ready(dispatch.decode(a, plan=config["plan"]))
    log(f"warmed up {len({a.payload.shape for a in arrs})} shapes in "
        f"{time.perf_counter() - t0:.1f} s")
    lengths, stored = np.array(lengths), np.array(stored, np.int64)
    return State(arrs=arrs, docids=docids, lengths=lengths, stored=stored,
                 call_bytes=work.bytes_of_work(stored, lengths),
                 order=gen.rng_for(seed, 3).permutation(len(arrs)),
                 sample=_sample(seed, arrs, lengths),
                 in_flight=int(traffic["in_flight"]), plan=config["plan"],
                 entry=dispatch.decode, held={})


def window(state: State, seconds: float) -> Window:
    import jax

    n = ints = nbytes = failed = 0
    passes = len(state.order)
    pending = deque()  # (list index, output) of the issued calls

    def complete(j, out):
        nonlocal ints, nbytes, failed
        try:
            jax.block_until_ready(out)
        except Exception as e:  # a failed call is counted, the loop goes on
            failed += 1
            print(f"decode of list {j} failed: {e!r}", file=sys.stderr)
            return
        if j in state.sample:
            state.held[j] = out
        ints += int(state.lengths[j])
        nbytes += int(state.call_bytes[j])

    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        j = int(state.order[n % passes])
        n += 1
        try:
            pending.append((j, state.entry(state.arrs[j], plan=state.plan)))
        except Exception as e:
            failed += 1
            print(f"decode of list {j} failed: {e!r}", file=sys.stderr)
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
                int(state.stored.sum()), int(state.lengths.sum()))}


def footprint(state: State) -> dict:
    """Device bytes beside ``memory_peak_bytes``: the stored lists as the
    format counts them, the padded operands that hold them on the device,
    and the outputs the window kept for the check."""
    return {"index_stored": int(state.stored.sum()),
            "index_resident": sum(a.payload.nbytes + a.counts.nbytes
                                  + a.bases.nbytes for a in state.arrs),
            "kept_for_check": sum(o.nbytes for o in state.held.values())}


def check(state: State, win: Window) -> dict:
    """``wrong_ints``: ints of the kept outputs that differ from the encoded
    docids, or are missing (exact, limit 0); ``lists_unchecked``: sampled
    lists with no output from the window (limit 0)."""
    wrong = 0
    for j, out in state.held.items():
        want = state.docids[j]
        got = np.asarray(out).reshape(-1)[:want.size]
        wrong += want.size - got.size + int(np.count_nonzero(
            got != want[:got.size]))
    return {"wrong_ints": (wrong, 0),
            "lists_unchecked": (len(state.sample) - len(state.held), 0)}
