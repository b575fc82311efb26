"""The trace reduction on hand-made traces with known answers, and on a
small trace recorded on a TPU v5e (three ``dispatch.decode`` calls of a
5,000-int list inside a ``bench.window`` annotation, the program's spans
mirrored into the profiler)."""
import os

import numpy as np
import pytest

from chipbench import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "decode_v5e.xplane.pb")


def _xspace(tmp_path, planes):
    """Write ``{plane: {line: [(name, start_ns, dur_ns)]}}`` as an
    ``.xplane.pb`` file and return its path."""
    from jax.profiler import ProfileData

    text = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names = sorted({ev[0] for evs in lines.values() for ev in evs})
        meta = {n: i for i, n in enumerate(names, 1)}
        body = []
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            events = " ".join(
                f"events {{ metadata_id: {meta[n]} offset_ps: {int(s * 1000)}"
                f" duration_ps: {int(d * 1000)} }}" for n, s, d in evs)
            body.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 '
                        f"{events} }}")
        body += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                 for n, i in meta.items()]
        text.append(f'planes {{ id: {pid} name: "{pname}" {" ".join(body)} }}')
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        "\n".join(text)))
    return str(path)


HOST = {"python": [("bench.window", 0, 10000), ("execute", 0, 5500),
                   ("decode", 800, 700), ("PjitFunction(x)", 850, 100),
                   ("gallop", 3500, 1900), ("finalize", 5500, 4500)]}
SPANS = {"bench.window", "execute", "decode", "gallop", "finalize"}


def test_one_chip(tmp_path):
    raw = tracing.read_xspace(_xspace(tmp_path, {
        "/device:TPU:0": {"XLA Ops": [("vbyte_kernel", 1000, 2000),
                                      ("fusion.1", 2000, 2000),
                                      ("vbyte_kernel", 6000, 1000)],
                          "XLA Modules": [("jit_x", 0, 10000)]},
        "/host:CPU": HOST}))
    s = tracing.summarize(raw, SPANS)
    assert s.n_chips == 1
    assert s.window_s == pytest.approx(10e-6)
    # busy: [1000, 4000) and [6000, 7000)
    assert s.busy_s == pytest.approx(4e-6)
    assert s.idle_share == pytest.approx(0.6)
    assert s.device_seconds("vbyte") == pytest.approx(3e-6)
    assert s.device_seconds("^fusion") == pytest.approx(2e-6)
    # gaps [0, 1000) mid 500: execute (decode opens at 800);
    # [4000, 6000) mid 5000: gallop; [7000, 10000) mid 8500: finalize
    assert s.idle_by_span == pytest.approx(
        {"execute": 1e-6, "gallop": 2e-6, "finalize": 3e-6})
    b = s.breakdown()
    assert b["device_ops"][0] == ["vbyte_kernel", pytest.approx(3e-6)]
    assert b["idle_gaps"][0] == ["finalize", pytest.approx(3e-6)]


def test_two_chips_average_and_clip(tmp_path):
    raw = tracing.read_xspace(_xspace(tmp_path, {
        "/device:TPU:0": {"XLA Ops": [("k", 1000, 2000)]},
        # runs past the window's end: clipped to it
        "/device:TPU:1": {"XLA Ops": [("k", 0, 12000)]},
        "/host:CPU": HOST}))
    s = tracing.summarize(raw, SPANS)
    assert s.n_chips == 2
    assert s.busy_s == pytest.approx((2e-6 + 10e-6) / 2)
    assert s.device_seconds("k") == pytest.approx(12e-6)
    # chip 0 idles [0,1000) execute, [3000,10000) mid 6500 finalize
    assert s.idle_by_span == pytest.approx(
        {"execute": 0.5e-6, "finalize": 3.5e-6})


def test_no_device_plane_is_an_error(tmp_path):
    raw = tracing.read_xspace(_xspace(tmp_path, {"/host:CPU": HOST}))
    with pytest.raises(RuntimeError, match="no TPU"):
        tracing.summarize(raw, SPANS)


def _busy_by_sweep(evs, t0, t1):
    """Busy time by a 1-ns grid (independent of the interval union)."""
    grid = np.zeros(int(t1 - t0) + 1, bool)
    for _, s, d in evs:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            grid[int(a - t0):int(b - t0)] = True
    return grid.sum() * 1e-9


def test_device_skew_keeps_programs_after_their_enqueue():
    # enqueues end at 10, 50, 90; the device clock reads 1000 behind
    skew = tracing.device_skew(np.array([10.0, 50.0, 90.0]),
                               np.array([-985.0, -930.0, -880.0]))
    assert skew == pytest.approx(995.0)
    assert tracing.device_skew(np.array([]), np.array([1.0])) == 0.0


def test_recorded_chip_trace():
    raw = tracing.read_xspace(RECORDED)
    # this v5e trace's device clock runs about a millisecond behind
    assert 900e3 < raw.skew_ns < 1300e3
    s = tracing.summarize(raw, {tracing.WINDOW, "decode"})
    t0, t1 = tracing.window_bounds(raw)
    (evs,) = raw.device.values()
    assert s.busy_s == pytest.approx(_busy_by_sweep(evs, t0, t1), abs=5e-9)
    assert 0 < s.idle_share < 1
    kernel = s.device_seconds(tracing.DECODE_KERNELS)
    assert 0 < kernel <= s.busy_s
    # three decode calls: three kernel launches in the window
    n = sum(1 for name, st, d in evs
            if st >= t0 and st + d <= t1
            and __import__("re").search(tracing.DECODE_KERNELS, name))
    assert n == 3
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9)
    assert set(s.idle_by_span) <= {tracing.WINDOW, "decode"}
    ops = dict(s.breakdown()["device_ops"])
    assert ops["vbyte_decode_blocked [tpu_custom_call]"] == pytest.approx(
        kernel)
