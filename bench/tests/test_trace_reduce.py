"""``bench.trace_reduce`` on a hand-made trace whose answers are worked out
by hand: two TPU devices, one kernel, one collective that half overlaps a
kernel, an operation outside the window, and host spans that label the
gaps. Times below are nanoseconds; and on a trace recorded on the chip."""

import gzip
import os

import pytest

from bench import trace_reduce as T


def _plane(pid, name, line, events):
    meta = {n: i + 1 for i, n in enumerate(dict.fromkeys(n for n, _, _ in events))}
    evs = "".join(f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
                  f"duration_ps: {(e - s) * 1000} }}\n" for n, s, e in events)
    md = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                 for n, i in meta.items())
    return (f'planes {{ id: {pid} name: "{name}"\n'
            f'lines {{ id: 1 name: "{line}" timestamp_ns: 0\n{evs}}}\n{md}}}\n')


TRACE = (
    _plane(1, "/device:TPU:0", "XLA Ops", [
        ("fusion.1", 1000, 3000),
        ("seg_aggregate.3", 4000, 5000),
        ("all-to-all.1", 4500, 6000),
        ("fusion.2", 8000, 9000),
        ("fusion.3", 11000, 12000),          # after the window
    ])
    + _plane(2, "/device:TPU:1", "XLA Ops", [("fusion.1", 2000, 4000)])
    + _plane(3, "/host:CPU", "python", [
        ("window", 1000, 10000),
        ("train_epoch", 1000, 5500),
        ("$adamw.py:30 adamw_update", 6000, 8000),
        ("$builtins isinstance", 6500, 6600),
    ])
)


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    return T.reduce(str(path), kernels=("seg_aggregate",))


def test_union_and_uncovered_length():
    u = T._union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert T._length(u) == 7
    assert T._minus((2, 6), u) == 2        # 3..5 is uncovered
    assert T._clip(u, 1, 6) == [(1, 3), (5, 6)]


def test_window_busy_and_idle(reduced):
    # Device 0 is busy 2000 + 2000 + 1000 ns of the 9000 ns window, device 1
    # 2000 ns; the operation after the window does not count.
    assert reduced["window_s"] == pytest.approx(9000e-9)
    assert reduced["busy_s"] == pytest.approx(3500e-9)
    assert reduced["idle_share"] == pytest.approx(1 - 3500 / 9000)


def test_kernel_and_collective_time(reduced):
    assert reduced["kernel_s"] == {"seg_aggregate": pytest.approx(500e-9)}
    assert reduced["collective_s"] == pytest.approx(750e-9)
    # 4500..5000 overlaps the kernel; 5000..6000 runs alone.
    assert reduced["collective_exposed_s"] == pytest.approx(500e-9)


def test_breakdown(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["fusion.1"] == pytest.approx(2000e-9)
    assert ops["all-to-all.1"] == pytest.approx(750e-9)
    assert reduced["device_ops"][0][0] == "fusion.1"
    assert "fusion.3" not in ops
    gaps = reduced["idle_gaps"]
    assert gaps[0] == ["$adamw.py:30 adamw_update", pytest.approx(2000e-9)]
    assert sorted(n for n, _ in gaps[1:]) == ["no host span", "train_epoch"]


def test_no_window_span_is_an_error(tmp_path):
    from jax.profiler import ProfileData
    path = tmp_path / "nowindow.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _plane(1, "/device:TPU:0", "XLA Ops", [("fusion.1", 0, 10)])))
    with pytest.raises(ValueError, match="window"):
        T.reduce(str(path))


# A trace recorded on a TPU v5e by ``bench/run.py:record_trace``: the
# one-worker step on a 4,096-node graph, one epoch, traced with the
# Python tracer.
CHIP_TRACE = os.path.join(os.path.dirname(__file__), "data", "tpu_small.xplane.pb.gz")


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("chip") / "tpu_small.xplane.pb"
    with gzip.open(CHIP_TRACE, "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_chip_trace_against_its_events(chip_trace):
    """The reduction of a real trace equals sums over its events taken
    directly: one device, whose operations do not overlap."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(chip_trace)
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for p in pd.planes if p.name == "/host:CPU"
            for line in p.lines for e in line.events]
    lo, hi = next((s, e) for n, s, e in host if n == T.WINDOW_SPAN)
    ops = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                 for p in pd.planes if p.name == "/device:TPU:0"
                 for line in p.lines if line.name == T.OPS_LINE
                 for e in line.events if e.start_ns + e.duration_ns > lo
                 and e.start_ns < hi)
    assert all(a[1] <= b[0] for a, b in zip(ops, ops[1:]))
    busy = sum(min(e, hi) - max(s, lo) for s, e, _ in ops)
    kernel = sum(min(e, hi) - max(s, lo) for s, e, n in ops if "seg_aggregate" in n)

    got = T.reduce(chip_trace, kernels=("seg_aggregate",))
    assert got["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert got["busy_s"] == pytest.approx(busy * 1e-9)
    assert got["idle_share"] == pytest.approx(1 - busy / (hi - lo))
    assert got["kernel_s"] == {"seg_aggregate": pytest.approx(kernel * 1e-9)}
    assert "collective_s" not in got
    assert got["device_ops"][0][0].startswith("seg_aggregate")
    gaps = [g for _, g in got["idle_gaps"]]
    assert len(gaps) == 10 and gaps == sorted(gaps, reverse=True)
    assert any("adamw_update" in name for name, _ in got["idle_gaps"])
