"""``bench.scopes`` on a hand-made trace whose answers are worked out by
hand, and on the trace recorded on the chip; the readers of the program's
tables on a program that has none; and ``reduce()`` of the recorded trace,
pinned. Times in the hand-made trace are nanoseconds."""

import gzip
import os
import sys

import pytest

from bench import program, scopes
from bench import run as R
from bench import trace_reduce as T


def _plane(pid, name, lines):
    names = list(dict.fromkeys(n for evs in lines.values() for n, _, _ in evs))
    meta = {n: i + 1 for i, n in enumerate(names)}
    body = ""
    for li, (line, evs) in enumerate(lines.items()):
        body += (f'lines {{ id: {li + 1} name: "{line}" timestamp_ns: 0\n'
                 + "".join(f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
                           f"duration_ps: {(e - s) * 1000} }}\n" for n, s, e in evs)
                 + "}\n")
    md = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                 for n, i in meta.items())
    return f'planes {{ id: {pid} name: "{name}"\n{body}{md}}}\n'


TRACE = (
    _plane(1, "/device:TPU:0", {
        "XLA Modules": [("jit_step_sm(123)", 1000, 6000), ("jit_add(9)", 7000, 7500)],
        "XLA Ops": [
            ("fusion.1", 1000, 2000),
            ("seg_aggregate.3", 2000, 4000),
            ("fusion.2", 4500, 5000),
            ("copy.1", 5000, 5500),
            ("add", 7000, 7500),              # the eager optimizer's module
            ("fusion.1", 11000, 12000),       # after the window
        ]})
    + _plane(2, "/device:TPU:1", {
        "XLA Modules": [("jit_step_sm(123)", 1000, 6000)],
        "XLA Ops": [("fusion.1", 1000, 3000)]})
    + _plane(3, "/host:CPU", {"python": [
        ("window", 1000, 10000),
        ("epoch", 900, 9500),
        ("epoch/step", 900, 5200),
        ("epoch/optimizer", 5200, 7200),
        ("$builtins isinstance", 6000, 6100),
        ("epoch/fetch", 7200, 9500),
    ]})
)
OPS = {"fusion.1": "layer0/update", "seg_aggregate.3": "layer0/aggregate/k8",
       "fusion.2": "layer0/aggregate/k8/bwd", "add": "loss"}
SPANS = ["epoch", "epoch/step", "epoch/optimizer", "epoch/fetch"]


@pytest.fixture(scope="module")
def hand(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    return str(path)


def test_scope_time_counts_the_step_module_only(hand):
    got = scopes.scope_time(hand, "jit_step_sm", OPS)
    # Averaged over the two devices that ran the step; ``add`` ran in the
    # optimizer's module and the late fusion.1 after the window.
    assert got["by_scope"] == {
        "layer0/update": pytest.approx(1500e-9),
        "layer0/aggregate/k8": pytest.approx(1000e-9),
        "layer0/aggregate/k8/bwd": pytest.approx(250e-9)}
    assert got["unattributed_s"] == pytest.approx(250e-9)
    assert got["step_s"] == pytest.approx(3000e-9)
    assert scopes.scope_sum(got["by_scope"], "aggregate") == pytest.approx(1250e-9)
    assert scopes.scope_sum(got["by_scope"], "update") == pytest.approx(1500e-9)
    with pytest.raises(ValueError, match="jit_other"):
        scopes.scope_time(hand, "jit_other", OPS)


def test_idle_by_innermost_program_span(hand):
    got = scopes.idle_by_span(hand, SPANS)
    # Device 0 idles 4000..4500, 5500..7000 and 7500..10000 of the window;
    # the Python tracer's event is no program span.
    assert got == {"epoch/step": pytest.approx(500e-9),
                   "epoch/optimizer": pytest.approx(1500e-9),
                   "epoch/fetch": pytest.approx(2000e-9),
                   scopes.NO_SPAN: pytest.approx(500e-9)}
    assert scopes.idle_by_span(hand, []) == {scopes.NO_SPAN: pytest.approx(4500e-9)}
    # 4000..4500 and 5500..6000 fall inside the step module's run.
    assert scopes.idle_by_span(hand, SPANS, module="jit_step_sm") == {
        "jit_step_sm": pytest.approx(1000e-9),
        "epoch/optimizer": pytest.approx(1000e-9),
        "epoch/fetch": pytest.approx(2000e-9),
        scopes.NO_SPAN: pytest.approx(500e-9)}


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("chip") / "tpu_small.xplane.pb"
    with gzip.open(os.path.join(os.path.dirname(__file__), "data",
                                "tpu_small.xplane.pb.gz"), "rb") as f:
        path.write_bytes(f.read())
    return str(path)


def test_reduce_of_the_chip_trace_is_unchanged(chip_trace):
    got = T.reduce(chip_trace, kernels=("seg_aggregate",))
    assert got["window_s"] == pytest.approx(0.12003575)
    assert got["busy_s"] == pytest.approx(0.038760037)
    assert got["kernel_s"] == {"seg_aggregate": pytest.approx(0.038337697)}
    assert got["device_ops"][0] == ["seg_aggregate.21", pytest.approx(0.006248203)]
    assert got["idle_gaps"][0] == ["$adamw.py:23 adamw_update",
                                   pytest.approx(0.003361589)]


def test_chip_trace_by_scope_and_span(chip_trace):
    """With no scope map every op of the step's module is unattributed, and
    the step's module holds the kernel; the idle time by span adds up to
    the idle time ``reduce`` reads."""
    red = T.reduce(chip_trace, kernels=("seg_aggregate",))
    st = scopes.scope_time(chip_trace, "jit_step_sm", {})
    assert st["by_scope"] == {} and st["unattributed_s"] == st["step_s"]
    assert red["kernel_s"]["seg_aggregate"] <= st["step_s"] <= red["busy_s"]
    kernel = {n: "layer0/aggregate" for n, _ in red["device_ops"]}
    assert scopes.scope_time(chip_trace, "jit_step_sm", kernel)["by_scope"][
        "layer0/aggregate"] > 0
    idle = red["window_s"] - red["busy_s"]
    by_span = scopes.idle_by_span(chip_trace, ["train_epoch"])
    assert sum(by_span.values()) == pytest.approx(idle)
    assert set(by_span) == {"train_epoch", scopes.NO_SPAN}
    in_step = scopes.idle_by_span(chip_trace, ["train_epoch"], module="jit_step_sm")
    assert sum(in_step.values()) == pytest.approx(idle)
    assert 0 < in_step["jit_step_sm"] < idle


NEW_READERS = ("agg_slot_fill", "agg_ns_per_slot", "compile_s",
               "build_partition_s")
TRACED = {"trace": {"kernel_s": {"seg_aggregate": 2.0}, "busy_s": 2.1,
                    "idle_share": 0.01},
          "trace_epochs": 2}


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_reads_nothing_without_the_program_tables(name, monkeypatch):
    """A program from before ``repro.utils.trace`` (the parent's case)."""
    monkeypatch.setitem(sys.modules, "repro.utils.trace", None)
    assert program.tables() is None
    metrics_dir = os.path.join(R.HERE, "metrics")
    assert R.read_metric(metrics_dir, name, dict(TRACED)) is None


def test_readers_on_tables(monkeypatch):
    tables = {
        "spans": {"epoch": {"n": 8, "s": 60.0, "min": 7.4},
                  "epoch/optimizer/compile": {"n": 40, "s": 1.8},
                  "epoch/step/compile": {"n": 3, "s": 2.5},
                  "build/partition": {"n": 1, "s": 22.5, "min": 22.5}},
        "counters": {"agg.edges": 8 * 750, "agg.slots": 8 * 1000}}
    monkeypatch.setattr(program, "tables", lambda: tables)
    metrics_dir = os.path.join(R.HERE, "metrics")
    got = {n: R.read_metric(metrics_dir, n, dict(TRACED)) for n in NEW_READERS}
    assert got == {"agg_slot_fill": pytest.approx(75.0),
                   # 1 s of kernel an epoch over 1,000 slots an epoch
                   "agg_ns_per_slot": pytest.approx(1e6),
                   "compile_s": pytest.approx(4.3),
                   "build_partition_s": pytest.approx(22.5)}
    assert R.read_metric(metrics_dir, "agg_ns_per_slot", {"trace": None}) is None
