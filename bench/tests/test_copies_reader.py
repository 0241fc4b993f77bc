"""The reader of ``agg_copies_per_edge`` on stub program tables: the
copy counter over the edge counter, and nothing where the program has no
tables or no copy counter (a program from before the counter)."""

import os
import sys

import pytest

from bench import program
from bench import run as R

METRICS = os.path.join(R.HERE, "metrics")
TRACED = {"trace": {"kernel_s": {"seg_aggregate": 2.0}, "busy_s": 2.1,
                    "idle_share": 0.01},
          "trace_epochs": 2}


def _read(monkeypatch, counters):
    tables = {"spans": {"epoch": {"n": 8, "s": 60.0, "min": 7.4}},
              "counters": counters}
    monkeypatch.setattr(program, "tables", lambda: tables)
    return R.read_metric(METRICS, "agg_copies_per_edge", dict(TRACED))


def test_copies_over_edges(monkeypatch):
    got = _read(monkeypatch, {"agg.edges": 8 * 750, "agg.slots": 8 * 1000,
                              "agg.copies": 8 * 810})
    assert got == pytest.approx(1.08)


@pytest.mark.parametrize("counters", [
    {"agg.edges": 8 * 750, "agg.slots": 8 * 1000},  # no copy counter
    {"agg.copies": 8 * 810},                         # no edge counter
    {},
])
def test_reads_nothing_without_both_counters(monkeypatch, counters):
    assert _read(monkeypatch, counters) is None


def test_reads_nothing_without_the_program_tables(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.utils.trace", None)
    assert program.tables() is None
    assert R.read_metric(METRICS, "agg_copies_per_edge", dict(TRACED)) is None
