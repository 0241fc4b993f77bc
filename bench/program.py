"""The program's own span and counter tables (``repro.utils.trace``), as
they stand in the benchmark's process when the readers run.

They cover the whole process: the build, the checked and warm-up epochs,
the window and the traced epochs. A program without that module (a commit
from before it) gives None, and a reader that needs the tables then
reads nothing.
"""

from __future__ import annotations

import importlib
from typing import Dict, Optional


def tables() -> Optional[Dict[str, Dict]]:
    try:
        mod = importlib.import_module("repro.utils.trace")
    except ImportError:
        return None
    return mod.snapshot()


def span(name: str) -> Optional[Dict]:
    """One row of the span table, or None."""
    t = tables()
    return t["spans"].get(name) if t else None


def counter(name: str) -> Optional[float]:
    t = tables()
    return t["counters"].get(name) if t else None
