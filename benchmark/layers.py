"""Per-layer metrics. Each is a data file metrics/<name>.json with a
`reader`; this file evaluates the readers. A reader that finds nothing
to read returns None and the metric is left out of the result line — a
share of a peak is never reported as 0.

Readers:
  counter_ratio  sum of deltas of the selected /metrics series between
                 the scrapes at the window's start and end, divided by
                 `per`: "queries" / "pushes" (the client's count over the
                 window), "one", or another selection of series; times
                 `scale`.
  compiles       new files in the compile cache directory over the
                 window plus the deltas of the selected counters.
  device_idle    1 - device busy seconds / the seconds the trace covers (its
                 own start and stop times), in %.
"""

from __future__ import annotations

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
_SERIES = re.compile(r'^([A-Za-z_:][\w:]*)(?:\{(.*)\})?$')
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def _split(key: str):
    m = _SERIES.match(key)
    return (m.group(1), dict(_LABEL.findall(m.group(2) or ""))) if m else (key, {})


def selected(scrape: dict, sel: dict) -> float | None:
    """Sum of the series of `family` whose labels pass `labels` (label ->
    allowed values) and `not_labels`; None where no series matches."""
    total, found = 0.0, False
    for key, val in scrape.items():
        family, labels = _split(key)
        if family != sel["family"]:
            continue
        if any(labels.get(k) not in v for k, v in sel.get("labels", {}).items()):
            continue
        if any(labels.get(k) in v for k, v in sel.get("not_labels", {}).items()):
            continue
        total, found = total + val, True
    return total if found else None


def delta(before: dict, after: dict, sels: list) -> float | None:
    """Growth of the selected series between two scrapes. A series that
    is new in `after` started at 0."""
    total, found = 0.0, False
    for sel in sels:
        b = selected(after, sel)
        if b is not None:
            total, found = total + b - (selected(before, sel) or 0.0), True
    return total if found else None


class Facts:
    """What a run hands the readers."""

    def __init__(self, scrapes: tuple, counts: dict, new_cache_files: int, trace: dict | None):
        self.scrapes = scrapes      # /metrics (before, after) the window
        self.counts = counts        # {"queries": n, "pushes": n} answered in the window, each its own
        self.new_cache_files = new_cache_files  # compile cache files new over the window
        self.trace = trace          # xplane.py's reduction of the capture, or None


def evaluate(reader: dict, facts: Facts) -> float | None:
    kind = reader["type"]
    if kind in ("counter_ratio", "compiles"):
        before, after = facts.scrapes
        num = delta(before, after, reader["series"])
        if kind == "compiles":
            return (num or 0.0) + facts.new_cache_files
        per = reader.get("per", "one")
        den = (1.0 if per == "one" else facts.counts.get(per) if isinstance(per, str)
               else delta(before, after, per))
        if num is None or not den:
            return None
        return num / den * reader.get("scale", 1.0)
    if kind == "device_idle":
        if facts.trace is None:
            return None
        return 100.0 * (1.0 - facts.trace["busy_s"] / facts.trace["window_s"])
    raise ValueError(f"unknown reader type {kind!r}")


def load_reader(name: str) -> dict:
    with open(os.path.join(HERE, "metrics", name + ".json")) as f:
        return json.load(f)["reader"]
