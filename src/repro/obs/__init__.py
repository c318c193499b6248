"""Unified observability layer: tracker protocol, pluggable sinks,
histograms, nestable spans. See ``repro/obs/README.md`` for the full
metrics reference and ``repro.obs.tracker`` for the row schema and
determinism contract; ``repro.obs.lint`` checks emitted metric names
against the reference doc (the verify.sh obs lane)."""

from repro.obs.tracker import (
    DEFAULT_BOUNDS,
    NULL,
    WALL_FIELDS,
    Histogram,
    JsonlSink,
    MemorySink,
    NullTracker,
    Sink,
    Tracker,
    deterministic_rows,
)

__all__ = [
    "DEFAULT_BOUNDS",
    "NULL",
    "WALL_FIELDS",
    "Histogram",
    "JsonlSink",
    "MemorySink",
    "NullTracker",
    "Sink",
    "Tracker",
    "deterministic_rows",
]
