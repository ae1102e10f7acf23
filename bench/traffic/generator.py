"""The one general traffic generator: it turns a mix file into the fixed
work of one run.

Each request is one of the paper's random range queries (PASS §5.1.2),
sent by an independent user: it asks for every aggregate the
configuration names, with intervals at the mix's ``ci_level``. A mix file
states the loop (``open``: arrivals on a schedule, or ``closed``: sessions
that each wait for their answer), the arrival process and rate or the
number of sessions, and an optional ingest stream. Beside an ingest stream,
every ``whole_table_every``-th request asks for the whole table's totals
instead: its exact count names the state of the stream it was served from.

The multiset of gaps is drawn once from ``TEMPLATE_SEED``; a run's seed
only reorders it. Every seed therefore offers the same amount of work at
the same load, and runs differ in order and in the predicates drawn.
"""
from __future__ import annotations

import numpy as np

TEMPLATE_SEED = 0
QUERIES_PER_REQUEST = 1


def shape_rows(mix: dict) -> list[int]:
    """Every request size the mix sends (warm-up covers their classes)."""
    return [QUERIES_PER_REQUEST]


def open_schedule(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Due offsets (s, ascending, all < ``seconds``) of an open-loop
    window."""
    arr = mix["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    rate = float(arr["rate_per_s"])
    tpl = np.random.default_rng(TEMPLATE_SEED)
    gaps = tpl.exponential(1.0 / rate, size=int(rate * seconds * 2) + 16)
    n = int(np.searchsorted(np.cumsum(gaps), seconds, side="left"))
    # any order of the n gaps ends at the same time, inside the window
    return np.cumsum(gaps[:n][np.random.default_rng(seed).permutation(n)])


def whole_table(mix: dict, i: int) -> bool:
    """Whether the ``i``-th request of an open loop asks for the whole
    table instead of a random range."""
    every = mix.get("ingest", {}).get("whole_table_every")
    return every is not None and i % int(every) == 0


def closed_sessions(mix: dict) -> int:
    """The number of closed-loop sessions, each with one request
    outstanding."""
    return int(mix["sessions"])
