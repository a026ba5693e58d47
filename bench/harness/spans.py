"""The benchmark's own host spans, around its calls into the program.

Each span is kept in memory (name, start, end on ``time.perf_counter``)
and, while the profiler records, also written into its trace as a
``jax.profiler.TraceAnnotation`` named ``bench.<name>``, where the trace
reduction finds it on the same clock as the device's operations.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

import jax

PREFIX = "bench."


class Spans:
    def __init__(self):
        self.done: List[Tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
        self.done.append((name, t0, time.perf_counter()))
