"""The benchmark's own spans around its calls into each layer.  Kept in
memory; under ``--trace 1`` each also goes into the profiler's trace as
a ``jax.profiler.TraceAnnotation`` named ``bench:<name>``, so an idle
gap of the device can be named by what the host was doing."""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict
from typing import Dict, List, Tuple

PREFIX = "bench:"


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: Dict[str, List[Tuple[float, float]]] = \
            defaultdict(list)
        #: when the measured window began (the harness sets it): set-up
        #: and warm-up run the same calls under the same span names
        self.window_from = float("inf")

    @contextlib.contextmanager
    def span(self, name: str):
        note = None
        if self.annotate:
            import jax
            note = jax.profiler.TraceAnnotation(PREFIX + name)
            note.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records[name].append((t0, time.perf_counter()))
            if note is not None:
                note.__exit__(None, None, None)

    def total_s(self, name: str) -> float:
        """Seconds under ``name`` over the whole process."""
        return sum(b - a for a, b in self.records.get(name, ()))

    def window_s(self, name: str) -> List[float]:
        """The spans of ``name`` that began inside the window, each in
        seconds: warm-up's are not among them."""
        return [b - a for a, b in self.records.get(name, ())
                if a >= self.window_from]
