"""The span primitive of the round loop.

``span`` is used at every timed site of ``driver.train_global`` and the
engine sites it times: it enters ``jax.profiler.TraceAnnotation`` (which
records nothing with no profiler session on; under ``--profile_dir`` the
span lands on the ``/host:CPU`` plane, on the clock the device planes
use) and on exit writes its duration under ``key`` into ``row`` — a row
of ``results["round_timings"]``, or ``results["setup_timings"]``.  Spans
stay in memory in those rows and go out with ``results``: there is no
flag, no exporter and no second store.  The identifiers (``round=r``)
are what the spans of one round share; nesting gives the parent.

The key's suffix is its unit: ``_ms`` is milliseconds and ``_s`` seconds,
both to three decimals.  Absolute stamps (``t_dispatch_s``,
``t_ready_s``, and those between set-up's phases) are the driver's own
``perf_counter`` readings, written beside the durations they bound.
"""

from __future__ import annotations

import contextlib
import time

import jax


def _scaled(seconds: float, key: str) -> float:
    if key.endswith("_ms"):
        return round(seconds * 1e3, 3)
    if key.endswith("_s"):
        return round(seconds, 3)
    raise ValueError(f"span key {key!r} names no unit (_ms or _s)")


@contextlib.contextmanager
def span(name: str, row: dict | None = None, key: str | None = None, **ids):
    """Time the body as ``row[key]`` and as a profiler event ``name``.
    Without ``row`` / ``key`` it is the annotation alone (a site whose
    own arithmetic already fills its key)."""
    with jax.profiler.TraceAnnotation(name, **ids):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if row is not None and key is not None:
                row[key] = _scaled(time.perf_counter() - t0, key)

