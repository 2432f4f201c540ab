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

``hbm`` is the counter beside it: the device allocator's own statistics
(``Device.memory_stats()``), written into the same rows at the same
sites (``hbm_in_use_bytes``, ``hbm_peak_bytes``: whole bytes).  A reading
is written only where the backend reports one: the CPU's allocator keeps
no statistics, and a row of a CPU run has neither key.
"""

from __future__ import annotations

import contextlib
import time

import jax


def device_stats(device) -> dict | None:
    """The allocator's statistics of one device, or ``None`` where the
    backend keeps none (the CPU)."""
    return device.memory_stats()


def hbm(row: dict, devices) -> dict | None:
    """Read the allocator of each of ``devices`` and write into ``row``
    what the fullest chip holds now, ``hbm_in_use_bytes``
    (``bytes_in_use``), and the highest mark any chip has reached since
    the process started, ``hbm_peak_bytes`` (``peak_bytes_in_use``: the
    allocator never lowers it).  Returns the statistics of the chip with
    the highest mark, whole; where no device reports any it writes
    nothing and returns ``None``.  One host call a device, nothing on
    the device."""
    stats = [s for s in map(device_stats, devices) if s]
    if not stats:
        return None
    row["hbm_in_use_bytes"] = max(int(s["bytes_in_use"]) for s in stats)
    fullest = max(stats, key=lambda s: s["peak_bytes_in_use"])
    row["hbm_peak_bytes"] = int(fullest["peak_bytes_in_use"])
    return fullest


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

