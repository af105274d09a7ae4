"""Bounded cohort prefetch — `prefetch_to_device` double-buffering for
federated rounds.

PERF.md's scale validation found the 3400-client FEMNIST north-star run
driver-dispatch bound at ~1 s/round while the in-graph
scan path is ~70x faster: the chip idles while the host gathers sampled
client rows, synchronously ships them to HBM, and resolves metrics key by
key. But client sampling is a pure function of `(seed, round_idx)`
(algorithms.fedavg.client_sampling), chaos fault schedules are a pure
function of `(plan seed, round_idx)` (robustness.chaos.FaultPlan.events),
and the padded cohort geometry is static — so round t+1's staged cohort is
fully knowable while round t executes. This module is the flax/t5x
`prefetch_to_device` input-pipeline pattern applied to federated cohorts
instead of batches.

`CohortPrefetcher` runs a SINGLE staging thread (stagings are serialized —
`PackedClients.select` is a host memcpy and `StreamingPackedClients.select`
holds its own lock around the LRU, so one worker keeps ordering trivial and
the host-RAM footprint at one in-progress cohort) and keeps at most `depth`
staged-or-in-progress cohorts alive. The staging callback does the gather /
fault-injection / padding / non-blocking `jax.device_put`; this class owns
only scheduling, bounding, and rollback invalidation.

Correctness contract (tests/test_pipeline.py):
- staging is a pure function of `round_idx` — a re-staged cohort is
  byte-identical to the original, so guard retries and cache misses can
  always fall back to staging on demand;
- consumed cohorts leave the prefetcher (their device buffers are donated
  into `round_fn` by the pipelined drive loop and must never be re-issued);
- `invalidate()` (guard rollback) drops every in-flight future, so a
  retried round can never consume a cohort staged against the rolled-back
  timeline.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

from fedml_tpu import telemetry


@dataclass
class StagedCohort:
    """One round's device-resident inputs, staged ahead of consumption.

    `x`/`y`/`counts` (+ optional `participation`) are committed device
    arrays ready to feed `round_fn`; `faults` is the host-side
    FaultEvents used for the round's history record; `client_idx` is the
    sampled cohort (test observability); `personal` (graft-pfl — None
    unless the run personalizes) is `{"rows": host bank row ids, "tree":
    device-resident [C, ...] adapter rows}`, staged alongside the data so
    the round dispatch stays one hop and the scatter-back targets exactly
    the rows that were fed. `rows` (the cohort's real rows) and `slots`
    (what the round program executes for it, padding included, i.e. up to
    the cohort's last real batch where the program stops there:
    engine.round_slots with the host counts) are host integers counted at
    staging, for the `dispatch` span, as are `lanes` and `trip`
    (engine.round_work: slots = lanes x trip x batch)."""

    round_idx: int
    x: Any
    y: Any
    counts: Any
    participation: Any | None
    faults: Any | None
    client_idx: np.ndarray
    personal: Any | None = None
    rows: int = 0
    slots: int = 0
    lanes: int = 0
    trip: int = 0


#: invalidate()'s default scope: every job's in-flight stagings (the
#: single-job drive loops' legacy guard-rollback semantics).
_ALL_JOBS = object()


class CohortPrefetcher:
    """Depth-bounded background stager keyed by (job, round index).

    `prefetch(r)` schedules staging of round r if there is capacity;
    `get(r)` returns round r's StagedCohort, staging it on demand on a miss
    (first round, guard retry after `invalidate()`, or depth exhaustion);
    `invalidate()` forgets every in-flight staging. `staged_rounds` /
    `consumed_rounds` / `misses` expose the schedule to tests.

    Multi-tenant scope (`job=` on prefetch/get/invalidate): the serving
    scheduler shares ONE prefetcher across tenant jobs, so staged buffers
    are keyed by `(job, round_idx)` and `invalidate(job=X)` drops only X's
    in-flight cohorts — one tenant's rollback can never evict another
    tenant's staged rounds. `job=None` everywhere (the single-job drive
    loops) reproduces the legacy behavior exactly, including the drop-ALL
    `invalidate()`. With a job given, the staging callback is called as
    `stage_fn(round_idx, job)` and runs under `telemetry.job_scope(job)`
    so stager-thread spans carry the tenant label."""

    def __init__(self, stage_fn: Callable[..., StagedCohort], depth: int = 2):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self._stage_fn = stage_fn
        self.depth = int(depth)
        self._pool = ThreadPoolExecutor(max_workers=1,
                                        thread_name_prefix="cohort-prefetch")
        # (job, round_idx) -> Future; job is None for single-job drives
        self._inflight: dict[tuple, Future] = {}
        self._lock = threading.Lock()
        self.staged_rounds: list[int] = []   # every staging that actually ran
        self.consumed_rounds: list[int] = []
        self.misses = 0
        self.invalidations = 0
        self._staged_at: dict[tuple, float] = {}  # key -> staging-done time

    def _submit(self, round_idx: int, job=None) -> Future:
        # the span open on the scheduling thread (the round that called
        # prefetch(), or the stage_wait of a missing get()) becomes the
        # parent of the spans the worker opens for this staging
        cause = telemetry.open_span_id()

        def work():
            # the append is atomic under the GIL; single worker => ordered
            self.staged_rounds.append(round_idx)
            with telemetry.adopt(cause):
                if job is None:
                    staged = self._stage_fn(round_idx)
                else:
                    with telemetry.job_scope(job):
                        staged = self._stage_fn(round_idx, job)
            # stager thread vs invalidate()'s clear() on the main thread —
            # the timestamp write must not resurrect an invalidated round
            with self._lock:
                self._staged_at[(job, round_idx)] = time.monotonic()
            return staged

        return self._pool.submit(work)

    def prefetch(self, round_idx: int, job=None) -> bool:
        """Schedule round `round_idx` (of `job`, when serving) for
        background staging. No-op (False) when it is already in flight or
        the pipeline is at depth."""
        key = (job, round_idx)
        with self._lock:
            if key in self._inflight or len(self._inflight) >= self.depth:
                return False
            self._inflight[key] = self._submit(round_idx, job)
            return True

    def get(self, round_idx: int, job=None) -> StagedCohort:
        """Round `round_idx`'s staged cohort; blocks until staged. The
        cohort leaves the prefetcher — its buffers are the caller's to
        donate. A miss stages on demand (same bytes, staging is pure)."""
        key = (job, round_idx)
        with self._lock:
            fut = self._inflight.pop(key, None)
            miss = fut is None
            depth_in_flight = len(self._inflight)
            if miss:
                self.misses += 1
                fut = self._submit(round_idx, job)
        staged = fut.result()
        self.consumed_rounds.append(round_idx)
        # pipeline-occupancy gauge: how deep the pipeline was when this
        # round was consumed and how long its cohort sat staged-ahead
        # (0 on a miss — it was staged on demand just now)
        with self._lock:
            done_at = self._staged_at.pop(key, None)
        ahead_s = max(0.0, time.monotonic() - done_at) if done_at else 0.0
        telemetry.gauge("prefetch_occupancy", round=round_idx,
                        inflight=depth_in_flight, ahead_s=round(ahead_s, 6),
                        miss=miss)
        return staged

    def invalidate(self, job=_ALL_JOBS) -> None:
        """Drop in-flight prefetches (guard rollback): the retried round
        re-stages from scratch, and no cohort scheduled before the rollback
        can be consumed after it. Default scope is EVERY job (the legacy
        single-job semantics); `invalidate(job=X)` drops only job X's
        stagings, leaving other tenants' staged cohorts untouched."""
        with self._lock:
            keys = [k for k in self._inflight
                    if job is _ALL_JOBS or k[0] == job]
            dropped = len(keys)
            for k in keys:
                # best-effort; an already-running job just gets dropped
                self._inflight.pop(k).cancel()
                self._staged_at.pop(k, None)
            if job is _ALL_JOBS:
                self._staged_at.clear()
        self.invalidations += 1
        telemetry.gauge("prefetch_invalidate", dropped=dropped)

    def close(self) -> None:
        self.invalidate()
        self._pool.shutdown(wait=True)

    def __enter__(self) -> "CohortPrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
