"""The one round-record path shared by the eager and pipelined drive loops.

Before graft-trace, `_train_eager` and `_train_pipelined` each assembled,
logged, and appended history records with their own copy of the same code
(and the pipelined copy deferred the host fetch, which a mid-flush crash
could silently lose). `RoundRecordLog` is the single owner now:

- `add(record)` parks a record that may still hold device-resident values
  (the pipelined loop's deferred train metrics);
- `flush()` performs ONE `jax.device_get` over everything pending (inside a
  `metrics_fetch` span), scalarizes, appends to `history` byte-compatibly
  with the pre-telemetry format (checkpoint resume depends on it), mirrors
  to the metrics logger, writes the round log line, and emits a
  `round_committed` ledger event carrying the resolved robustness counters.

The eager loop calls `add` + `flush` every round; the pipelined loop calls
`add` per round and `flush` only at its sync points (guard, eval,
checkpoint, end of drive) — exactly the old deferral structure, minus the
duplication.
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Optional

import jax

from fedml_tpu.telemetry.tracer import NULL_TRACER

log = logging.getLogger("fedml_tpu.fedavg")

#: record keys mirrored into the `round_committed` ledger event — the
#: robustness counters whose loss in a mid-flush crash was the PR 6 bug.
_LEDGER_KEYS = ("participated_count", "quarantined_count", "guard_retries",
                "chaos_dropped", "chaos_nan", "chaos_corrupt")


def moe_load_summary(load, held=None, path=None) -> Dict[str, float]:
    """The `moe_load` event's fields from a round's [expert layers, experts]
    counts of the tokens every routed expert received (summed over steps and
    lanes): the busiest expert's, the mean, and how many got none. `held`
    ((first, count) of the experts this chip holds, for a model that computes
    a share of an expert-parallel layer) adds the same of the held columns:
    `held` (their pairs), `held_max`, `held_mean`, `held_empty`. `path` (the
    round's `moe_path` sums, such a model's too) adds `bounded` and
    `fallback`: the expert-layer calls, a lane and step each, that fit the
    share's row buffer and those that took the worst-case path
    (`ops/moe.py`)."""
    import numpy as np

    load = np.asarray(load, np.float64)
    out = {"max": float(load.max()), "mean": float(load.mean()),
           "empty": int((load == 0).sum())}
    if held is not None:
        first, count = held
        mine = load[:, first:first + count]
        out.update(held=float(mine.sum()), held_max=float(mine.max()),
                   held_mean=float(mine.mean()),
                   held_empty=int((mine == 0).sum()))
    if path is not None:
        out.update(bounded=float(path[0]), fallback=float(path[1]))
    return out


def _scalar(v: Any) -> Any:
    """Device/numpy scalars -> python floats; host ints/strs unchanged."""
    return float(v) if hasattr(v, "dtype") else v


class RoundRecordLog:
    """Owns pending round records from `add()` until `flush()` commits them
    to history + metrics logger + the telemetry ledger."""

    def __init__(self, tracer=None, history: Optional[List[Dict]] = None,
                 metrics_logger=None, ledger=None, bank=None,
                 experts_held=None):
        self.tracer = tracer or NULL_TRACER
        #: (first, count) of the router's experts the model holds, where it
        #: holds a share (`moe_load_summary`); a fact of the build, not data
        self.experts_held = experts_held
        self.history = history if history is not None else []
        self.metrics_logger = metrics_logger
        self.ledger = ledger
        self.bank = bank
        self._pending: List[Dict[str, Any]] = []
        #: high-water mark of pending records — the pipelined loop's bounded
        #: run-ahead regression pin (tests/test_pipeline.py) reads this
        self.max_pending = 0

    def __len__(self) -> int:
        return len(self._pending)

    def add(self, record: Dict[str, Any]) -> None:
        self._pending.append(record)
        self.max_pending = max(self.max_pending, len(self._pending))

    def flush(self, round_idx: Optional[int] = None) -> None:
        """One deferred host sync for every pending record (the pipelined
        loop's single-device_get-per-flush contract), then commit."""
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        with self.tracer.span("metrics_fetch", round_idx,
                              records=len(pending)):
            pending = jax.device_get(pending)
        for rec in pending:
            # the reserved _ledger key carries per-cohort stats blocks
            # (already host arrays after the device_get above — stats ride
            # the SAME deferred fetch, no extra sync); it never reaches
            # history/metrics, and without an attached ledger it is dropped
            blocks = rec.pop("_ledger", None)
            if self.ledger is not None and blocks:
                with self.tracer.span("ledger_write", round_idx,
                                      blocks=len(blocks)):
                    for block in blocks:
                        self.ledger.apply(block)
            # the reserved _bank key carries personal adapter-row blocks
            # (graft-pfl) — updated rows ride the SAME deferred fetch as
            # metrics and ledger stats, then scatter into the mmap bank
            bank_blocks = rec.pop("_bank", None)
            if self.bank is not None and bank_blocks:
                with self.tracer.span("bank_write", round_idx,
                                      blocks=len(bank_blocks)):
                    for block in bank_blocks:
                        self.bank.apply(block)
            # the reserved _moe_load key carries a routed-expert model's
            # per-expert token counts (a vector: history takes scalars); it
            # rides the same fetch and leaves as a `moe_load` event, as do
            # the paths a share's dispatch took (_moe_path)
            load, path = rec.pop("_moe_load", None), rec.pop("_moe_path", None)
            if load is not None:
                self.tracer.event("moe_load", round=rec["round"],
                                  **moe_load_summary(load, self.experts_held,
                                                     path))
            rec = {k: _scalar(v) for k, v in rec.items()}
            self.history.append(rec)
            if self.metrics_logger is not None:
                self.metrics_logger.log(
                    {k: v for k, v in rec.items() if k != "round"},
                    step=rec["round"])
            log.info("round %d: %s", rec["round"],
                     {k: v for k, v in rec.items() if k != "round"})
            self.tracer.event(
                "round_committed", round=rec["round"],
                **{k: rec[k] for k in _LEDGER_KEYS if k in rec})
