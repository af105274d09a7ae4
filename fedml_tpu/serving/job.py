"""Declarative federated jobs: a round program as a schedulable unit.

The drive loops (`fedml_tpu/algorithms`) own the whole process — one job,
one `train()` call to completion. `JobDescriptor` lifts the inputs of such
a run (model, algorithm, FedConfig, client-store handle, rng seed, round
budget) into a declarative value, and `Job` wraps the runtime state so ONE
round is a `step()` call the scheduler can interleave with other tenants.

Bit-reproducibility argument: everything a round consumes is a pure
function of `(cfg.seed, round_idx)` — sampling, staging, the round rng,
chaos faults and straggler latencies — and each Job owns its own
`FedAvgAPI` (params, aggregator state, jit wrappers) plus its own round
counter. Interleaving tenants therefore cannot perturb any tenant's
stream: a job stepped under the scheduler trains byte-identical params to
the same job run solo through `FedAvgAPI.train` (tests/test_serving.py).

Synchronous jobs reuse `FedAvgAPI.train_one_round` verbatim; buffered jobs
(`cfg.buffer_size > 0`) reuse `algorithms.buffered.BufferedRunner` — the
same step/drain code path as the classic buffered loop — optionally in
`partial_dispatch` mode, where each dispatch round stages only as many
replacement clients as arrivals have freed buffer capacity
(`FedAvgAPI.stage_partial_cohort`) instead of re-running the full cohort.

Overload robustness (graft-slo): `evict()` snapshots the job's FULL
Checkpointable surface to host — params/adapters + aggregator (and codec
residual) state via `_ckpt_tree`, the history via `_ckpt_meta`, the
buffered runner's device buffer + birth tags + pending-arrival schedule
via `BufferedRunner.snapshot()` (the same surface guard rollback rewinds),
and the round guard's loss window — then drops every device reference, so
the tenant's mesh slot is free. `resume()` rebuilds the api/runner from
the descriptor (the persistent XLA compile cache makes the rebuild a
warm start — traced again, compiled never) and restores the snapshot;
an evicted-then-resumed tenant trains byte-identical final params to its
uninterrupted solo run, for sync AND buffered (straggler-armed) tenants
(tests/test_serving.py). Snapshots optionally spill to the mmap-backed
`serving.evict_store.EvictionStore` so parked tenants cost file pages,
not RSS. Under LoRA the snapshot is adapters-only (`_ckpt_tree` strips
the deterministic frozen base), so eviction is O(adapter bytes).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import numpy as np

from fedml_tpu.algorithms.buffered import BufferedRunner
from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.core.config import FedConfig
from fedml_tpu.robustness.chaos import summarize as chaos_summary
from fedml_tpu.telemetry.records import RoundRecordLog

#: SLO classes a tenant may declare: latency-bound tenants form a strict
#: priority tier in the scheduler's pick and may preempt throughput-bound
#: residents via evict(); throughput-bound tenants absorb the slack.
SLO_CLASSES = ("throughput", "latency")


@dataclass(frozen=True)
class JobDescriptor:
    """Everything needed to (re)build one tenant's federated run.

    `weight` feeds the scheduler's deficit-weighted fair-share policy;
    `partial_dispatch` opts a buffered job into replacement-client
    dispatch. `trainer_factory` defaults to the standard classification
    trainer over `create_model(cfg.model, output_dim=dataset.class_num)`.

    graft-slo fields: `slo` declares the tenant's class (see SLO_CLASSES);
    `deadline_s` arms the scheduler's per-tenant deadline-miss ledger
    (completion - submission > deadline_s -> a `deadline_miss` event —
    measured telemetry, never a pick input); `guard` attaches a round
    guard (robustness.guard.RoundGuard) to the served job, mirroring the
    solo drive's rollback-and-retry semantics exactly.
    """

    name: str
    config: FedConfig
    dataset: Any  # data.registry.FederatedDataset (any backing store)
    aggregator_name: str = "fedavg"
    trainer_factory: Optional[Callable[[], Any]] = None
    chaos: Any = None  # robustness.chaos.FaultPlan
    weight: float = 1.0
    partial_dispatch: bool = False
    slo: str = "throughput"
    deadline_s: Optional[float] = None
    guard: Any = None  # robustness.guard.RoundGuard
    #: models.adapter_bank.AdapterBank — required when config.personalize.
    #: The bank is HOST state (mmap-backed), owned by the caller and shared
    #: across evict/resume: eviction flushes its dirty rows to disk but
    #: never closes it, so a resumed tenant gathers exactly the rows its
    #: evicted self scattered.
    bank: Any = None
    extra: dict = field(default_factory=dict, hash=False, compare=False)

    def __post_init__(self):
        if self.slo not in SLO_CLASSES:
            raise ValueError(
                f"unknown slo class {self.slo!r}; choose from {SLO_CLASSES}")

    @property
    def kind(self) -> str:
        return "buffered" if self.config.buffer_size > 0 else "sync"

    @property
    def codec(self) -> str:
        """This tenant's update-codec name ("none" when transport is raw).

        Per-tenant compression rides `config.update_codec` into the job's
        own FedAvgAPI, so one scheduler can interleave a codec-on tenant
        with codec-off ones — each tenant's admit/round programs (and their
        COMPILE/COMMS budget accounting) stay per-job, and a codec-on
        tenant served next to raw tenants trains byte-identical to the same
        job solo (the serving bit-reproducibility argument is per-job
        state, which the codec residual is part of)."""
        return self.config.update_codec or "none"

    @property
    def drive(self) -> str:
        """Which COMPILE_BUDGET.json drive this tenant's jit programs are
        accounted against (per-tenant compile-budget gate)."""
        return "buffered" if self.config.buffer_size > 0 else "eager"

    @property
    def rounds(self) -> int:
        return int(self.config.comm_round)

    def build_trainer(self):
        from fedml_tpu.models.lora import maybe_wrap_lora

        if self.trainer_factory is not None:
            # factory-built trainers get the same LoRA seam the stock path
            # has — a tenant descriptor with lora_rank > 0 federates
            # adapters no matter how its trainer was constructed
            return maybe_wrap_lora(self.trainer_factory(), self.config)
        from fedml_tpu.core.trainer import ClassificationTrainer
        from fedml_tpu.models.registry import create_model

        return maybe_wrap_lora(
            ClassificationTrainer(
                create_model(self.config.model,
                             output_dim=self.dataset.class_num)),
            self.config)

    def build_api(self) -> FedAvgAPI:
        """A fresh FedAvgAPI for this descriptor — the SAME construction a
        solo `train()` run uses, so served and solo runs share programs."""
        return FedAvgAPI(self.dataset, self.config, self.build_trainer(),
                         aggregator_name=self.aggregator_name)

    def build(self) -> "Job":
        return Job(self)


class Job:
    """One tenant's runtime: (queued ->) pending -> running -> committed,
    with evicted as a parkable detour and cancelled as the other terminal.

    `step(tracer)` executes exactly one dispatch round (buffered jobs also
    drain after their final round) and returns True once the job has
    consumed its whole round budget. The scheduler owns WHEN steps happen;
    the job owns WHAT a step does — and what it does is independent of the
    interleaving by construction (see module docstring).

    `build=False` defers `desc.build_api()` until `materialize()` — the
    admission-controlled scheduler admits hundreds of tenants without
    paying device state for any that never reach the mesh."""

    def __init__(self, desc: JobDescriptor, build: bool = True):
        self.desc = desc
        self.name = desc.name
        self.api: Optional[FedAvgAPI] = None
        self.runner: Optional[BufferedRunner] = None
        self.records: Optional[RoundRecordLog] = None
        self.round_idx = 0
        self.state = "queued"
        # eviction snapshot (host pytree, or an EvictionStore holding it)
        self._snapshot = None
        self._spill_store = None
        # scheduler bookkeeping (deficit-weighted fair share + bench timing)
        self.deficit = 0.0
        self.dispatched_ticks = 0
        self.submit_t: Optional[float] = None
        self.start_t: Optional[float] = None
        self.finish_t: Optional[float] = None
        self._submit_seq = 0  # scheduler-stamped submission index
        self.warm_start = False  # scheduler warm-pool signature hit
        # one-shot staged-cohort handoff from the scheduler's shared
        # prefetcher into the api's stage seam (sync path)
        self._staged_override = None
        if build:
            self.materialize()

    def materialize(self) -> None:
        """Build (or rebuild, on resume) the device-facing runtime: the
        FedAvgAPI, the buffered runner, and the stage-override seam.
        Idempotent while an api is live."""
        if self.api is not None:
            return
        self.api = self.desc.build_api()
        if self.desc.bank is not None:
            # the drive loops attach via train(bank=...); served jobs step
            # through train_one_round directly, so the seam is here
            self.api.bank = self.desc.bank
        if self.desc.kind == "buffered":
            # the guard rides into the runner so donation gating matches
            # the solo buffered drive (a guard snapshot holds the buffer's
            # arrays — donating them would deallocate the snapshot)
            self.runner = BufferedRunner(
                self.api, chaos=self.desc.chaos, guard=self.desc.guard,
                partial_dispatch=self.desc.partial_dispatch)
        self._orig_stage_fn = self.api.stage_fn
        self.api.stage_fn = self._stage_or_override
        if self.state == "queued":
            self.state = "pending"

    # ------------------------------------------------------------- plumbing
    @property
    def done(self) -> bool:
        return self.state == "committed"

    @property
    def closed(self) -> bool:
        """Terminal either way: committed or cancelled — the job will
        never be scheduled again."""
        return self.state in ("committed", "cancelled")

    @property
    def resident(self) -> bool:
        """Whether this job currently holds device state (a mesh slot)."""
        return self.api is not None

    @property
    def history(self):
        return self.api.history

    @property
    def prefetchable(self) -> bool:
        """Whether this job's cohorts can be staged ahead by round index:
        staging must be pure in round_idx, which partial dispatch is not
        (its width depends on in-flight capacity at dispatch time)."""
        return not (self.desc.kind == "buffered"
                    and self.desc.partial_dispatch)

    def _stage_or_override(self, round_idx, **kw):
        staged = self._staged_override
        if staged is not None and staged.round_idx == round_idx:
            self._staged_override = None
            return staged
        return self._orig_stage_fn(round_idx, **kw)

    def stage(self, round_idx: int):
        """Stage one cohort for this job — the shared prefetcher's staging
        callback (pure in round_idx; chaos faults derived per round)."""
        return self._orig_stage_fn(round_idx, chaos=self.desc.chaos)

    # ------------------------------------------------------ evict / resume
    def evict(self, tracer, reason: str = "preempted", store=None) -> bool:
        """Checkpointed preemption: fetch the job's full state surface to
        host, drop every device reference (the mesh slot is free), park
        the snapshot (optionally spilled into `store`, an EvictionStore).
        Only called at step boundaries, where the record log is flushed
        and no staged cohort is in flight. Returns False when there is
        nothing resident to evict."""
        if self.api is None or self.closed:
            return False
        if self.records is not None:
            self.records.flush(self.round_idx)
        if self.desc.bank is not None:
            # flush AFTER the record flush above scattered any pending
            # _bank blocks: the parked tenant's personal rows are on disk
            # before the slot frees, so resume gathers the exact bytes
            self.desc.bank.flush()
        buf = None
        host_snap = None
        in_flight = 0
        if self.runner is not None:
            if self.api._buffer is not None:
                buf = jax.device_get(self.api._buffer)
            # the pending dict holds the client-step programs' stacked
            # device results — device_get folds them (and nothing else;
            # host ints/lists pass through) into plain numpy
            host_snap = jax.device_get(self.runner.host.snapshot())
            in_flight = self.runner.in_flight
        guard = self.desc.guard
        snap = {
            "tree": jax.device_get(self.api._ckpt_tree()),
            "meta": self.api._ckpt_meta(),
            "buffer": buf,
            "host": host_snap,
            "in_flight": in_flight,
            "round_idx": self.round_idx,
            "state": self.state,
            "guard_losses": (list(guard._losses)
                             if guard is not None else None),
        }
        if store is not None:
            store.save(self.name, snap)
            self._snapshot = None
            self._spill_store = store
        else:
            self._snapshot = snap
            self._spill_store = None
        # free the mesh slot: every device reference goes
        self.api = None
        self.runner = None
        self.records = None
        self._staged_override = None
        self.state = "evicted"
        tracer.event("job_evicted", job=self.name, round=self.round_idx,
                     reason=reason)
        return True

    def resume(self, tracer) -> bool:
        """Rebuild the runtime from the descriptor and restore the parked
        snapshot. The rebuild re-traces the same programs a fresh build
        would — with the persistent compile cache enabled XLA serves them
        warm (cache_hits > 0, no new compiles: tests/test_serving.py) —
        and the restored bytes make the resumed run a bitwise continuation
        of the evicted one."""
        if self.state != "evicted":
            return False
        snap = (self._spill_store.load(self.name)
                if self._spill_store is not None else self._snapshot)
        self._snapshot = None
        self._spill_store = None
        self.materialize()
        api = self.api
        api._ckpt_load(snap["tree"], snap["meta"])
        if self.runner is not None:
            if snap["buffer"] is not None:
                api._buffer = jax.device_put(snap["buffer"])
            self.runner.host.restore(snap["host"])
            self.runner.in_flight = snap["in_flight"]
        guard = self.desc.guard
        if guard is not None and snap["guard_losses"] is not None:
            guard._losses.clear()
            guard._losses.extend(snap["guard_losses"])
        self.round_idx = snap["round_idx"]
        self.state = snap["state"]
        if self.state == "running":
            # _ckpt_load restored the history INTO api.history in place;
            # the fresh record log binds to that same list
            self.records = RoundRecordLog(tracer, api.history, None,
                                          bank=self.desc.bank)
        tracer.event("job_resumed", job=self.name, round=self.round_idx)
        return True

    def cancel(self) -> None:
        """Terminal removal (admission shed / caller cancel): device refs
        and any parked snapshot are dropped; the job never runs again."""
        self.api = None
        self.runner = None
        self.records = None
        self._snapshot = None
        self._spill_store = None
        self._staged_override = None
        self.state = "cancelled"

    # ----------------------------------------------------------------- step
    def step(self, tracer, staged=None) -> bool:
        """One schedulable unit of this job. `staged` (optional) is a
        prefetched cohort for `self.round_idx`. Returns True when the job
        just finished (drain included)."""
        if self.closed:
            return True
        if self.api is None:
            self.materialize()
        if self.state == "pending":
            self.state = "running"
            self.records = RoundRecordLog(tracer, self.api.history, None,
                                          bank=self.desc.bank)
        if self.desc.kind == "sync":
            self._step_sync(tracer, staged)
        else:
            self._step_buffered(tracer, staged)
        if self.round_idx >= self.desc.rounds:
            self.state = "committed"
        return self.done

    def _step_sync(self, tracer, staged) -> None:
        """One sync round — guard retry attempts included, mirroring
        `FedAvgAPI._eager_round` exactly (snapshot refs, salted rng,
        verdict/rollback/exhausted events), so a guard-armed served tenant
        stays byte-identical to its solo run."""
        cfg = self.api.cfg
        guard = self.desc.guard
        r = self.round_idx
        retries = 0
        while True:
            rejected = False
            with tracer.round(r) as rspan:
                faults = None
                if self.desc.chaos is not None and staged is None:
                    n_cohort = min(cfg.client_num_per_round,
                                   self.api.dataset.client_num)
                    faults = self.desc.chaos.events(r, n_cohort)
                snapshot = None
                if guard is not None:
                    # jax pytrees are immutable: the refs ARE the snapshot
                    snapshot = (self.api._ckpt_tree(), self.api._ckpt_meta())
                self._staged_override = staged
                train_metrics = self.api.train_one_round(r, faults=faults,
                                                         rng_salt=retries,
                                                         tracer=tracer)
                with tracer.span("device_wait", r):
                    jax.block_until_ready(self.api.global_variables)
                if guard is not None:
                    total = max(train_metrics.get("total", 1.0), 1.0)
                    loss = train_metrics.get("loss_sum", 0.0) / total
                    with tracer.span("guard_verdict", r):
                        verdict = guard.inspect(r, loss,
                                                self.api.global_variables)
                    tracer.event("guard_verdict", round=r, ok=verdict.ok,
                                 reason=verdict.reason)
                    if not verdict.ok and retries < guard.max_retries:
                        retries += 1
                        tracer.event("guard_rollback", round=r,
                                     retry=retries)
                        self.api._ckpt_load(*snapshot)
                        rejected = True  # new attempt, new round span
                if not rejected:
                    record = {"round": r, "round_time": rspan.elapsed()}
                    staged_used, stats = self.api._last_dispatch
                    block = FedAvgAPI._ledger_block(r, staged_used, stats)
                    if block is not None:
                        record["_ledger"] = [block]
                    bank_block = self.api._bank_block(r)
                    if bank_block is not None:
                        record["_bank"] = [bank_block]
                    if staged_used.faults is not None:
                        record.update(chaos_summary(staged_used.faults))
                        for k in ("participated_count", "quarantined_count"):
                            if k in train_metrics:
                                record[k] = train_metrics[k]
                    if guard is not None and retries:
                        record["guard_retries"] = retries
                    if (r % cfg.frequency_of_the_test == 0
                            or r == cfg.comm_round - 1):
                        record.update(self.api.evaluate(r, tracer))
                    self.records.add(record)
                    self.records.flush(r)
            if not rejected:
                break
            staged = None  # restage the retry (attempt buffers were donated)
        self.round_idx += 1

    def _step_buffered(self, tracer, staged) -> None:
        """One buffered dispatch round — guard retry attempts included,
        mirroring `train_buffered` (runner.snapshot/restore over globals +
        buffer + arrival schedule, salted rng, restage on retry)."""
        cfg = self.api.cfg
        runner = self.runner
        host = runner.host
        guard = self.desc.guard
        r = self.round_idx
        retries = 0
        while True:
            rejected = False
            with tracer.round(r) as rspan:
                if staged is None:
                    staged = self._stage_buffered(r, tracer)
                snapshot = runner.snapshot() if guard is not None else None
                rng_round = runner.base_rng(r, retries)
                out = runner.step(r, staged, rng_round, tracer)
                train_metrics: dict = {}
                if out["commit_metrics"]:
                    with tracer.span("metrics_fetch", r):
                        for m in jax.device_get(out["commit_metrics"]):
                            for key in m:
                                train_metrics[key] = (
                                    train_metrics.get(key, 0.0)
                                    + float(m[key]))
                if guard is not None and out["commit_metrics"]:
                    total = max(train_metrics.get("total", 1.0), 1.0)
                    loss = train_metrics.get("loss_sum", 0.0) / total
                    with tracer.span("guard_verdict", r):
                        verdict = guard.inspect(r, loss,
                                                self.api.global_variables)
                    tracer.event("guard_verdict", round=r, ok=verdict.ok,
                                 reason=verdict.reason)
                    if not verdict.ok and retries < guard.max_retries:
                        retries += 1
                        tracer.event("guard_rollback", round=r,
                                     retry=retries)
                        runner.restore(snapshot)
                        rejected = True
                if not rejected:
                    record = {"round": r, "round_time": rspan.elapsed(),
                              "buffer_commits": out["n_commits"],
                              "committed_updates": host.committed_updates,
                              "buffer_fill": host.fill,
                              "_ledger": out["ledger_blocks"]}
                    for key in ("loss_sum", "total", "participated_count",
                                "quarantined_count", "staleness_sum",
                                "staleness_max"):
                        if key in train_metrics:
                            record[key] = train_metrics[key]
                    if staged is not None and staged.faults is not None:
                        record.update(chaos_summary(staged.faults))
                    if guard is not None and retries:
                        record["guard_retries"] = retries
                    if (r % cfg.frequency_of_the_test == 0
                            or r == cfg.comm_round - 1):
                        record.update(self.api.evaluate(r, tracer))
                    self.records.add(record)
                    self.records.flush(r)
            if not rejected:
                break
            staged = None  # restage the retry against the restored timeline
        self.round_idx += 1
        if self.round_idx >= cfg.comm_round:
            self._drain_buffered(tracer)

    def _stage_buffered(self, round_idx: int, tracer):
        """Stage this dispatch round's cohort — the full seeded sample in
        classic mode, the freed-capacity prefix (padded to static width)
        in partial mode, or None when there is no capacity at all (the
        dispatch program is skipped; the round only processes arrivals)."""
        cfg = self.api.cfg
        cohort = min(cfg.client_num_per_round, self.api.dataset.client_num)
        width = self.runner.capacity(cohort)
        if width <= 0:
            return None
        if width >= cohort:
            return self.api.stage_fn(round_idx, chaos=self.desc.chaos,
                                     tracer=tracer)
        return self.api.stage_partial_cohort(round_idx, width, cohort,
                                             chaos=self.desc.chaos,
                                             tracer=tracer)

    def _drain_buffered(self, tracer) -> None:
        out = self.runner.drain(tracer)
        if not out["n_commits"]:
            return
        host = self.runner.host
        cfg = self.api.cfg
        record = {"round": cfg.comm_round, "round_time": 0.0,
                  "buffer_commits": out["n_commits"],
                  "committed_updates": host.committed_updates,
                  "buffer_fill": host.fill,
                  "_ledger": out["ledger_blocks"]}
        with tracer.span("metrics_fetch", out["drain_round"]):
            for m in jax.device_get(out["commit_metrics"]):
                for key in m:
                    record[key] = record.get(key, 0.0) + float(m[key])
        self.records.add(record)
        self.records.flush(cfg.comm_round)

    def final_params(self):
        """Host copy of the final global variables (bitwise-comparable)."""
        return jax.device_get(self.api.global_variables)

    def __repr__(self) -> str:  # pragma: no cover - debug nicety
        return (f"Job({self.name!r}, kind={self.desc.kind}, "
                f"round={self.round_idx}/{self.desc.rounds}, "
                f"state={self.state})")


def params_equal(a, b) -> bool:
    """Bitwise equality over two fetched variable pytrees."""
    la = jax.tree.leaves(a)
    lb = jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))
