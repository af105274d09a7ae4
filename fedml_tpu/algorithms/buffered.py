"""Staleness-aware buffered asynchronous aggregation (FedBuff) — the drive
loop that removes the global round barrier.

Every synchronous drive loop commits on a round barrier: one straggler stalls
the whole cohort (ROADMAP item 3). Here client updates are *admitted* into a
device-resident K-row buffer the moment they arrive, tagged with their birth
round, and *committed* into globals (and FedOpt momenta) only when K updates
have accumulated — commits are decoupled from dispatch rounds, so a slow
client delays nobody; its update lands late and staleness-discounted
(`weight * (1 + staleness) ** -alpha` by default — pluggable via
`aggregators.make_staleness_discount`) instead of being dropped.

Determinism is the same bar PR 4/5 set, without an execution barrier: the
arrival schedule is a pure function of the seed. At dispatch round t the
whole cohort's updates are computed against the globals *as of dispatch*
(one jitted `client_step` program — vmap(local_update), no aggregation);
each client's arrival round is t + latency, with latency drawn from the
seeded straggler plan (`robustness.chaos.FaultPlan.latencies`). Arrivals are
processed in deterministic (arrival_round, birth_round, slot) order, so the
sequence of admit/commit programs — and therefore the final model — is
bitwise reproducible run-to-run. The degenerate config (buffer_size =
cohort, alpha = 0, no stragglers) admits each round's cohort in slot order
and commits exactly once per round with zero staleness, reproducing the
synchronous round's aggregation bit-exactly (tests/test_buffered.py).

Guard integration: the pre-round snapshot covers globals, aggregator state,
the update buffer, its birth tags, AND the host-side pending-arrival
schedule, so a rollback rewinds the whole async timeline; the retried round
re-runs with a salted rng, exactly like the synchronous loops. The buffer is
donated into the admit program only when no guard is armed — a guard
snapshot holds the buffer's arrays, and donation would deallocate them (the
same donate-when-restageable rule the pipelined loop applies to cohorts).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu import telemetry
from fedml_tpu.algorithms.aggregators import (
    build_buffer_admit,
    build_buffer_commit,
    make_staleness_discount,
)
from fedml_tpu.algorithms.engine import _vmapped_update
from fedml_tpu.data.prefetch import CohortPrefetcher
from fedml_tpu.robustness.chaos import summarize as chaos_summary
from fedml_tpu.telemetry.records import RoundRecordLog

log = logging.getLogger(__name__)


def build_client_step_fn(trainer, cfg, donate_data: bool = False,
                         collect_stats: bool = False):
    """Jitted cohort step WITHOUT aggregation: vmap(local_update) over the
    staged cohort, same per-client rng stream as the synchronous round
    (crngs = split(round_rng, C)) — so a buffered run and a synchronous run
    at the same round rng train bit-identical client updates. The stacked
    LocalResult stays device-resident until every row has been admitted.

    `collect_stats=True` returns `(result, cohort_stats_rows)` from the same
    program — the buffered drive's feed into the client ledger (admit/commit
    programs stay byte-identical; stats are dispatch-time observations)."""
    batched = _vmapped_update(trainer, cfg)

    def client_step(global_variables, x, y, counts, rng):
        crngs = jax.random.split(rng, x.shape[0])
        result = batched(global_variables, x, y, counts, crngs)
        if collect_stats:
            from fedml_tpu.algorithms.engine import cohort_stats

            return result, cohort_stats(global_variables, result)
        return result

    from fedml_tpu.core.builder import donating_jit

    # x/y are staged fresh per round (and re-staged on a guard retry), so
    # their HBM may be reused in place; counts survives — the admit program
    # reads it long after the step
    return donating_jit(client_step, (1, 2) if donate_data else ())


def init_buffer(result, k: int) -> Dict[str, Any]:
    """Fresh all-zero K-row update buffer shaped after one stacked
    LocalResult (row shapes = the per-client shapes)."""
    def row(l):
        return jnp.zeros((k,) + l.shape[1:], l.dtype)

    return {
        "vars": jax.tree.map(row, result.variables),
        "steps": jnp.zeros((k,), result.num_steps.dtype),
        "weights": jnp.zeros((k,), jnp.float32),
        "metrics": {name: row(v) for name, v in result.metrics.items()},
        "birth": jnp.zeros((k,), jnp.int32),
        "fill": jnp.zeros((), jnp.int32),
    }


class _HostState:
    """The host-side mirror of the async schedule — everything the guard
    snapshot must capture beyond the device pytrees."""

    def __init__(self):
        # birth -> {"vars","steps","metrics","counts","remaining"}: stacked
        # client-step results held until every arriving row is admitted
        self.pending: Dict[int, Dict[str, Any]] = {}
        # arrival_round -> [(birth, slot), ...]
        self.arrivals: Dict[int, List[Tuple[int, int]]] = {}
        self.fill = 0            # mirrors buf["fill"] (admits are host-driven)
        self.births: List[int] = []  # birth tag of each filled buffer row
        # global client id of each filled row (ledger staleness attribution)
        self.row_clients: List[int] = []
        self.commits = 0
        self.committed_updates = 0

    def snapshot(self):
        return (
            {b: dict(d) for b, d in self.pending.items()},
            {r: list(v) for r, v in self.arrivals.items()},
            self.fill, list(self.births), self.commits,
            self.committed_updates, list(self.row_clients),
        )

    def restore(self, snap):
        (pending, arrivals, fill, births, commits, committed,
         row_clients) = snap
        self.pending = {b: dict(d) for b, d in pending.items()}
        self.arrivals = {r: list(v) for r, v in arrivals.items()}
        self.fill = fill
        self.births = list(births)
        self.commits = commits
        self.committed_updates = committed
        self.row_clients = list(row_clients)


class BufferedRunner:
    """One buffered tenant's admit/commit machinery as a schedulable unit.

    Owns the device buffer, the host-side arrival schedule (`_HostState`),
    and the three jitted programs (client_step / admit / commit), exposing
    ONE dispatch round as `step()` plus the end-of-drive `drain()` — so the
    classic `train_buffered` loop below and the multi-tenant serving
    scheduler (`fedml_tpu.serving`) drive the SAME code path and a tenant's
    admit/commit sequence is bit-identical to running its job solo.

    `partial_dispatch=True` (the FedBuff follow-up PR 9 deferred): instead
    of re-running the full cohort every dispatch round, only as many
    replacement clients are dispatched as arrivals have freed capacity
    (`capacity() = cohort - in_flight`) — the caller stages that prefix of
    the round's seeded sample, padded back to the cohort's static width
    (`FedAvgAPI.stage_partial_cohort`) so the client_step signature — and
    therefore the compile budget — never changes. A zero-capacity round
    passes `staged=None` to `step()`, which skips the dispatch program
    entirely and only processes arrivals. With no stragglers, capacity is
    always the full cohort and partial mode degenerates bit-exactly into
    full dispatch."""

    def __init__(self, api, chaos=None, guard=None, discount_fn=None,
                 partial_dispatch: bool = False):
        cfg = api.cfg
        k = int(cfg.buffer_size)
        if k < 1:
            raise ValueError(
                f"buffer_size must be >= 1 in buffered mode, got {k}")
        if discount_fn is None:
            discount_fn = make_staleness_discount(cfg.staleness_alpha)
        self.api = api
        self.cfg = cfg
        self.k = k
        self.chaos = chaos
        self.partial_dispatch = bool(partial_dispatch)
        # a guard snapshot holds the buffer's arrays — donation would
        # deallocate them (the donate-when-restageable rule)
        self.codec = getattr(api, "codec", None)
        self.admit_fn = build_buffer_admit(donate_buffer=guard is None,
                                           codec=self.codec)
        self.commit_fn = build_buffer_commit(api.aggregator, discount_fn)
        # stats are always collected (the traced program must not depend on
        # whether a ledger happens to be attached — ledger on/off
        # bit-identity); the admit/commit programs are untouched
        self.client_step = build_client_step_fn(
            api.trainer, cfg, donate_data=True, collect_stats=True)
        self.host = _HostState()
        # dispatched-but-unadmitted updates: partial mode's capacity counter
        # (full mode maintains it too — it is pure bookkeeping there)
        self.in_flight = 0
        api._buffer = None  # device buffer; exposed for tests/introspection
        api._buffer_host = self.host

    def base_rng(self, round_idx: int, salt: int = 0):
        rng = jax.random.fold_in(jax.random.PRNGKey(self.cfg.seed), round_idx)
        if salt:
            rng = jax.random.fold_in(rng, salt)
        return rng

    def capacity(self, cohort: int) -> int:
        """How many replacement clients the next dispatch round may stage:
        the full cohort in classic mode, `cohort - in_flight` in partial
        mode (never negative)."""
        if not self.partial_dispatch:
            return cohort
        return max(0, cohort - self.in_flight)

    # -- guard snapshot/rollback: jax pytrees are immutable, so holding refs
    # IS the device snapshot; the host schedule needs explicit copies
    def snapshot(self):
        return (self.api._ckpt_tree(), self.api._ckpt_meta(),
                self.api._buffer, self.host.snapshot(), self.in_flight)

    def restore(self, snap) -> None:
        tree, meta, buf, host_snap, in_flight = snap
        self.api._ckpt_load(tree, meta)
        self.api._buffer = buf
        self.host.restore(host_snap)
        self.in_flight = in_flight

    def _do_commit(self, commit_round: int, rng_round, seq: int,
                   commit_metrics, ledger_blocks, tracer) -> None:
        """One buffer commit; appends the commit's device metric dict."""
        api, host = self.api, self.host
        rng = rng_round if seq == 0 else jax.random.fold_in(rng_round, seq)
        with tracer.span("commit", commit_round):
            api.global_variables, api.agg_state, m = self.commit_fn(
                api.global_variables, api.agg_state, api._buffer,
                np.int32(commit_round), rng)
        staleness = [commit_round - b for b in host.births]
        p50 = float(np.median(staleness)) if staleness else 0.0
        smax = max(staleness) if staleness else 0
        tracer.event("buffer_committed", round=commit_round, size=host.fill,
                     staleness_p50=p50, staleness_max=int(smax))
        telemetry.gauge("staleness", round=commit_round, p50=p50,
                        max=int(smax))
        # per-client staleness attribution for the ledger (host-derived —
        # the commit program is unchanged); rides the record's _ledger key
        ledger_blocks.append({
            "round": commit_round,
            "client_idx": np.asarray(host.row_clients, np.int64),
            "staleness": np.asarray(staleness, np.int32)})
        host.committed_updates += host.fill
        host.commits += 1
        host.fill = 0
        host.births = []
        host.row_clients = []
        # the commit only read the buffer — reset the fill scalar host-side
        api._buffer = dict(api._buffer, fill=jnp.zeros((), jnp.int32))
        commit_metrics.append(m)

    def process_arrivals(self, now: int, rng_round, commit_metrics,
                         ledger_blocks, seq_base: int, tracer) -> int:
        """Admit round `now`'s due arrivals in (birth, slot) order; commit
        every time the buffer fills. Returns the number of commits made."""
        api, host = self.api, self.host
        due = sorted(host.arrivals.pop(now, []))
        n_commits = 0
        for birth, slot in due:
            src = host.pending[birth]
            with tracer.span("admit", now):
                args = (api._buffer, src["vars"], src["steps"],
                        src["metrics"], src["counts"], np.int32(slot),
                        np.int32(birth))
                if self.codec is not None:
                    # codec-on admit decodes the row's delta against the
                    # CURRENT globals — the same reference the commit's
                    # aggregation applies it to. Base-stripped: buffer rows
                    # are adapters-only under LoRA (engine strips inside
                    # the vmap) and the delta reference must match them.
                    from fedml_tpu.models.lora import strip_lora_base

                    args = args + (strip_lora_base(api.global_variables),)
                api._buffer = self.admit_fn(*args)
            host.fill += 1
            self.in_flight -= 1
            host.births.append(birth)
            # host numpy row (pending stores client_idx as np.asarray at
            # dispatch), so this index is a host read, not a device fetch
            host.row_clients.append(src["client_idx"][slot])
            tracer.event("update_admitted", round=now, birth=birth,
                         fill=host.fill)
            src["remaining"] -= 1
            if src["remaining"] == 0:
                del host.pending[birth]
            if host.fill == self.k:
                self._do_commit(now, rng_round, seq_base + n_commits,
                                commit_metrics, ledger_blocks, tracer)
                n_commits += 1
        return n_commits

    def step(self, round_idx: int, staged, rng_round, tracer) -> dict:
        """One dispatch round: run the client-step program over `staged`
        (skipped when None — a zero-capacity partial round), schedule each
        surviving client's arrival at round + latency (seeded straggler
        plan; 0 without chaos), then admit/commit round `round_idx`'s due
        arrivals. Returns {ledger_blocks, commit_metrics, n_commits}."""
        api, host = self.api, self.host
        ledger_blocks: list = []
        if staged is not None:
            with tracer.span("dispatch", round_idx):
                result, stats = self.client_step(
                    api.global_variables, staged.x, staged.y,
                    staged.counts, rng_round)
            if api._buffer is None:
                api._buffer = init_buffer(result, self.k)
            n = len(staged.client_idx)
            lat = (self.chaos.latencies(round_idx, n)
                   if self.chaos is not None
                   else np.zeros(n, np.int32)).tolist()
            surviving = [c for c in range(n)
                         if staged.faults is None
                         or bool(staged.faults.participation[c])]
            for c in surviving:
                host.arrivals.setdefault(
                    round_idx + lat[c], []).append((round_idx, c))
            self.in_flight += len(surviving)
            if surviving:
                host.pending[round_idx] = {
                    "vars": result.variables,
                    "steps": result.num_steps,
                    "metrics": result.metrics,
                    "counts": staged.counts,
                    # slot -> global client id, read back at admit time
                    # for the ledger's staleness attribution
                    "client_idx": np.asarray(staged.client_idx),
                    "remaining": len(surviving),
                }
            participated = (
                np.asarray(staged.faults.participation, bool)
                if staged.faults is not None else np.ones(n, bool))
            ledger_blocks.append({
                "round": round_idx,
                "client_idx": np.asarray(staged.client_idx),
                "participated": participated,
                "stats": stats})
        commit_metrics: list = []
        n_commits = self.process_arrivals(round_idx, rng_round,
                                          commit_metrics, ledger_blocks,
                                          0, tracer)
        telemetry.gauge("buffer_fill", round=round_idx,
                        fill=host.fill, commits=n_commits)
        return {"ledger_blocks": ledger_blocks,
                "commit_metrics": commit_metrics,
                "n_commits": n_commits}

    def drain(self, tracer) -> dict:
        """Outstanding straggler arrivals land on virtual rounds past the
        last dispatch, then the final partial buffer flushes through the
        masked commit path (participation = arange(K) < fill). No new
        client work runs here, so the schedule stays a pure function of
        the seed. Returns {ledger_blocks, commit_metrics, n_commits}."""
        host = self.host
        drain_round = self.cfg.comm_round
        commit_metrics: list = []
        ledger_blocks: list = []
        n_commits = 0
        while host.arrivals:
            rng_round = self.base_rng(drain_round, 0)
            n_commits += self.process_arrivals(drain_round, rng_round,
                                               commit_metrics, ledger_blocks,
                                               0, tracer)
            drain_round += 1
        if host.fill > 0:
            self._do_commit(drain_round, self.base_rng(drain_round, 0), 0,
                            commit_metrics, ledger_blocks, tracer)
            n_commits += 1
        return {"ledger_blocks": ledger_blocks,
                "commit_metrics": commit_metrics,
                "n_commits": n_commits,
                "drain_round": drain_round}


def train_buffered(api, start_round: int, ckpt_dir, ckpt_every,
                   metrics_logger, chaos, guard, tracer,
                   discount_fn=None, ledger=None) -> None:
    """The buffered drive loop (`cfg.buffer_size > 0`), called from
    FedAvgAPI.train() under its tracer/checkpoint scaffolding.

    Per dispatch round t: stage the cohort (through the SAME `stage_fn` seam
    as the synchronous loops — with `cfg.pipeline_depth > 0` a background
    prefetcher stages rounds t+1..t+depth while t executes), then hand the
    round to the `BufferedRunner`: run the client-step program against the
    current globals, schedule each surviving client's arrival at
    t + latency, admit every update whose arrival round is t, and commit
    whenever the buffer reaches K. After the last dispatch round the
    runner's `drain()` lands the outstanding arrivals on virtual rounds and
    flushes the final partial buffer."""
    cfg = api.cfg
    runner = BufferedRunner(api, chaos=chaos, guard=guard,
                            discount_fn=discount_fn)
    host = runner.host
    records = RoundRecordLog(tracer, api.history, metrics_logger,
                             ledger=ledger)
    prefetcher = None
    if cfg.pipeline_depth > 0:
        prefetcher = CohortPrefetcher(
            lambda r: api.stage_fn(r, chaos=chaos), depth=cfg.pipeline_depth)
        api._last_prefetcher = prefetcher  # test/ops introspection

    round_idx = start_round
    retries = 0
    try:
        while round_idx < cfg.comm_round:
            with tracer.round(round_idx) as rspan:
                with tracer.span("stage_wait", round_idx):
                    staged = (prefetcher.get(round_idx) if prefetcher
                              else api.stage_fn(round_idx, chaos=chaos,
                                                tracer=tracer))
                assert staged.round_idx == round_idx
                if prefetcher:
                    for ahead in range(1, cfg.pipeline_depth + 1):
                        if round_idx + ahead < cfg.comm_round:
                            prefetcher.prefetch(round_idx + ahead)
                snapshot = None
                if guard is not None:
                    snapshot = runner.snapshot()
                rng_round = runner.base_rng(round_idx, retries)
                out = runner.step(round_idx, staged, rng_round, tracer)
                ledger_blocks = out["ledger_blocks"]
                commit_metrics = out["commit_metrics"]
                n_commits = out["n_commits"]
                train_metrics: dict = {}
                if commit_metrics:
                    with tracer.span("metrics_fetch", round_idx):
                        for m in jax.device_get(commit_metrics):
                            for key in m:
                                train_metrics[key] = (
                                    train_metrics.get(key, 0.0)
                                    + float(m[key]))
                if guard is not None and commit_metrics:
                    total = max(train_metrics.get("total", 1.0), 1.0)
                    loss = train_metrics.get("loss_sum", 0.0) / total
                    with tracer.span("guard_verdict", round_idx):
                        verdict = guard.inspect(round_idx, loss,
                                                api.global_variables)
                    tracer.event("guard_verdict", round=round_idx,
                                 ok=verdict.ok, reason=verdict.reason)
                    if not verdict.ok and retries < guard.max_retries:
                        retries += 1
                        log.warning(
                            "guard: %s — rolled back (buffer + schedule), "
                            "retrying with fresh rng (%d/%d)",
                            verdict.reason, retries, guard.max_retries)
                        tracer.event("guard_rollback", round=round_idx,
                                     retry=retries)
                        runner.restore(snapshot)
                        if prefetcher:
                            prefetcher.invalidate()
                        continue
                    if not verdict.ok:
                        log.warning("guard: %s — retries exhausted, "
                                    "accepting the round", verdict.reason)
                record = {"round": round_idx, "round_time": rspan.elapsed(),
                          "buffer_commits": n_commits,
                          "committed_updates": host.committed_updates,
                          "buffer_fill": host.fill,
                          "_ledger": ledger_blocks}
                for key in ("loss_sum", "total", "participated_count",
                            "quarantined_count", "staleness_sum",
                            "staleness_max"):
                    if key in train_metrics:
                        record[key] = train_metrics[key]
                if staged.faults is not None:
                    record.update(chaos_summary(staged.faults))
                if guard is not None and retries:
                    record["guard_retries"] = retries
                retries = 0
                if (round_idx % cfg.frequency_of_the_test == 0
                        or round_idx == cfg.comm_round - 1):
                    record.update(api.evaluate(round_idx, tracer))
                records.add(record)
                records.flush(round_idx)
                if ckpt_dir and (round_idx + 1) % ckpt_every == 0:
                    with tracer.span("checkpoint", round_idx):
                        api.save_checkpoint(ckpt_dir, round_idx + 1)
            round_idx += 1
    finally:
        if prefetcher:
            prefetcher.close()

    # -- drain: the runner lands the outstanding straggler arrivals on
    # virtual rounds and flushes the final partial buffer (see
    # BufferedRunner.drain)
    out = runner.drain(tracer)
    if out["n_commits"]:
        record = {"round": cfg.comm_round, "round_time": 0.0,
                  "buffer_commits": out["n_commits"],
                  "committed_updates": host.committed_updates,
                  "buffer_fill": host.fill,
                  "_ledger": out["ledger_blocks"]}
        with tracer.span("metrics_fetch", out["drain_round"]):
            for m in jax.device_get(out["commit_metrics"]):
                for key in m:
                    record[key] = record.get(key, 0.0) + float(m[key])
        records.add(record)
        records.flush(cfg.comm_round)
