"""FedAvg simulator API — reference-parity surface, TPU-native internals.

Mirrors reference fedml_api/standalone/fedavg/fedavg_api.py:13-215 (`train`,
`_client_sampling`, `_aggregate`, `_local_test_on_all_clients`) and subsumes
the distributed path (reference FedAvgAPI.py:20): what the reference does with
1 server + N MPI workers is here one jitted round over vectorized clients —
the device mesh (fedml_tpu.parallel) is the "cluster".
"""

from __future__ import annotations

import copy
import logging
import os
from collections import deque
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu import telemetry
from fedml_tpu.algorithms.aggregators import make_aggregator
from fedml_tpu.algorithms.engine import (
    build_client_eval_fn,
    build_eval_fn,
    build_federation_eval_fn,
    build_round_fn,
    packed_lanes,
    round_slots,
    round_work,
    stage_to_device,
)
from fedml_tpu.core.config import FedConfig
from fedml_tpu.data.packed_store import MmapPackedStore, materialize
from fedml_tpu.data.packing import pack_eval_batches, pad_clients
from fedml_tpu.data.prefetch import CohortPrefetcher, StagedCohort
from fedml_tpu.data.registry import FederatedDataset
from fedml_tpu.robustness.chaos import apply_faults, summarize as chaos_summary
from fedml_tpu.telemetry.records import RoundRecordLog, _scalar  # noqa: F401
from fedml_tpu.utils.checkpoint import Checkpointable

log = logging.getLogger(__name__)


def client_sampling(round_idx: int, client_num_in_total: int, client_num_per_round: int) -> np.ndarray:
    """Seeded per-round sampling, identical semantics to reference
    FedAVGAggregator.client_sampling (FedAVGAggregator.py:89-97):
    np.random.seed(round_idx) then choice without replacement."""
    if client_num_in_total == client_num_per_round:
        return np.arange(client_num_in_total)
    num = min(client_num_per_round, client_num_in_total)
    rng = np.random.RandomState(round_idx)  # fixed seed per round for reproducibility
    return rng.choice(client_num_in_total, num, replace=False)


def fast_client_sampling(round_idx: int, client_num_in_total: int,
                         client_num_per_round: int) -> np.ndarray:
    """O(cohort) uniform sampling without replacement: the first `num`
    values of a seeded Feistel permutation of [0, N).

    `rng.choice(N, num, replace=False)` above materialises and shuffles all
    N ids — O(N) per round, the measured 1M-client bottleneck
    (BENCH_SCALE_r01.json: 9.9 rounds/s vs 334.6 at 10k). A balanced
    4-round Feistel network over the enclosing power-of-four domain is a
    keyed bijection, so walking ids 0..num-1 through it (cycle-walking
    values that land >= N back through the network, expected < 2 passes)
    yields distinct in-range ids in O(num) work and memory. Keys derive
    from RandomState(round_idx), so sampling stays a pure function of the
    round index — but the permutation differs from `client_sampling`'s
    shuffle, so this path is OPT-IN (--fast_sampling) to preserve seeded
    trajectories by default.
    """
    n = int(client_num_in_total)
    if n == client_num_per_round:
        return np.arange(n)
    num = min(client_num_per_round, n)
    half_bits = max(1, (max(n - 1, 1).bit_length() + 1) // 2)
    mask = np.uint64((1 << half_bits) - 1)
    keys = np.random.RandomState(round_idx).randint(
        0, 2 ** 63, size=4, dtype=np.int64).astype(np.uint64)

    def permute(v: np.ndarray) -> np.ndarray:
        left = (v >> np.uint64(half_bits)) & mask
        right = v & mask
        for k in keys:  # splitmix64-style round function, truncated to a half
            mixed = right * np.uint64(0x9E3779B97F4A7C15) + k
            mixed ^= mixed >> np.uint64(29)
            mixed = mixed * np.uint64(0xBF58476D1CE4E5B9)
            mixed ^= mixed >> np.uint64(32)
            left, right = right, left ^ (mixed & mask)
        return (left << np.uint64(half_bits)) | right

    vals = permute(np.arange(num, dtype=np.uint64))
    oob = vals >= n
    while oob.any():
        vals = np.where(oob, permute(vals), vals)
        oob = vals >= n
    return vals.astype(np.int64)


#: samples one vmapped eval step may hold at once. An eval step forwards
#: chunk x n_max samples as ONE batch, and its conv activations scale with
#: that: 64 clients x 480 samples of CNN_DropOut (the 3400-writer FEMNIST
#: split) is 8.7 GB of conv outputs on a 16 GB chip, 4096 samples ~1.2 GB.
EVAL_STEP_SAMPLES = 4096
#: tokens a federation-eval step may run at once over the clients it vmaps,
#: a sequence a client: 20 silos of 1,024-token rows stay one step; at 4,096
#: tokens 20 lanes of Kimi Linear's forward pass asked the TPU compiler for
#: 11.7 GB beside a 4.6 GB base, 8 lanes fit
EVAL_STEP_TOKENS = 32768


def _eval_chunk(x, num_clients: int) -> int:
    """Clients per eval step for a split packed as `x` [clients, n_max, ...]:
    at most 64, and no more than keeps the step under EVAL_STEP_SAMPLES
    samples. A sample that is a sequence of token ids (integers, [T]) counts
    its tokens too: no more clients than keep one sequence a client under
    EVAL_STEP_TOKENS. The resident and the streaming eval share it, so they
    walk identical chunk geometry."""
    n_max = x.shape[1]
    chunk = max(1, min(num_clients, 64, EVAL_STEP_SAMPLES // max(n_max, 1)))
    if len(x.shape) == 3 and np.issubdtype(x.dtype, np.integer):
        chunk = max(1, min(chunk, EVAL_STEP_TOKENS // x.shape[2]))
    return chunk


def _experts_held(trainer):
    """(first, count) of the router's experts the trainer's model holds, where
    its `describe()` says it holds a share of them (the `moe_load` event's
    `held*`); else None."""
    module = getattr(trainer, "module", None)
    said = module.describe() if hasattr(module, "describe") else {}
    if said.get("experts_held") == said.get("experts_routed"):
        return None
    return said["experts_first"], said["experts_held"]


def _moe_record(train_metrics) -> dict:
    """A routed-expert model's vectors among a round's metrics (`moe_load`,
    and a share's `moe_path`) under the record's reserved keys: they leave
    in the `moe_load` event (`telemetry/records.py`); history takes scalars."""
    return {"_" + k: train_metrics[k] for k in ("moe_load", "moe_path")
            if k in train_metrics}


def _host_metrics(train_metrics) -> dict:
    """A round's metric sums fetched in ONE host round trip: scalars as
    floats; a vector (a routed-expert model's `moe_load`) stays an array."""
    return {k: float(v) if np.ndim(v) == 0 else v
            for k, v in jax.device_get(train_metrics).items()}


class FedAvgAPI(Checkpointable):
    """Single-controller federated simulator.

    `aggregator_name` swaps the server rule (fedavg/fedopt/robust/fednova)
    while the client path stays identical — the reference achieves the same
    reuse by subclassing FedAVGAggregator.
    """

    def __init__(
        self,
        dataset: FederatedDataset,
        config: FedConfig,
        model_trainer,
        aggregator_name: str = "fedavg",
    ):
        self.dataset = dataset
        self.cfg = config
        self.trainer = model_trainer
        self.aggregator = make_aggregator(aggregator_name, config)
        self.mesh = None
        self._tensor_sharding = None
        from fedml_tpu.codecs import make_codec

        # the compressed-update-transport seam (graft-codec): None keeps
        # every code path EXACTLY as before — codec-off rounds are
        # bit-identical by construction, not by tolerance
        self.codec = make_codec(config.update_codec, config)
        # graft-matrix: the per-drive mutual-exclusion checks that used to
        # live here as a wall of if/raise now live in ONE table
        # (core/spec.py EXCLUSIONS) — validate() raises the table's reason
        # for the first violated pair, same messages as before. The
        # aggregator rule is not a config field, so overlay its level for
        # the n-ary constraints (tensor x codec x robust/fednova).
        config.validate(aggregator=aggregator_name)
        if config.tensor_shards > 0:
            from fedml_tpu.parallel import TensorSharding, make_tensor_mesh

            self.mesh = make_tensor_mesh(config.tensor_shards)
            self._tensor_sharding = TensorSharding.for_model(
                self.mesh, config.model)
        # the API's round programs ALWAYS return the ledger's per-cohort
        # stats rows (collect_stats=True): whether a ledger is attached to
        # the drive only changes host-side scatter writes, never the traced
        # program — that is the whole ledger on/off bit-identity argument.
        # Direct builder callers (the analysis enumeration) keep the
        # legacy 3-tuple default, so COMPILE/COMMS budgets are untouched.
        self._round_has_stats = True
        # whether the round program stops its step loop at the cohort's last
        # real batch (engine.live_steps: the vmap engine, plain or under
        # GSPMD) or runs every step (shard_map meshes, silo groups): what
        # `round_slots` is told at staging
        self._live_steps = False
        # vmap lanes the cohort's clients are packed onto (engine.
        # packed_lanes); None: a lane a client, every program but the plain
        # vmap round below
        self._lanes = None
        if config.tensor_shards > 0:
            # tensor path keeps the INNER aggregator — the codec lives in
            # the round's own wire transports (build_tensor_round_fn), and
            # init_codec_agg_state below extends the state
            self.round_fn = build_round_fn(
                model_trainer, config, self.aggregator,
                donate_data=config.pipeline_depth > 0,
                param_sharding=self._tensor_sharding,
                collect_stats=True,
                codec=self.codec)
            self._live_steps = bool(config.shard_step)
        elif config.backend == "shard_map":
            from fedml_tpu.parallel import build_sharded_round_fn, make_mesh

            # any mesh_shape flattens onto the 1-D clients axis; richer axes
            # (groups/stages) belong to the hierarchical / splitnn APIs
            shape = (int(np.prod(config.mesh_shape)),) if config.mesh_shape else None
            self.mesh = make_mesh(shape, axis_names=("clients",))
            if self.codec is not None:
                from fedml_tpu.core.builder import wrap_codec

                # residual slots span the PADDED cohort (pad_clients rounds
                # the width up to a mesh multiple before dispatch)
                n_ax = self.mesh.shape["clients"]
                slots = min(config.client_num_per_round, dataset.client_num)
                slots = -(-slots // n_ax) * n_ax
                self.aggregator = wrap_codec(
                    self.aggregator, self.codec, slots)
            self.round_fn = build_sharded_round_fn(
                model_trainer, config, self.aggregator, self.mesh,
                collect_stats=True
            )
        elif config.silo_threshold > 0:
            from fedml_tpu.algorithms.silo_grouped import (
                build_silo_round_fn, silo_trainer)

            # the silo-grouped lowering repacks clients into silo groups, so
            # its outputs don't align with the cohort axis — no ledger stats
            self._round_has_stats = False
            self.round_fn = build_silo_round_fn(
                silo_trainer(model_trainer, config.silo_threshold),
                config, self.aggregator)
        else:
            if self.codec is not None and config.buffer_size == 0:
                from fedml_tpu.core.builder import wrap_codec

                # sync vmap/pipelined drives: wrap the aggregator HERE (not
                # inside build_round_fn) so init_state below yields the
                # extended {"agg", "codec"} tree that checkpoints, guard
                # snapshots and donation all ride. Buffered drives keep the
                # inner aggregator — their codec stage lives at admit
                # (algorithms/buffered.py), commits aggregate decoded rows.
                slots = min(config.client_num_per_round, dataset.client_num)
                self.aggregator = wrap_codec(
                    self.aggregator, self.codec, slots)
            self._live_steps = True
            # the pipelined drive loop stages a fresh device copy of the
            # cohort every round, so its buffers can be donated into the
            # round; the eager loop keeps the non-donating default
            if config.personalize:
                # graft-pfl: the personalized twin — same round shape plus
                # trailing [C, ...] personal adapter rows in/out, staged
                # from / scattered into the mmap bank by the drive. Every
                # other branch above is table-illegal with personalize
                # (core/spec.py), so this is the ONLY personalized build.
                from fedml_tpu.algorithms.engine import (
                    build_personal_round_fn)

                self.round_fn = build_personal_round_fn(
                    model_trainer, config, self.aggregator,
                    donate_data=config.pipeline_depth > 0,
                    collect_stats=True)
            else:
                if not (config.buffer_size > 0
                        or config.rounds_per_dispatch > 1):
                    # derived from the federation, never set. The buffered
                    # drive's client step and the superstep drive's K-round
                    # program run a lane a client, and so does the eager
                    # round the superstep is held bit-identical to
                    train = dataset.train
                    self._lanes = packed_lanes(
                        getattr(train, "counts", None),
                        min(config.client_num_per_round, dataset.client_num),
                        train.n_max, config.batch_size)
                self.round_fn = build_round_fn(
                    model_trainer, config, self.aggregator,
                    donate_data=config.pipeline_depth > 0,
                    collect_stats=True, lanes=self._lanes)
        self._personalized = bool(config.personalize)
        # what `program_scopes` lowers: the jitted object built here, never
        # what `round_fn` holds later (a caller may wrap it); the avals of
        # its arguments are taken at the first dispatch
        self._round_program = getattr(self.round_fn, "jitted", self.round_fn)
        self._round_avals = None
        self._scopes = None
        #: the attached personal adapter bank (models/adapter_bank.py) —
        #: set by train(bank=...) or directly; required when personalizing
        self.bank = None
        self.eval_fn = build_eval_fn(model_trainer)
        self.client_eval_fn = build_client_eval_fn(model_trainer)
        self._personal_eval_fn = None
        if config.personalize:
            from fedml_tpu.algorithms.engine import (
                build_personal_client_eval_fn)

            self._personal_eval_fn = build_personal_client_eval_fn(
                model_trainer)
        self._fed_eval_fn = build_federation_eval_fn(model_trainer)
        self._resident_cache = None
        # superstep drive state: jitted K-round programs keyed by
        # (k_eff, chaos_armed, in_graph_sampling), and the device-resident
        # whole-train-store arrays they gather cohorts from (None until
        # first use; () = residency unavailable, eager fallback)
        self._superstep_cache: dict = {}
        self._resident_train = None
        self.history: list[dict[str, Any]] = []
        # The stage seam: every cohort — eager or pipelined, any backing
        # store — reaches the device through this one callable
        # (signature: stage_fn(round_idx, *, chaos=None, faults=None,
        # tracer=None) -> StagedCohort). Injectable: multihost deployments
        # swap in a sharded stager (parallel.multihost.sample_sharded_cohort
        # + stage_local_cohort) that gathers only this host's slice.
        self.stage_fn = self._stage_cohort

        rng = jax.random.PRNGKey(config.seed)
        example = jnp.asarray(dataset.train.x[:1, 0])
        self.global_variables = model_trainer.init(rng, example)
        self.agg_state = self.aggregator.init_state(self.global_variables)
        if self._tensor_sharding is not None:
            # commit params + aggregator state to their tensor shards once;
            # the round_fn keeps them sharded (and donated, when enabled)
            # from then on
            self.global_variables = self._tensor_sharding.place(
                self.global_variables)
            if self.codec is not None:
                from fedml_tpu.parallel.tensor import init_codec_agg_state

                self.agg_state = init_codec_agg_state(
                    self._tensor_sharding, self.global_variables,
                    self.agg_state)
            else:
                self.agg_state = self._tensor_sharding.place(self.agg_state)

        bs = config.batch_size if config.batch_size > 0 else 256
        self._test_batches = pack_eval_batches(*dataset.test_global, max(bs, 64))

    # ------------------------------------------------------------------ train
    def train_one_round(self, round_idx: int, faults=None,
                        rng_salt: int = 0, tracer=None) -> dict[str, Any]:
        """One synchronous round. `faults` (robustness.chaos.FaultEvents for
        this round's cohort) injects drops/NaN/corruption at the host
        boundary and arms the in-round participation mask + quarantine;
        `rng_salt` != 0 derives a fresh round rng (guard retries — salt 0
        keeps the legacy stream bit-exactly). Phase spans (stage/h2d/
        dispatch/metrics_fetch) bracket — never enter — the jitted call, so
        an installed tracer changes no lowered program.

        Staging goes through `self.stage_fn` — the SAME seam the pipelined
        loop's prefetcher calls — so the eager and pipelined paths feed
        `round_fn` byte-identical cohorts no matter which backing store
        (PackedClients / StreamingPackedClients / MmapPackedStore) is
        underneath."""
        cfg = self.cfg
        if tracer is None:
            tracer = telemetry.get_tracer() or telemetry.NULL_TRACER
        staged = self.stage_fn(round_idx, faults=faults, tracer=tracer)
        with tracer.span("dispatch", round_idx,
                         rows=staged.rows * cfg.epochs, slots=staged.slots,
                         lanes=staged.lanes, trip=staged.trip):
            rng = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), round_idx)
            if rng_salt:
                rng = jax.random.fold_in(rng, rng_salt)
            train_metrics, stats, new_personal = self._dispatch(staged, rng)
        # the drive loops pick the cohort's ledger stats up from here; the
        # stats arrays stay device-resident until RoundRecordLog's deferred
        # flush fetch — train_one_round itself never syncs on them. The
        # personal rows defer the same way (_bank_block -> record["_bank"]).
        self._last_dispatch = (staged, stats)
        self._last_personal = ((staged.personal["rows"], new_personal)
                               if staged.personal is not None else None)
        with tracer.span("metrics_fetch", round_idx):
            # ONE host round trip for the whole metrics dict — per-key float()
            # was one blocking transfer per metric
            return _host_metrics(train_metrics)

    def _dispatch(self, staged, rng) -> tuple:
        """One call of `round_fn` on a staged cohort: the new global model
        and aggregator state land on the API -> (train_metrics, stats or
        None, new personal rows or None). The first call keeps its
        arguments' avals for `program_scopes` (taken before the call: the
        program donates them)."""
        args = [self.global_variables, self.agg_state, staged.x, staged.y,
                staged.counts, rng]
        if staged.personal is not None:
            args.append(staged.personal["tree"])
        if staged.participation is not None:
            args.append(staged.participation)
        if self._round_avals is None:
            self._round_avals = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        out = self.round_fn(*args)
        self.global_variables, self.agg_state, train_metrics = out[:3]
        stats = out[3] if self._round_has_stats else None
        return train_metrics, stats, out[4] if self._personalized else None

    def program_scopes(self, tracer=None) -> dict[str, str]:
        """{HLO instruction name: op_name} of the round program's compiled
        text, for the instructions under a declared scope
        (`telemetry/scopes.py`): what joins a device trace's ops to the
        program's phases. Lowers and compiles the jitted object this API
        built, on the avals of its first dispatch (the executable that ran,
        from memory: no second backend compile), once; emits one
        `program_scopes` event to `tracer` (the installed one, else the last
        drive's). {} before any round ran, and where the compiled text lacks
        this tree's scopes (`stale`: a compile cache another tree filled).
        Nothing calls it in a run that is not traced."""
        if self._scopes is not None or self._round_avals is None:
            return self._scopes or {}
        from fedml_tpu.telemetry.scopes import program_map

        self._scopes, event = program_map(
            self._round_program, self._round_avals,
            getattr(self._round_program, "__name__", "round_fn"))
        tracer = (tracer or telemetry.get_tracer()
                  or getattr(self, "_last_tracer", None))
        if tracer is not None:
            tracer.event("program_scopes", **event)
        return self._scopes

    def train(self, ckpt_dir: str | None = None, ckpt_every: int = 25,
              metrics_logger=None, chaos=None, guard=None,
              tracer=None, ledger=None, bank=None) -> list[dict[str, Any]]:
        """Drive loop. `chaos` (robustness.chaos.FaultPlan) injects a seeded
        deterministic fault schedule per round; `guard`
        (robustness.guard.RoundGuard) inspects every round and, on a bad
        verdict, rolls back to the pre-round state through the Checkpointable
        interface (`_ckpt_tree`/`_ckpt_load` on the in-memory snapshot — the
        same tree `save_checkpoint` persists) and re-runs the round with a
        fresh rng salt, up to `guard.max_retries` before accepting.

        `cfg.pipeline_depth > 0` switches to the asynchronous round pipeline
        (`_train_pipelined`): cohort t+k staged by a background thread while
        round t executes, staged buffers donated into `round_fn`, metrics
        resolved in one deferred `jax.device_get`. Bit-identical to the
        eager loop at any depth — tests/test_pipeline.py.

        `tracer` (telemetry.Tracer) records per-round phase spans and the
        structured event ledger; when None, a default tracer is created
        (with a TRACE.jsonl manifest next to the checkpoints when
        `ckpt_dir` is given) and closed at the end of the drive. The
        tracer is installed as the module-level telemetry seam for the
        duration, so the chaos harness, guard, prefetcher, and compile
        cache emit into the same ledger — including from the background
        staging thread.

        `ledger` (telemetry.client_ledger.ClientLedger) attaches the
        per-client health ledger: every drive's per-cohort stats rows are
        scatter-written into it from RoundRecordLog's flush. Attaching a
        ledger changes NO traced program and adds NO sync points — final
        params are bit-identical with it on or off.

        `bank` (models.adapter_bank.AdapterBank, graft-pfl) attaches the
        personal adapter bank a personalized run REQUIRES: cohort rows are
        gathered at staging, the round's updated rows ride
        RoundRecordLog's one deferred device_get and scatter back from its
        flush (`_bank` blocks), and the probe lift eval writes the lift
        sidecar on test rounds. Cluster sharing (--adapter_clusters) maps
        clients onto bank rows through the attached ledger's ema_loss
        column."""
        cfg = self.cfg
        if bank is not None:
            self.bank = bank
        if cfg.personalize and self.bank is None:
            raise ValueError(
                "personalize=True needs an attached adapter bank "
                "(models/adapter_bank.py) — pass --adapter_bank_dir on the "
                "CLI or train(bank=...)")
        #: cluster-mode row assignment reads the SAME ledger the stats
        #: scatter into (ema_loss column)
        self._drive_ledger = ledger
        owns_tracer = tracer is None
        if tracer is None:
            tracer = telemetry.Tracer(
                jsonl_path=os.path.join(ckpt_dir, "TRACE.jsonl")
                if ckpt_dir else None)
        self._last_tracer = tracer  # test/ops introspection
        start_round = 0
        if ckpt_dir:
            start_round = self.maybe_restore(ckpt_dir)
        from fedml_tpu.utils.cache import hook_monitoring

        hook_monitoring()  # `compile` events, whoever enabled the cache
        telemetry.install(tracer)
        # a profiler window (--profile_rounds) drains through this before
        # it opens and closes: the rounds serialize on the global model
        tracer.drain_fn = lambda: jax.block_until_ready(self.global_variables)
        try:
            with tracer.span("drive"):
                if cfg.buffer_size > 0:
                    # staleness-aware buffered aggregation (FedBuff): no
                    # global round barrier — commits fire when K updates
                    # have accumulated, stragglers admitted late
                    from fedml_tpu.algorithms.buffered import train_buffered

                    train_buffered(self, start_round, ckpt_dir, ckpt_every,
                                   metrics_logger, chaos, guard, tracer,
                                   ledger=ledger)
                elif cfg.pipeline_depth > 0:
                    self._train_pipelined(start_round, ckpt_dir, ckpt_every,
                                          metrics_logger, chaos, guard,
                                          tracer, ledger)
                elif cfg.rounds_per_dispatch > 1:
                    # multi-round superstep: K rounds per jitted dispatch,
                    # bit-identical to the eager loop (tests/test_superstep);
                    # K == 1 never reaches here — the eager branch below IS
                    # the structurally-off path (no superstep program built)
                    self._train_superstep(start_round, ckpt_dir, ckpt_every,
                                          metrics_logger, chaos, guard,
                                          tracer, ledger)
                else:
                    self._train_eager(start_round, ckpt_dir, ckpt_every,
                                      metrics_logger, chaos, guard, tracer,
                                      ledger)
                if ckpt_dir:
                    with tracer.span("checkpoint"):
                        self.save_checkpoint(ckpt_dir, cfg.comm_round)
        finally:
            if self.bank is not None:
                # memmap writes are already durable pages; flush fsyncs so
                # a resumed run reads the bank bitwise
                self.bank.flush()
            tracer.drain_fn = None
            telemetry.uninstall(tracer)
            if owns_tracer:
                tracer.close()
        return self.history

    def _train_eager(self, start_round, ckpt_dir, ckpt_every, metrics_logger,
                     chaos, guard, tracer, ledger=None) -> None:
        """Legacy synchronous drive loop: stage, dispatch, block, resolve —
        every phase serialized against the device. Records commit through
        the same `RoundRecordLog` path as the pipelined loop (one code path
        for history/metrics/ledger), flushed every round."""
        records = RoundRecordLog(tracer, self.history, metrics_logger,
                                 ledger=ledger, bank=self.bank,
                                 experts_held=_experts_held(self.trainer))
        round_idx = start_round
        while round_idx < self.cfg.comm_round:
            round_idx = self._eager_round(round_idx, records, chaos=chaos,
                                          guard=guard, tracer=tracer,
                                          ckpt_dir=ckpt_dir,
                                          ckpt_every=ckpt_every)

    def _eager_round(self, round_idx, records, *, chaos, guard, tracer,
                     ckpt_dir, ckpt_every) -> int:
        """One eager round — guard retry attempts included — extracted from
        the legacy loop body unchanged, so the superstep drive's rollback
        replay (`_train_superstep`) runs the EXACT per-round program, rng
        salting, record assembly and flush the eager loop would. Returns
        round_idx + 1."""
        cfg = self.cfg
        retries = 0
        while True:
            rejected = False
            with tracer.round(round_idx) as rspan:
                faults = None
                if chaos is not None:
                    n_cohort = min(cfg.client_num_per_round, self.dataset.client_num)
                    faults = chaos.events(round_idx, n_cohort)
                snapshot = None
                if guard is not None:
                    # jax pytrees are immutable: holding the refs IS the snapshot
                    snapshot = (self._ckpt_tree(), self._ckpt_meta())
                train_metrics = self.train_one_round(round_idx, faults=faults,
                                                     rng_salt=retries,
                                                     tracer=tracer)
                with tracer.span("device_wait", round_idx):
                    jax.block_until_ready(self.global_variables)
                if guard is not None:
                    total = max(train_metrics.get("total", 1.0), 1.0)
                    loss = train_metrics.get("loss_sum", 0.0) / total
                    with tracer.span("guard_verdict", round_idx):
                        verdict = guard.inspect(round_idx, loss,
                                                self.global_variables)
                    tracer.event("guard_verdict", round=round_idx,
                                 ok=verdict.ok, reason=verdict.reason)
                    if not verdict.ok and retries < guard.max_retries:
                        retries += 1
                        log.warning("guard: %s — rolled back, retrying with "
                                    "fresh rng (%d/%d)", verdict.reason, retries,
                                    guard.max_retries)
                        tracer.event("guard_rollback", round=round_idx,
                                     retry=retries)
                        self._ckpt_load(*snapshot)
                        rejected = True  # new attempt, new round span
                    elif not verdict.ok:
                        log.warning("guard: %s — retries exhausted, accepting "
                                    "the round", verdict.reason)
                if not rejected:
                    record = {"round": round_idx, "round_time": rspan.elapsed()}
                    block = self._ledger_block(round_idx, *self._last_dispatch)
                    if block is not None:
                        record["_ledger"] = [block]
                    bank_block = self._bank_block(round_idx)
                    if bank_block is not None:
                        record["_bank"] = [bank_block]
                    if faults is not None:
                        record.update(chaos_summary(faults))
                        for k in ("participated_count", "quarantined_count"):
                            if k in train_metrics:
                                record[k] = train_metrics[k]
                    if guard is not None and retries:
                        record["guard_retries"] = retries
                    record.update(_moe_record(train_metrics))
                    if round_idx % cfg.frequency_of_the_test == 0 or round_idx == cfg.comm_round - 1:
                        record.update(self.evaluate(round_idx, tracer))
                    records.add(record)
                    records.flush(round_idx)
                    if ckpt_dir and (round_idx + 1) % ckpt_every == 0:
                        with tracer.span("checkpoint", round_idx):
                            self.save_checkpoint(ckpt_dir, round_idx + 1)
            if not rejected:
                return round_idx + 1

    # ------------------------------------------------- superstep drive loop
    def _resident_train_arrays(self):
        """Device-resident (x, y, counts) of the WHOLE train store for the
        superstep's in-graph cohort gather, built once; None when the store
        is streaming (lazy-decode) or over the byte budget — the drive then
        falls back to the eager loop."""
        if self._resident_train is None:
            from fedml_tpu.data.packed_store import resident_train_arrays

            res = resident_train_arrays(self.dataset.train)
            self._resident_train = res if res is not None else ()
        return self._resident_train or None

    def _superstep_fn(self, num_rounds: int, chaos_armed: bool,
                      in_graph_sampling: bool):
        """The jitted K-round program for this (k, chaos, sampling) shape,
        built once per combination — the drive's tail chunk (comm_round %
        K) and eval-cadence clamps reuse cache slots, they don't retrace
        per chunk."""
        key = (num_rounds, chaos_armed, in_graph_sampling)
        fn = self._superstep_cache.get(key)
        if fn is None:
            from fedml_tpu.algorithms.engine import build_superstep_fn

            fn = build_superstep_fn(
                self.trainer, self.cfg, self.aggregator, num_rounds,
                client_num_in_total=self.dataset.client_num,
                collect_stats=self._round_has_stats,
                chaos_armed=chaos_armed,
                in_graph_sampling=in_graph_sampling)
            self._superstep_cache[key] = fn
        return fn

    def _superstep_k(self, round_idx: int, ckpt_dir, ckpt_every: int) -> int:
        """Rounds the next superstep may fuse: up to cfg.rounds_per_dispatch,
        clamped so any eval round (frequency_of_the_test cadence or final
        round) or checkpoint round lands chunk-FINAL — eval reads the
        post-round model and checkpoints persist it, so neither can sit in
        the middle of a fused program. Returns >= 1; a 1 means the next
        round IS a boundary and runs through the plain eager round."""
        cfg = self.cfg
        k_max = min(cfg.rounds_per_dispatch, cfg.comm_round - round_idx)
        for j in range(k_max):
            r = round_idx + j
            if (r % cfg.frequency_of_the_test == 0
                    or r == cfg.comm_round - 1
                    or (ckpt_dir and (r + 1) % ckpt_every == 0)):
                return j + 1
        return k_max

    def _train_superstep(self, start_round, ckpt_dir, ckpt_every,
                         metrics_logger, chaos, guard, tracer,
                         ledger=None) -> None:
        """Multi-round fused drive loop (`cfg.rounds_per_dispatch` K > 1).

        Each dispatch runs up to K federated rounds as ONE jitted lax.scan
        (engine.build_superstep_fn): cohorts are gathered in-graph from the
        device-resident train store, per-round chaos masks are precomputed
        host-side as [K, C] arrays from the seeded FaultPlan, and the rng
        stream is fold_in(PRNGKey(seed), round_idx) per scanned round — the
        EXACT eager stream — so final params, aggregator state (fedopt
        momenta, codec residuals) and ledger stats rows are bit-identical
        to K eager rounds (tests/test_superstep.py). Metrics and stats come
        back [K]-leading and flush through RoundRecordLog as K records with
        ONE deferred device_get.

        Degradation: a streaming/over-budget train store, or chaos on
        integer inputs (host fault application is data-dependent there),
        falls back to `_train_eager` wholesale. A guard rejection inside a
        chunk rolls the WHOLE chunk back (params + guard state) and replays
        it through `_eager_round` at K=1 to localize and retry the bad
        round with the eager loop's exact salted-rng semantics."""
        cfg = self.cfg
        resident = self._resident_train_arrays()
        reason = None
        if resident is None:
            reason = ("train store is streaming or over the resident byte "
                      "budget")
        elif chaos is not None and not jnp.issubdtype(resident[0].dtype,
                                                      jnp.floating):
            reason = ("chaos faults on integer inputs are data-dependent on "
                      "the host and cannot be replayed in-graph")
        if reason is not None:
            log.warning("superstep (rounds_per_dispatch=%d) unavailable: %s "
                        "— running the eager loop", cfg.rounds_per_dispatch,
                        reason)
            self._train_eager(start_round, ckpt_dir, ckpt_every,
                              metrics_logger, chaos, guard, tracer, ledger)
            return
        records = RoundRecordLog(tracer, self.history, metrics_logger,
                                 ledger=ledger,
                                 experts_held=_experts_held(self.trainer))
        round_idx = start_round
        while round_idx < cfg.comm_round:
            k = self._superstep_k(round_idx, ckpt_dir, ckpt_every)
            if k == 1:
                # boundary round (eval/checkpoint/tail): the plain eager
                # round — same program the superstep's rollback replay uses
                round_idx = self._eager_round(
                    round_idx, records, chaos=chaos, guard=guard,
                    tracer=tracer, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every)
            else:
                round_idx = self._superstep_chunk(
                    round_idx, k, records, resident, chaos=chaos,
                    guard=guard, tracer=tracer, ckpt_dir=ckpt_dir,
                    ckpt_every=ckpt_every)

    def _superstep_chunk(self, r0, k, records, resident, *, chaos, guard,
                         tracer, ckpt_dir, ckpt_every) -> int:
        """One K-round fused dispatch: host precompute -> one jitted scan ->
        per-round verdicts -> commit K records (or roll the chunk back and
        replay it eagerly). Returns the next round index (always r0 + k —
        a rollback replay still ends the chunk, just eagerly)."""
        cfg = self.cfg
        n_total = self.dataset.client_num
        cohort = min(cfg.client_num_per_round, n_total)
        in_graph = cfg.fast_sampling and cohort < n_total
        rollback = False
        with tracer.round(r0) as rspan:
            with tracer.span("stage", r0, rounds=k):
                rids = np.arange(r0, r0 + k, dtype=np.int32)
                per_round = {"round_idx": rids}
                sampler = (fast_client_sampling if cfg.fast_sampling
                           else client_sampling)
                # host indices always computed (O(K*C) tiny): the ledger
                # records client ids even when sampling reruns in-graph
                idx_block = np.stack([
                    sampler(r, n_total,
                            cfg.client_num_per_round).astype(np.int32)
                    for r in range(r0, r0 + k)])
                if in_graph:
                    from fedml_tpu.algorithms.sampling import (
                        feistel_keys_block)

                    per_round["keys"] = feistel_keys_block(r0, k)
                else:
                    per_round["idx"] = idx_block
                faults_list = None
                if chaos is not None:
                    faults_list, masks = chaos.events_block(r0, k, cohort)
                    per_round.update(masks)
                # the cohorts are gathered in-graph from the resident store:
                # what is staged is indices, what the chunk trains is this
                counts_block = self.dataset.train.counts[idx_block]
                rows = int(counts_block.sum())
                slots = sum(round_slots(cfg, cohort,
                                        self.dataset.train.x.shape[1], c)
                            for c in counts_block)
            with tracer.span("h2d", r0, bytes=sum(
                    a.nbytes for a in per_round.values())):
                per_round = jax.device_put(per_round)
            snapshot = guard_state = None
            if guard is not None:
                snapshot = (self._ckpt_tree(), self._ckpt_meta())
                # the guard is stateful (loss window, test doubles' flags);
                # the eager replay below must re-inspect from the SAME state
                guard_state = copy.deepcopy(vars(guard))
            superstep = self._superstep_fn(k, chaos is not None, in_graph)
            with tracer.span("dispatch", r0, rounds=k,
                             rows=rows * cfg.epochs, slots=slots):
                out = superstep(self.global_variables, self.agg_state,
                                *resident, jax.random.PRNGKey(cfg.seed),
                                per_round)
                if self._round_has_stats:
                    new_gv, new_st, train_metrics, stats = out
                else:
                    new_gv, new_st, train_metrics = out
                    stats = None
            with tracer.span("device_wait", r0):
                jax.block_until_ready(new_gv)
            if guard is not None:
                with tracer.span("metrics_fetch", r0):
                    host_metrics = jax.device_get(train_metrics)
                for j in range(k):
                    r = r0 + j
                    # host_metrics is already on the host (one device_get
                    # above) — numpy scalars feed the guard directly
                    m_j = {mk: mv[j] for mk, mv in host_metrics.items()}
                    total = max(m_j.get("total", 1.0), 1.0)
                    loss = m_j.get("loss_sum", 0.0) / total
                    # the chunk-final params stand in for round j's (a NaN
                    # in params/momenta persists through the scan, so
                    # non-finite state is still caught; the eager replay
                    # then localizes the exact bad round)
                    with tracer.span("guard_verdict", r):
                        verdict = guard.inspect(r, loss, new_gv)
                    tracer.event("guard_verdict", round=r, ok=verdict.ok,
                                 reason=verdict.reason)
                    if not verdict.ok:
                        rollback = True
                        log.warning(
                            "guard: %s at round %d inside a %d-round "
                            "superstep — chunk rolled back, replaying "
                            "eagerly to localize", verdict.reason, r, k)
                        tracer.event("guard_rollback", round=r, retry=0)
                        self._ckpt_load(*snapshot)
                        guard.__dict__.update(guard_state)
                        break
            if not rollback:
                self.global_variables = new_gv
                self.agg_state = new_st
                elapsed = rspan.elapsed()
                for j in range(k):
                    r = r0 + j
                    record = {"round": r, "round_time": elapsed / k}
                    if stats is not None:
                        faults_j = faults_list[j] if faults_list else None
                        n = idx_block.shape[1]
                        participated = (
                            np.asarray(faults_j.participation, bool)[:n]
                            if faults_j is not None else np.ones(n, bool))
                        record["_ledger"] = [{
                            "round": r,
                            "client_idx": idx_block[j],
                            # device rows ride the flush's one deferred fetch
                            "stats": jax.tree.map(lambda a, jj=j: a[jj],
                                                  stats),
                            "participated": participated,
                        }]
                    if faults_list is not None:
                        record.update(chaos_summary(faults_list[j]))
                        for mk in ("participated_count", "quarantined_count"):
                            if mk in train_metrics:
                                record[mk] = train_metrics[mk][j]
                    if j == k - 1 and (
                            r % cfg.frequency_of_the_test == 0
                            or r == cfg.comm_round - 1):
                        record.update(self.evaluate(r, tracer))
                    records.add(record)
                records.flush(r0 + k - 1)
                tracer.event("superstep_committed", round=r0, rounds=k,
                             k=cfg.rounds_per_dispatch)
                if ckpt_dir and (r0 + k) % ckpt_every == 0:
                    with tracer.span("checkpoint", r0 + k - 1):
                        self.save_checkpoint(ckpt_dir, r0 + k)
        if rollback:
            # replay the whole chunk through the eager round — exact eager
            # guard/retry/record semantics, one round span per attempt
            r = r0
            while r < r0 + k:
                r = self._eager_round(r, records, chaos=chaos, guard=guard,
                                      tracer=tracer, ckpt_dir=ckpt_dir,
                                      ckpt_every=ckpt_every)
        return r0 + k

    @staticmethod
    def _ledger_block(round_idx, staged, stats):
        """One per-cohort stats block for a round record's `_ledger` key.

        `stats` holds device arrays (possibly mesh-padded past the true
        cohort — ClientLedger.apply trims to len(client_idx)); they stay
        unresolved until the record log's single deferred device_get."""
        if stats is None:
            return None
        n = len(staged.client_idx)
        participated = (np.asarray(staged.faults.participation, bool)[:n]
                        if staged.faults is not None else np.ones(n, bool))
        return {"round": round_idx,
                "client_idx": np.asarray(staged.client_idx),
                "participated": participated,
                "stats": stats}

    def _bank_block(self, round_idx):
        """One personal-row block for a round record's `_bank` key — the
        rows stay device-resident until the record log's single deferred
        device_get, then AdapterBank.apply scatters them (graft-pfl)."""
        last = getattr(self, "_last_personal", None)
        if last is None:
            return None
        rows, new_personal = last
        return {"round": round_idx, "client_idx": np.asarray(rows),
                "rows": new_personal}

    def _bank_rows(self, idx) -> np.ndarray:
        """Bank row ids for a cohort: the client ids themselves (one row
        per client), or their EMA-loss cluster buckets under
        --adapter_clusters K (the bank holds K shared rows; assignment is
        a static O(cohort) bucket of the attached ledger's ema_loss
        column — a missing ledger reads as loss 0, bucket 0)."""
        idx = np.asarray(idx, np.int64)
        k = self.cfg.adapter_clusters
        if k <= 0:
            return idx
        from fedml_tpu.models.adapter_bank import cluster_rows

        ledger = getattr(self, "_drive_ledger", None)
        ema = (np.asarray(ledger.column("ema_loss"))[idx]
               if ledger is not None else np.zeros(idx.size, np.float32))
        return cluster_rows(ema, k)

    # --------------------------------------------------------- stage seam
    def _stage_cohort(self, round_idx: int, chaos=None, faults=None,
                      tracer=None) -> StagedCohort:
        """Host half of one round as a pure function of `round_idx`: sample
        -> gather -> chaos faults + participation mask -> mesh pad ->
        non-blocking `jax.device_put` (engine.stage_to_device). This is the
        default `self.stage_fn` — the ONE staging path both drive loops
        share: the eager loop calls it inline (train_one_round, with the
        round's pre-computed `faults`), the pipelined loop calls it from
        the prefetcher's staging thread (with the `chaos` plan, deriving
        faults per round). Staging is pure in `round_idx`, so the two are
        byte-identical — the pipelined == eager bit-identity pin depends
        on it. Spans route through the installed tracer when none is
        passed (the stager thread carries no tracer argument) and are
        tagged thread="stager" when staged ahead."""
        cfg = self.cfg
        if tracer is None:
            tracer = telemetry.get_tracer() or telemetry.NULL_TRACER
        with tracer.span("stage", round_idx):
            sampler = (fast_client_sampling if cfg.fast_sampling
                       else client_sampling)
            idx = sampler(round_idx, self.dataset.client_num,
                          cfg.client_num_per_round)
            if faults is None and chaos is not None:
                faults = chaos.events(round_idx, len(idx))
            x, y, counts = self.dataset.train.select(idx)
            participation = None
            if faults is not None:
                x = apply_faults(faults, x)
                participation = np.asarray(faults.participation, bool)
            if self.mesh is not None:
                n_before = counts.shape[0]
                x, y, counts = pad_clients(x, y, counts, self.mesh.shape["clients"])
                if participation is not None and counts.shape[0] > n_before:
                    # padded rows are zero-count no-ops either way; marking them
                    # non-participating keeps participated_count honest
                    participation = np.concatenate(
                        [participation,
                         np.zeros(counts.shape[0] - n_before, bool)])
            personal = None
            if self.cfg.personalize:
                if self.bank is None:
                    raise ValueError(
                        "personalize=True needs an attached adapter bank "
                        "(models/adapter_bank.py) — pass --adapter_bank_dir "
                        "on the CLI or train(bank=...)")
                # O(cohort) coalesced preads; never-scattered clients come
                # back as zero rows (the personalization identity). The
                # mesh-pad branch above is unreachable here — every meshed
                # lowering is table-illegal with personalize.
                rows = self._bank_rows(idx)
                with tracer.span("bank_gather", round_idx, rows=len(rows)):
                    gathered = self.bank.gather(rows)
            # counted here, where `counts` is still a host array: the real
            # rows, and the slots the round program runs for them
            n_rows = int(counts.sum())
            work = self._round_work(x, counts)
        host = [x, y, counts] + ([] if participation is None
                                 else [participation])
        if self.cfg.personalize:
            host += jax.tree.leaves(gathered)
        with tracer.span("h2d", round_idx,
                         bytes=sum(a.nbytes for a in host)):
            dx, dy, dc, dp = stage_to_device(x, y, counts, participation,
                                             sharding=self._cohort_sharding())
            if self.cfg.personalize:
                personal = {"rows": rows, "tree": jax.device_put(gathered)}
        return StagedCohort(round_idx, dx, dy, dc, dp, faults, idx,
                            personal=personal, rows=n_rows, **work)

    def _round_work(self, x, counts) -> dict:
        """engine.round_work of a staged host cohort (lanes, trip, slots),
        by the steps THIS API's round program executes for it."""
        return round_work(self.cfg, x.shape[0], x.shape[1],
                          counts if self._live_steps else None, self._lanes)

    def _cohort_sharding(self):
        """Where a staged cohort goes on a mesh round: rows over the
        `clients` axis (replicated over `tensor`, where the mesh has one) —
        the in_specs of both mesh rounds. None off-mesh: one device."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec("clients"))

    def stage_partial_cohort(self, round_idx: int, width: int, cohort: int,
                             chaos=None, tracer=None) -> StagedCohort:
        """Partial-cohort staging for buffered serving (the FedBuff
        follow-up PR 9 deferred): stage only the first `width` clients of
        round `round_idx`'s seeded `cohort`-sized sample — the replacement
        slots freed by admitted arrivals — padded back to the static
        `cohort` width so the client_step signature (and the compile
        budget) never changes. Padding rows are zero-count no-ops and do
        NOT appear in `client_idx`, so the buffered runner schedules
        arrivals only for real rows. With `width == cohort` this is
        byte-identical to `_stage_cohort` (same sampler, same select, same
        device commit), which is what makes partial mode degenerate
        bit-exactly into full dispatch when no stragglers hold capacity."""
        cfg = self.cfg
        if tracer is None:
            tracer = telemetry.get_tracer() or telemetry.NULL_TRACER
        with tracer.span("stage", round_idx, width=width):
            sampler = (fast_client_sampling if cfg.fast_sampling
                       else client_sampling)
            idx = sampler(round_idx, self.dataset.client_num,
                          cohort)[:width]
            faults = (chaos.events(round_idx, len(idx))
                      if chaos is not None else None)
            x, y, counts = self.dataset.train.select(idx)
            if faults is not None:
                x = apply_faults(faults, x)
            if counts.shape[0] < cohort:
                x, y, counts = pad_clients(x, y, counts, cohort)
            n_rows = int(counts.sum())
        with tracer.span("h2d", round_idx,
                         bytes=x.nbytes + y.nbytes + counts.nbytes):
            dx, dy, dc, _ = stage_to_device(x, y, counts, None)
        return StagedCohort(round_idx, dx, dy, dc, None, faults, idx,
                            rows=n_rows, **self._round_work(x, counts))

    def _train_pipelined(self, start_round, ckpt_dir, ckpt_every,
                         metrics_logger, chaos, guard, tracer,
                         ledger=None) -> None:
        """Asynchronous drive loop (`cfg.pipeline_depth` > 0).

        While round t executes, a background stager prepares cohorts
        t+1..t+depth (`_stage_cohort` via data.prefetch.CohortPrefetcher);
        the staged device buffers are donated into `round_fn`; train metrics
        stay device-resident and are resolved in ONE deferred
        `jax.device_get` per flush — forced early only when the guard needs
        the loss, or on test/checkpoint rounds. A deque of in-flight metric
        trees bounds host run-ahead to `pipeline_depth` dispatched rounds.

        Guard rollback restores the snapshot, DROPS every in-flight prefetch
        (`invalidate` — the rejected round's buffers were donated and gone),
        and re-stages the retried round on demand; staging is pure in
        round_idx, so the retry sees byte-identical inputs plus the salted
        rng, exactly like the eager loop."""
        cfg = self.cfg
        prefetcher = CohortPrefetcher(
            lambda r: self.stage_fn(r, chaos=chaos), depth=cfg.pipeline_depth)
        self._last_prefetcher = prefetcher  # test/ops introspection
        # records (possibly holding device-array metrics) defer through the
        # shared RoundRecordLog; structured events (chaos, rollback) hit the
        # ledger the moment they occur, so a crash mid-flush cannot lose them
        records = RoundRecordLog(tracer, self.history, metrics_logger,
                                 ledger=ledger, bank=self.bank,
                                 experts_held=_experts_held(self.trainer))
        self._last_records = records  # test/ops introspection (max_pending)
        inflight: deque = deque()

        round_idx = start_round
        retries = 0
        try:
            while round_idx < cfg.comm_round:
                with tracer.round(round_idx) as rspan:
                    with tracer.span("stage_wait", round_idx):
                        staged = prefetcher.get(round_idx)
                    # a rolled-back timeline can never leak a stale cohort in
                    assert staged.round_idx == round_idx
                    if self._personalized:
                        # read-after-write: this round's gather must see the
                        # previous round's scatter, but the prefetcher staged
                        # this cohort's personal rows ahead of that flush —
                        # commit pending bank blocks and re-gather NOW. Data
                        # buffers stay pipelined; only the (rank-r tiny)
                        # personal rows restage, and the per-round flush
                        # keeps the eager loop's exact write-then-read order
                        # (personalized pipelined == eager bit-exactly).
                        records.flush(round_idx)
                        rows = self._bank_rows(staged.client_idx)
                        with tracer.span("bank_gather", round_idx,
                                         rows=len(rows)):
                            staged.personal = {
                                "rows": rows,
                                "tree": jax.device_put(self.bank.gather(rows))}
                    for ahead in range(1, cfg.pipeline_depth + 1):
                        if round_idx + ahead < cfg.comm_round:
                            prefetcher.prefetch(round_idx + ahead)
                    snapshot = None
                    if guard is not None:
                        snapshot = (self._ckpt_tree(), self._ckpt_meta())
                    with tracer.span("dispatch", round_idx,
                                     rows=staged.rows * cfg.epochs,
                                     slots=staged.slots, lanes=staged.lanes,
                                     trip=staged.trip):
                        rng = jax.random.fold_in(jax.random.PRNGKey(cfg.seed),
                                                 round_idx)
                        if retries:
                            rng = jax.random.fold_in(rng, retries)
                        train_metrics, stats, new_personal = self._dispatch(
                            staged, rng)
                    inflight.append(train_metrics)
                    if len(inflight) > cfg.pipeline_depth:
                        # rounds are serialized on device by the global-variables
                        # dependency, so round t-depth is long done — blocking on
                        # its tiny metric tree bounds run-ahead without stalling
                        with tracer.span("device_wait", round_idx):
                            jax.block_until_ready(inflight.popleft())
                    is_test = (round_idx % cfg.frequency_of_the_test == 0
                               or round_idx == cfg.comm_round - 1)
                    is_ckpt = bool(ckpt_dir) and (round_idx + 1) % ckpt_every == 0
                    if guard is not None:
                        with tracer.span("metrics_fetch", round_idx):
                            train_metrics = _host_metrics(train_metrics)
                        total = max(train_metrics.get("total", 1.0), 1.0)
                        loss = train_metrics.get("loss_sum", 0.0) / total
                        with tracer.span("guard_verdict", round_idx):
                            verdict = guard.inspect(round_idx, loss,
                                                    self.global_variables)
                        tracer.event("guard_verdict", round=round_idx,
                                     ok=verdict.ok, reason=verdict.reason)
                        if not verdict.ok and retries < guard.max_retries:
                            retries += 1
                            log.warning("guard: %s — rolled back, retrying with "
                                        "fresh rng (%d/%d)", verdict.reason,
                                        retries, guard.max_retries)
                            tracer.event("guard_rollback", round=round_idx,
                                         retry=retries)
                            self._ckpt_load(*snapshot)
                            prefetcher.invalidate()
                            inflight.clear()
                            continue
                        if not verdict.ok:
                            log.warning("guard: %s — retries exhausted, "
                                        "accepting the round", verdict.reason)
                    record = {"round": round_idx, "round_time": rspan.elapsed()}
                    block = self._ledger_block(round_idx, staged, stats)
                    if block is not None:
                        # stats stay device-resident in the pending record;
                        # they resolve in the flush's one deferred device_get
                        record["_ledger"] = [block]
                    if staged.personal is not None:
                        # personal rows defer exactly like the stats: device
                        # arrays pending until the flush fetch, then the
                        # bank scatter (records.py `_bank`)
                        record["_bank"] = [{
                            "round": round_idx,
                            "client_idx": np.asarray(staged.personal["rows"]),
                            "rows": new_personal}]
                    if staged.faults is not None:
                        record.update(chaos_summary(staged.faults))
                        for k in ("participated_count", "quarantined_count"):
                            if k in train_metrics:
                                record[k] = train_metrics[k]
                    if guard is not None and retries:
                        record["guard_retries"] = retries
                    # device-resident until the flush's one fetch
                    record.update(_moe_record(train_metrics))
                    retries = 0
                    if is_test:
                        # eval reads the post-round model, so these dispatches
                        # block on the round chain anyway — resolving now is free
                        record.update(self.evaluate(round_idx, tracer))
                    records.add(record)
                    # flush at sync points, and ALSO whenever the pending
                    # backlog exceeds ~2x the pipeline depth: unbounded
                    # deferral let deep pipelines accumulate host-side
                    # record debt that competed with the staging thread
                    # for the one CPU (BENCH_r06 depth-4 regression) —
                    # the flush here rides rounds that are long done on
                    # device, so it adds no stall
                    if (guard is not None or is_test or is_ckpt
                            or len(records) >= max(4, 2 * cfg.pipeline_depth)):
                        records.flush(round_idx)
                    if is_ckpt:
                        with tracer.span("checkpoint", round_idx):
                            self.save_checkpoint(ckpt_dir, round_idx + 1)
                round_idx += 1
        finally:
            prefetcher.close()
        records.flush()

    # -- checkpoint state (utils.checkpoint.Checkpointable): global model +
    # aggregator state + history (SURVEY §5: the reference's core FedAvg
    # cannot resume; this can)
    def _ckpt_tree(self):
        # LoRA: checkpoints persist adapters-only. The frozen base is a
        # pure function of cfg.seed (trainer.init), so storing it would
        # multiply checkpoint bytes by ~the model size for zero
        # information; resume/rollback re-attach the live base below.
        from fedml_tpu.models.lora import strip_lora_base

        return {"variables": strip_lora_base(self.global_variables),
                "agg_state": self.agg_state}

    def _ckpt_meta(self):
        # copy: the snapshot must not alias the live list a later flush
        # appends to
        return {"history": list(self.history)}

    def _ckpt_load(self, tree, meta):
        from fedml_tpu.models.lora import attach_lora_base

        # re-attach the deterministic frozen base from the live state (a
        # no-op when the trainer isn't LoRA-wrapped): guard rollback and
        # resume both restore adapters + agg state, never the base
        self.global_variables = attach_lora_base(tree["variables"],
                                                 self.global_variables)
        self.agg_state = tree["agg_state"]
        # in place: the drive loop's RoundRecordLog holds this list — a
        # rebind here would strand its post-rollback flushes on a stale copy
        self.history[:] = meta.get("history", [])

    # ------------------------------------------------------------------- eval
    def evaluate(self, round_idx: int, tracer) -> dict[str, float]:
        """A test round's evaluation, under one `eval` span."""
        with tracer.span("eval", round_idx):
            out = self.local_test_on_all_clients(round_idx)
            out.update(self.test_global(round_idx))
            out.update(self.personalization_lift(round_idx))
        return out

    def test_global(self, round_idx: int) -> dict[str, float]:
        bx, by, bm = self._test_batches
        m = self.eval_fn(self.global_variables, jnp.asarray(bx), jnp.asarray(by), jnp.asarray(bm))
        m = {k: float(v) for k, v in jax.device_get(m).items()}
        total = max(m.get("test_total", 1.0), 1.0)
        return {
            "Test/Acc": m.get("test_correct", 0.0) / total,
            "Test/Loss": m.get("test_loss", 0.0) / total,
        }

    def personalization_lift(self, round_idx: int,
                             probe: int = 64) -> dict[str, float]:
        """Accuracy lift of the personalized model over the global one on
        a sampled probe cohort (graft-pfl eval): each probe client
        evaluates under `params + its personal row` AND under the bare
        globals on its test split; the per-client delta lands in the
        bank's lift sidecar (tools/client_report.py surfaces it) and the
        probe mean logs as Personalization/Lift. O(probe) work and reads
        — never the full federation, never the million-row bank. {} when
        the run isn't personalized (test rounds stay byte-identical)."""
        if self.bank is None or not self.cfg.personalize:
            return {}
        ds = self.dataset
        n = min(probe, ds.client_num)
        idx = client_sampling(round_idx, ds.client_num, n)
        rows = self._bank_rows(idx)
        packed = ds.test or ds.train
        x, y, counts = packed.select(idx)
        x, y = jnp.asarray(x), jnp.asarray(y)
        counts = jnp.asarray(counts)
        personal = jax.device_put(self.bank.gather(rows))
        m_p = self._personal_eval_fn(self.global_variables, personal,
                                     x, y, counts)
        m_g = self.client_eval_fn(self.global_variables, x, y, counts)
        m_p, m_g = jax.device_get((m_p, m_g))
        total = np.maximum(np.asarray(m_g["test_total"], np.float64), 1.0)
        lift = ((np.asarray(m_p["test_correct"], np.float64)
                 - np.asarray(m_g["test_correct"], np.float64)) / total)
        self.bank.write_lift(rows, lift)
        return {"Personalization/Lift": float(lift.mean())}

    def local_test_on_all_clients(self, round_idx: int) -> dict[str, float]:
        """Reference _local_test_on_all_clients (fedavg_api.py:119-183): run the
        global model on every client's local train and test split, report
        sample-weighted aggregate accuracy. CI mode evaluates one client only
        (reference FedAVGAggregator.py:126-131).

        With cfg.resident_eval (default) the packed splits live on device and
        the whole federation evaluates in ONE jitted dispatch
        (engine.build_federation_eval_fn) — the chunked path re-sends every
        split from the host on every eval, one chunk per dispatch."""
        ds = self.dataset
        num = 1 if self.cfg.ci else ds.client_num
        splits = (("Train", ds.train), ("Test", ds.test or ds.train))
        out = {}
        resident = (not self.cfg.ci) and self._resident_eval_data(
            splits, round_idx)
        for split_name, packed in splits:
            chunk = _eval_chunk(packed.x, num)
            sums: dict[str, float] = {}
            if resident:
                m = self._fed_eval_fn(self.global_variables, *resident[split_name])
                sums = {k: float(v) for k, v in jax.device_get(m).items()}
            else:
                for start in range(0, num, chunk):
                    idx = np.arange(start, min(start + chunk, num))
                    x, y, counts = packed.select(idx)
                    if len(idx) < chunk:  # pad last chunk: stable jit cache
                        x, y, counts = pad_clients(x, y, counts, chunk)
                    m = self.client_eval_fn(
                        self.global_variables, jnp.asarray(x), jnp.asarray(y), jnp.asarray(counts)
                    )
                    # one fetch per chunk dispatch, then host-side sums —
                    # the per-key float(jnp.sum(v)) did D2H per metric key
                    for k, v in jax.device_get(m).items():
                        sums[k] = sums.get(k, 0.0) + float(v.sum())
            total = max(sums.get("test_total", 0.0), 1.0)
            out[f"{split_name}/Acc"] = sums.get("test_correct", 0.0) / total
            out[f"{split_name}/Loss"] = sums.get("test_loss", 0.0) / total
        return out

    def _resident_eval_data(self, splits, round_idx=None):
        """Device-resident [nc, chunk, n_max, ...] eval arrays per split,
        built once; None when disabled, over the byte budget, or more than
        the device has room for. The one-off transfer is the `eval_h2d`
        span, a child of the `eval` that needed it."""
        if not self.cfg.resident_eval:
            return None
        if self._resident_cache is not None:
            return self._resident_cache or None  # {} = previously over budget
        num = self.dataset.client_num
        uniq = {id(p): p for _, p in splits}  # test may alias train
        if not all(isinstance(p.x, np.ndarray)
                   or isinstance(p, MmapPackedStore)
                   for p in uniq.values()):
            # StreamingPackedClients exposes x as a lazy decode facade with no
            # nbytes; staging it would eagerly decode the whole split, which
            # is exactly what streaming exists to avoid — keep the chunked
            # path. Mmap shard stores DO size themselves from the header
            # (no data touched), so they fall through to the byte budget:
            # in-budget stores materialize() once and share the in-RAM
            # resident path bit-exactly, over-budget ones stay chunked.
            log.info("resident_eval disabled: streaming (lazy-decode) split — "
                     "using chunked eval")
            self._resident_cache = {}
            return None

        def staged_bytes(p):
            # what stage() actually device_puts: padded to a chunk multiple
            # same chunk geometry as the streaming path
            chunk = _eval_chunk(p.x, num)
            ratio = (-(-p.num_clients // chunk) * chunk) / p.num_clients
            return (p.x.nbytes + p.y.nbytes + p.counts.nbytes) * ratio

        total_bytes = sum(staged_bytes(p) for p in uniq.values())
        budget = self.cfg.resident_eval_budget
        stats = jax.devices()[0].memory_stats()
        if stats and "bytes_limit" in stats:
            # the federation eval may hold the split it scans TWICE: the
            # resident argument, and a converted copy the compiler plans
            # outside its loop (compiled for a described v5e under jax 0.9:
            # 5.2 GB f32 FEMNIST split -> 5.2 GB lane-padded bf16 temp) —
            # so the splits may take half of what the device has free,
            # whatever the configured budget says
            free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
            budget = min(budget, free // 2)
        if total_bytes > budget:
            log.warning(
                "resident_eval disabled: packed splits are %.1f GiB > budget "
                "%.1f GiB — falling back to chunked streaming eval",
                total_bytes / 2**30, budget / 2**30)
            self._resident_cache = {}
            return None

        def stage(packed):
            chunk = _eval_chunk(packed.x, num)
            if isinstance(packed, MmapPackedStore):
                # the ONE sanctioned whole-store read; in-budget (checked
                # above) and bit-identical to an in-RAM split of the same rows
                packed = materialize(packed,
                                     budget=self.cfg.resident_eval_budget)
            nc = -(-packed.num_clients // chunk)
            x, y, counts = pad_clients(packed.x, packed.y, packed.counts, chunk)
            return tuple(
                jax.device_put(a.reshape((nc, chunk) + a.shape[1:]))
                for a in (x, y, counts))

        staged: dict[int, tuple] = {}  # test may BE train (no test split)
        cache = {}
        tracer = telemetry.get_tracer() or telemetry.NULL_TRACER
        with tracer.span("eval_h2d", round_idx):
            for name, p in splits:
                if id(p) not in staged:
                    staged[id(p)] = stage(p)
                cache[name] = staged[id(p)]
            # the eval that follows needs them all: waiting here costs
            # nothing and gives the span the transfer's true length
            jax.block_until_ready(cache)
        self._resident_cache = cache
        return self._resident_cache
