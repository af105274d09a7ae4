"""Typed run configuration — replaces the reference's argparse-globals.

The reference passes a raw argparse `args` namespace through every layer
(reference fedml_experiments/distributed/fedavg/main_fedavg.py:46-112); here the
same knob surface is a frozen dataclass so it can be closed over by jitted
functions (all fields are static Python values, never traced).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any


@dataclass(frozen=True)
class FedConfig:
    """Knobs shared by every algorithm; mirrors reference `add_args`.

    Field names follow reference main_fedavg.py:46-112 so experiment configs
    transfer verbatim.
    """

    # data
    dataset: str = "mnist"
    data_dir: str = "./data"
    partition_method: str = "hetero"  # homo | hetero (LDA) | p-hetero | hetero-fix
    partition_alpha: float = 0.5
    client_num_in_total: int = 10
    client_num_per_round: int = 10

    # model
    model: str = "lr"

    # local training (reference my_model_trainer_classification.py:17-53)
    batch_size: int = 10  # -1 = full batch (the CI equivalence-oracle mode)
    client_optimizer: str = "sgd"  # sgd | adam
    lr: float = 0.03
    momentum: float = 0.0
    wd: float = 0.0
    epochs: int = 1  # local epochs E
    # reference my_model_trainer_classification.py:44 clips unconditionally at
    # 1.0 every step ("to avoid nan loss") — same default here; None disables
    grad_clip: float | None = 1.0
    # torch DataLoader(shuffle=True) analog. False = iterate each client's
    # samples in stored order (valid prefix), which makes minibatch
    # trajectories bit-comparable with a fixed-order reference DataLoader —
    # the reference-parity oracle (tests/test_reference_parity.py) relies on it
    shuffle: bool = True
    # Caller-asserted static shape info: every packed client row is FULL
    # (counts[i] == n_max) and n_max % batch_size == 0. The engine then drops
    # the padding-validity machinery (masks become literal ones and fold away,
    # no-op-step selects disappear) — trajectories are bit-identical to the
    # general path on data satisfying the contract
    # (tests/test_fedavg.py::test_assume_full_clients_bit_identical); on data
    # violating it, padded rows would be trained on. Opt-in.
    assume_full_clients: bool = False

    # federated loop
    comm_round: int = 10
    frequency_of_the_test: int = 1

    # server optimizer (FedOpt; reference main_fedopt.py:54-60)
    server_optimizer: str = "sgd"
    server_lr: float = 1.0
    server_momentum: float = 0.0

    # FedProx / FedNova
    fedprox_mu: float = 0.0

    # robust aggregation (reference robust_aggregation.py:32-55)
    norm_bound: float = 5.0
    stddev: float = 0.025

    # systems
    seed: int = 0
    ci: int = 0  # CI mode: eval a single client (reference FedAVGAggregator.py:126-131)
    # keep the packed train/test splits device-resident and run the
    # all-clients eval as ONE jitted scan (single dispatch) instead of
    # shipping 64-client chunks per eval; falls back to chunked streaming
    # when the splits exceed resident_eval_budget bytes
    resident_eval: bool = True
    resident_eval_budget: int = 8 << 30
    backend: str = "vmap"  # vmap (single chip) | shard_map (mesh)
    # >0 enables the asynchronous round pipeline in the FedAvg-family drive
    # loop: a background stager gathers/faults/pads/device_puts cohort t+k
    # (k <= pipeline_depth) while round t executes, staged buffers are
    # DONATED into round_fn, and train metrics stay device-resident until a
    # test/checkpoint round (or --guard) forces one jax.device_get.
    # Bit-identical to the eager driver at any depth
    # (tests/test_pipeline.py); 0 = eager legacy loop. The CLI default is 2
    # (experiments/common.py); the library default stays eager.
    pipeline_depth: int = 0
    # >0 enables the silo-grouped conv execution path (ResNetCifar models
    # only): convs with min(cin, cout) <= silo_threshold merge the round's
    # silos into one feature_group_count conv — measured 1.55x at 16-channel
    # stages on the v5e (docs/cross_silo_ladder.json). Trajectories match the
    # vmap engine to numerical tolerance (tests/test_silo_grouped.py).
    silo_threshold: int = 0
    mesh_shape: tuple[int, ...] = ()
    # >0 runs rounds on the 2D ('clients', 'tensor') mesh with params and
    # aggregator state tensor-sharded per the model family's partition-rule
    # table (parallel/tensor.py). Bit-identical in f32 to the replicated
    # round (tests/test_tensor_shard.py); 0 = replicated params.
    tensor_shards: int = 0
    # With tensor_shards > 1: shard the CLIENT STEP's compute too — the
    # round jits under GSPMD with params tensor-sharded per the rule table
    # and `with_sharding_constraint` hooks on the model zoo's matmul
    # intermediates (parallel/activations.py), so attention/MLP/logits
    # activations stay split over the tensor axis (Megatron-style,
    # Shoeybi et al. 2019). Per-device peak bytes of the step drop <=0.5x
    # at 4 shards (COMMS_BUDGET.json `tensor.step` entries). Trades f32
    # bit-identity for an allclose contract (reassociated contractions);
    # at tensor_shards <= 1 the constraints are structurally off and the
    # program stays bit-identical. Opt-in; default keeps the shard_map
    # storage-sharded round.
    shard_step: bool = False
    # Per-client personalization (models/adapter_bank.py): each client's
    # local step trains global adapters + its PERSONAL adapter row
    # (elementwise sum — the zero row is the identity, so a client's
    # first personalized round is bit-identical to the shared round),
    # and the round program returns the updated personal rows
    # UNAGGREGATED — they never enter the psum, wire bytes unchanged
    # (COMMS_BUDGET pins the personalized twin's collective bytes equal
    # to the shared one). Requires lora_rank > 0 (the personal row IS a
    # rank-r adapter). False = structurally off: the personalized round
    # builder is never invoked and every drive loop traces the exact
    # legacy program (EQUIV_PAIRS "personalization-off").
    personalize: bool = False
    # With personalize: >0 shares adapter rows per EMA-loss cluster
    # instead of per client — the bank holds K rows, cluster id is a
    # static bucket of the ledger's ema_loss column (O(cohort)/round).
    adapter_clusters: int = 0
    # >0 wraps the trainer in LoRA (models/lora.py): base params frozen
    # under a "lora_base" collection (tensor-sharded on the 2D mesh),
    # rank-r adapters under "params" — only adapters are federated,
    # aggregated, codec-compressed, and checkpointed. 0 = structurally
    # off (the trainer is never wrapped; legacy programs bit-identical).
    lora_rank: int = 0
    # Opt-in O(cohort) stateless cohort sampler (Feistel permutation over
    # client ids). Default off: the default path keeps bit-compat with the
    # seeded rng.choice trajectory of fedavg.client_sampling.
    fast_sampling: bool = False
    # >1 fuses K federated rounds into ONE jitted lax.scan dispatch
    # (engine.build_superstep_fn): cohort gather happens in-graph from a
    # device-resident train store, chaos/participation masks ship as [K, C]
    # arrays, and K rounds of metrics/stats resolve with a single deferred
    # device_get. Bit-identical to K eager rounds (tests/test_superstep.py);
    # eval/checkpoint cadence clamps each chunk so boundary rounds stay
    # chunk-final, and a guard rejection rolls the chunk back and replays it
    # eager at K=1 to localize the bad round. 1 = structurally off (the
    # superstep builder is never invoked; the legacy eager loop runs).
    # Requires the single-chip vmap engine: mutually exclusive with
    # pipeline_depth / buffer_size / tensor_shards / silo_threshold /
    # backend="shard_map".
    rounds_per_dispatch: int = 1
    # >0 enables staleness-aware buffered aggregation (FedBuff): client
    # updates are admitted into a device-resident K-row buffer tagged with
    # their birth round and committed into globals only when K updates have
    # accumulated — no global round barrier. Arrival order comes from the
    # chaos straggler plan; the degenerate config (buffer_size = cohort,
    # staleness_alpha = 0, no stragglers) is bit-identical to the
    # synchronous loop (tests/test_buffered.py). 0 = synchronous legacy.
    buffer_size: int = 0
    # Staleness-discount exponent: an update born at round b and committed
    # at round t gets weight count * (1 + (t - b)) ** -alpha. 0 disables
    # discounting ((1+s)**-0 == 1.0 exactly, preserving bit-identity).
    staleness_alpha: float = 0.5
    # Compressed update transport (fedml_tpu/codecs): "none" | "int8" |
    # "topk". "none" takes the exact legacy code path in every round
    # builder (bit-identical to a codec-free build); "int8" quantizes
    # update payloads to int8 with a per-leaf scale and error-feedback
    # residuals carried in agg state; "topk" ships static-shape
    # (values, idx) sparse payloads so jit signatures never change.
    update_codec: str = "none"
    # top-k codec: entries kept per leaf (clamped to the leaf size — a
    # static function of shapes, so compile counts stay flat).
    codec_k: int = 64
    # int8 codec: quantization level width in bits (2..8); payloads are
    # stored/transported as int8 regardless, fewer bits just coarsen the
    # grid (used for psum transports that need contributor headroom).
    codec_bits: int = 8
    dtype: str = "float32"  # compute dtype; bfloat16 for MXU-heavy models

    extra: dict[str, Any] = field(default_factory=dict, hash=False, compare=False)

    def replace(self, **kw) -> "FedConfig":
        return dataclasses.replace(self, **kw)

    def validate(self, **axes: str) -> "FedConfig":
        """Raise ValueError for the first feature-axis exclusion (or
        value requirement) this config violates — a lookup into the
        ONE compatibility table in core/spec.py (graft-matrix). Keyword
        args overlay non-config axis levels when the caller knows them,
        e.g. ``cfg.validate(chaos="on")``. Returns self so call sites can
        chain. Construction stays unchecked on purpose: tests and the
        analysis matrix build illegal configs to prove they are rejected
        at validation time."""
        from fedml_tpu.core.spec import validate_config

        validate_config(self, axes=axes or None)
        return self

    @classmethod
    def from_dict(cls, d: dict) -> "FedConfig":
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in d.items() if k in names}
        extra = {k: v for k, v in d.items() if k not in names}
        if extra:
            known.setdefault("extra", {}).update(extra)
        return cls(**known)
