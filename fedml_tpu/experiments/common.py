"""Shared experiment plumbing: the reference's argparse surface + run setup.

Flag names follow reference fedml_experiments/distributed/fedavg/
main_fedavg.py:46-112 verbatim so launch scripts transfer; GPU-mapping flags
are replaced by mesh flags (SURVEY §2.2 gpu_mapping -> jax.sharding.Mesh).
"""

from __future__ import annotations

import argparse
import logging
import random

import numpy as np

from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.trainer import (
    ClassificationTrainer,
    NWPTrainer,
    TagPredictionTrainer,
)
from fedml_tpu.data.registry import FederatedDataset, load_dataset
from fedml_tpu.models.registry import create_model


def add_args(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Reference add_args (main_fedavg.py:46-112), TPU-adapted."""
    parser.add_argument("--model", type=str, default="lr")
    parser.add_argument("--model_config", type=str, default=None,
                        help="JSON of a model's published configuration "
                             "keys, for a model whose sizes are a file's "
                             "and no argument's (deepseek_v2, kimi_linear, "
                             "jamba, mellum; default: the file kept beside "
                             "the model)")
    parser.add_argument("--dataset", type=str, default="mnist")
    parser.add_argument("--data_dir", type=str, default="./data")
    parser.add_argument("--partition_method", type=str, default="hetero")
    parser.add_argument("--partition_alpha", type=float, default=0.5)
    parser.add_argument("--client_num_in_total", type=int, default=10)
    parser.add_argument("--client_num_per_round", type=int, default=10)
    parser.add_argument("--batch_size", type=int, default=10)
    parser.add_argument("--client_optimizer", type=str, default="sgd")
    parser.add_argument("--lr", type=float, default=0.03)
    parser.add_argument("--wd", type=float, default=0.0)
    parser.add_argument("--momentum", type=float, default=0.0)
    parser.add_argument("--epochs", type=int, default=1)
    parser.add_argument("--comm_round", type=int, default=10)
    parser.add_argument("--frequency_of_the_test", type=int, default=1)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--ci", type=int, default=0)
    # TPU-native replacements for gpu_server_num / gpu_mapping_file
    parser.add_argument("--backend", type=str, default="vmap",
                        choices=["vmap", "shard_map"])
    parser.add_argument("--mesh_shape", type=int, nargs="*", default=None)
    parser.add_argument("--ckpt_dir", type=str, default=None)
    parser.add_argument("--run_dir", type=str, default="./wandb/latest-run/files")
    parser.add_argument("--fedprox_mu", type=float, default=0.0)
    parser.add_argument("--dtype", type=str, default="float32",
                        choices=["float32", "bfloat16"])
    # fault-tolerance drive-loop knobs (fedml_tpu.robustness)
    parser.add_argument("--chaos", type=int, default=0,
                        help="1 = inject a seeded deterministic fault "
                             "schedule (drops/NaN/corruption) per round")
    parser.add_argument("--chaos_seed", type=int, default=0)
    parser.add_argument("--chaos_drop_rate", type=float, default=0.0)
    parser.add_argument("--chaos_nan_rate", type=float, default=0.0)
    parser.add_argument("--chaos_corrupt_rate", type=float, default=0.0)
    # seeded straggler plan (buffered aggregation): straggling clients'
    # updates arrive 1..straggler_rounds dispatch rounds late
    parser.add_argument("--chaos_straggler_rate", type=float, default=0.0)
    parser.add_argument("--chaos_straggler_rounds", type=int, default=0)
    parser.add_argument("--guard", type=int, default=0,
                        help="1 = roll back + re-run rounds whose loss goes "
                             "non-finite or spikes")
    parser.add_argument("--guard_spike_factor", type=float, default=4.0)
    parser.add_argument("--guard_max_retries", type=int, default=2)
    # asynchronous round pipeline (fedml_tpu.data.prefetch): stage cohort
    # t+k while round t executes + deferred metric sync; bit-identical to
    # the eager loop at any depth, so it is on by default for CLI runs.
    # 0 restores the eager driver.
    parser.add_argument("--pipeline_depth", type=int, default=2,
                        help="cohort prefetch depth for the FedAvg-family "
                             "drive loop (0 = eager)")
    # tensor-parallel rounds (fedml_tpu.parallel.tensor): params +
    # aggregator state sharded per the model family's partition-rule table
    # over a 2D ('clients', 'tensor') mesh; bit-identical in f32 to the
    # replicated round
    parser.add_argument("--tensor_shards", type=int, default=0,
                        help="tensor-axis size of the 2D (clients, tensor) "
                             "mesh (0 = replicated params)")
    parser.add_argument("--shard_step", type=int, default=0,
                        help="1 = activation-shard the client step itself "
                             "(GSPMD + with_sharding_constraint on model "
                             "intermediates; allclose contract, needs "
                             "--tensor_shards > 1)")
    # federated LoRA (models/lora.py): frozen base + rank-r adapters;
    # only adapters cross the wire / hit the aggregator / get checkpointed
    parser.add_argument("--lora_rank", type=int, default=0,
                        help="LoRA adapter rank; 0 = full fine-tuning "
                             "(trainer never wrapped, legacy programs)")
    # multi-round fused dispatch (engine.build_superstep_fn): K rounds per
    # jitted lax.scan program — in-graph cohort gather from a device-resident
    # store, one deferred metrics fetch per chunk. Bit-identical to K eager
    # rounds; eval/checkpoint cadence clamps K per chunk. 1 = eager loop.
    parser.add_argument("--rounds_per_dispatch", type=int, default=1,
                        help="federated rounds fused into one device "
                             "program (1 = eager; needs pipeline_depth 0)")
    parser.add_argument("--fast_sampling", type=int, default=0,
                        help="1 = O(cohort) Feistel-permutation cohort "
                             "sampler (different seeded trajectory than the "
                             "default O(N) sampler)")
    # staleness-aware buffered aggregation (fedml_tpu.algorithms.buffered):
    # admit updates into a K-row device buffer, commit when it fills — no
    # global round barrier; deterministic under the seeded straggler plan
    parser.add_argument("--buffer_size", type=int, default=0,
                        help="update-buffer size K for FedBuff-style "
                             "buffered aggregation (0 = synchronous)")
    parser.add_argument("--staleness_alpha", type=float, default=0.5,
                        help="staleness-discount exponent: committed weight "
                             "= count * (1 + staleness) ** -alpha")
    # compressed update transport (fedml_tpu.codecs): codec stage between
    # the client step and the aggregator; "none" keeps the exact legacy
    # (bit-identical) round program
    parser.add_argument("--update_codec", type=str, default="none",
                        choices=["none", "int8", "topk"],
                        help="update transport codec: int8 quantization "
                             "with error feedback, or top-k sparsification "
                             "with static-shape payloads")
    parser.add_argument("--codec_k", type=int, default=64,
                        help="top-k codec: entries kept per leaf (clamped "
                             "to the leaf size)")
    parser.add_argument("--codec_bits", type=int, default=8,
                        help="int8 codec: quantization width in bits (2-8; "
                             "wire dtype stays int8)")
    # graft-trace observability (fedml_tpu.telemetry): TRACE.jsonl is
    # always written to <run_dir>/TRACE.jsonl; these knobs add sinks
    parser.add_argument("--trace_summary", type=int, default=0,
                        help="1 = print an end-of-run per-phase p50/p95 "
                             "span table")
    parser.add_argument("--trace_wandb", type=int, default=0,
                        help="1 = mirror per-round phase durations into the "
                             "metrics logger as trace/<phase>_s")
    parser.add_argument("--profile_rounds", type=str, default=None,
                        help="A:B = capture a jax.profiler trace window "
                             "covering rounds [A, B) into --profile_dir")
    parser.add_argument("--profile_dir", type=str, default=None,
                        help="TensorBoard trace dir for --profile_rounds "
                             "(default <run_dir>/trace)")
    parser.add_argument("--trace_max_mb", type=float, default=0,
                        help="rotate TRACE.jsonl when it exceeds this many "
                             "MB (archived as TRACE.jsonl.NNN; 0 = never)")
    # graft-ledger client-health observability (telemetry/client_ledger.py):
    # out-of-core per-client counters fed by the round programs' stats
    # vector; read back with tools/client_report.py
    parser.add_argument("--client_ledger_dir", type=str, default=None,
                        help="directory for the mmap-backed per-client "
                             "health ledger (None = ledger off)")
    # graft-pfl million-client personalization (models/adapter_bank.py):
    # per-client rank-r adapter rows in a packed sparse mmap bank —
    # O(cohort) gather/scatter per round, O(touched rows) disk
    parser.add_argument("--adapter_bank_dir", type=str, default=None,
                        help="directory for the personal adapter bank; "
                             "setting it turns personalization ON "
                             "(requires --lora_rank > 0); resumable — "
                             "reopening validates rows and layout")
    parser.add_argument("--adapter_clusters", type=int, default=0,
                        help="share K cluster rows instead of one row per "
                             "client (assignment: static EMA-loss bucket "
                             "from the client ledger; 0 = per-client rows)")
    return parser


def robustness_from_args(args):
    """(FaultPlan | None, RoundGuard | None) from the --chaos/--guard flags."""
    chaos = guard = None
    if getattr(args, "chaos", 0):
        from fedml_tpu.robustness.chaos import FaultPlan

        chaos = FaultPlan(
            seed=args.chaos_seed,
            drop_rate=args.chaos_drop_rate,
            nan_rate=args.chaos_nan_rate,
            corrupt_rate=args.chaos_corrupt_rate,
            straggler_rate=getattr(args, "chaos_straggler_rate", 0.0),
            straggler_rounds=getattr(args, "chaos_straggler_rounds", 0))
    if getattr(args, "guard", 0):
        from fedml_tpu.robustness.guard import RoundGuard

        guard = RoundGuard(spike_factor=args.guard_spike_factor,
                           max_retries=args.guard_max_retries)
    return chaos, guard


def tracer_from_args(args, metrics_logger=None):
    """The run's graft-trace Tracer: TRACE.jsonl manifest in run_dir
    (always on — it is the run's flight recorder), optional wandb mirror
    (--trace_wandb) and jax.profiler window (--profile_rounds A:B)."""
    import os

    from fedml_tpu import telemetry

    run_dir = getattr(args, "run_dir", None)
    jsonl = os.path.join(run_dir, "TRACE.jsonl") if run_dir else None
    if jsonl:
        os.makedirs(run_dir, exist_ok=True)
    profile_dir = getattr(args, "profile_dir", None)
    if profile_dir is None and run_dir:
        profile_dir = os.path.join(run_dir, "trace")
    max_mb = getattr(args, "trace_max_mb", 0) or 0
    return telemetry.Tracer(
        jsonl_path=jsonl,
        metrics_logger=metrics_logger if getattr(args, "trace_wandb", 0)
        else None,
        profile_rounds=getattr(args, "profile_rounds", None),
        profile_dir=profile_dir,
        max_bytes=int(max_mb * 2 ** 20) or None,
        run_meta={"model": args.model, "dataset": args.dataset,
                  "clients": args.client_num_in_total,
                  "clients_per_round": args.client_num_per_round,
                  "batch_size": args.batch_size,
                  "pipeline_depth": args.pipeline_depth})


def ledger_from_args(args, num_clients: int):
    """The run's ClientLedger (--client_ledger_dir), or None. The ledger is
    opened against the dataset's FULL client population — its disk footprint
    is O(num_clients), its per-round write is O(cohort)."""
    ledger_dir = getattr(args, "client_ledger_dir", None)
    if not ledger_dir:
        return None
    from fedml_tpu.telemetry.client_ledger import open_or_create

    return open_or_create(ledger_dir, num_clients)


def bank_from_args(args, num_clients: int, api):
    """The run's AdapterBank (--adapter_bank_dir), or None. Row count is
    the full client population (or --adapter_clusters K in cluster mode);
    disk stays O(touched rows) — sparse files, lazy zero rows. The row
    template is the api's live adapter tree, so resume validates layout
    against THIS run's model/rank."""
    bank_dir = getattr(args, "adapter_bank_dir", None)
    if not bank_dir:
        return None
    import jax

    from fedml_tpu.models.adapter_bank import open_or_create

    template = jax.tree.map(
        lambda l: np.zeros(l.shape, l.dtype),
        jax.device_get(api.global_variables["params"]))
    clusters = int(getattr(args, "adapter_clusters", 0) or 0)
    rows = clusters if clusters > 0 else num_clients
    return open_or_create(bank_dir, rows, template)


def config_from_args(args) -> FedConfig:
    d = {k: v for k, v in vars(args).items() if v is not None}
    d.pop("data_dir", None)
    d.pop("ckpt_dir", None)
    d.pop("run_dir", None)
    # observability knobs configure the tracer/ledger, not the round program
    for k in ("trace_summary", "trace_wandb", "profile_rounds",
              "profile_dir", "trace_max_mb", "client_ledger_dir"):
        d.pop(k, None)
    # --adapter_bank_dir IS the personalization switch: the bank location
    # is a drive-side concern, the personalize bit is the config axis
    if d.pop("adapter_bank_dir", None):
        d["personalize"] = True
    if d.get("mesh_shape"):
        d["mesh_shape"] = tuple(d["mesh_shape"])
    else:
        d.pop("mesh_shape", None)
    d["fast_sampling"] = bool(d.get("fast_sampling", 0))
    d["shard_step"] = bool(d.get("shard_step", 0))
    # the superstep subsumes the pipeline (there is no per-round host gap
    # left to overlap) — a fused CLI run drops the pipeline default rather
    # than tripping the library's mutual-exclusion check
    if int(d.get("rounds_per_dispatch", 1)) > 1:
        d["pipeline_depth"] = 0
    return FedConfig.from_dict(d)


def build_trainer(args, cfg: FedConfig, ds):
    """The model and the task trainer these arguments mean for `ds`, of
    which only `class_num` and `meta` are read (reference main preamble,
    main_fedavg.py:262-320: trainer chosen by dataset)."""
    model_kwargs = {"dtype": cfg.dtype}
    if args.dataset in ("shakespeare", "fed_shakespeare"):
        model_kwargs["vocab_size"] = 90
        model_kwargs["per_position"] = args.dataset == "fed_shakespeare"
    # dataset-contextual "cnn" dispatch, exactly the reference's
    # (standalone main_fedavg.py:315-340: cnn+har -> HAR_CNN,
    # cnn+cifar10 -> CNNCifar, cnn+mnist-family/femnist -> CNN_DropOut) —
    # the examples/baseline scripts rely on it
    model_name = args.model
    if model_name == "cnn":
        if args.dataset in ("har", "har_subject"):
            model_name = "har_cnn"
        elif args.dataset == "cifar10":
            model_name = "cnn_cifar"
    if getattr(args, "model_config", None):
        model_kwargs["config"] = args.model_config
    module = create_model(model_name, output_dim=ds.class_num, **model_kwargs)
    from fedml_tpu import telemetry

    said = module.describe() if hasattr(module, "describe") else {}
    train = getattr(ds, "train", None)
    if hasattr(module, "attention_tiles") and train is not None:
        # the tiles its attention computes at the data's sequence length
        said["attention_tiles"] = module.attention_tiles(
            int(train.x.shape[-1]))
    telemetry.emit("model_built", model=model_name, **{
        **{k: None for k in ("layers", "mixers", "experts_held",
                             "experts_routed")}, **said})
    if getattr(module, "frozen_base_only", False) and cfg.lora_rank <= 0:
        raise SystemExit(f"{model_name} trains as a frozen base only: give "
                         f"--lora_rank > 0")
    # task trainer by dataset (reference FedAvgAPI.py:33-39)
    if ds.meta.get("task") == "nwp" or args.dataset in ("fed_shakespeare", "stackoverflow_nwp"):
        trainer = NWPTrainer(module, pad_id=0)
    elif ds.meta.get("task") == "tag_prediction" or args.dataset == "stackoverflow_lr":
        trainer = TagPredictionTrainer(module)
    else:
        trainer = ClassificationTrainer(module)
    # federated LoRA: wrap AFTER task-trainer construction so the adapter
    # seam is task-agnostic; --lora_rank 0 returns the trainer unchanged
    from fedml_tpu.models.lora import maybe_wrap_lora

    return maybe_wrap_lora(trainer, cfg)


def setup_run(args) -> tuple[FedConfig, FederatedDataset, object]:
    """Seeds + logging + data, then `build_trainer` for what was loaded."""
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s [%(levelname)s] %(name)s: %(message)s",
    )
    # persistent XLA compile cache (repo-local, gitignored): repeat CLI runs
    # of compile-heavy mains (DARTS/GDAS especially) skip recompilation
    from fedml_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()
    random.seed(args.seed)
    np.random.seed(args.seed)
    cfg = config_from_args(args)
    extra_load = {}
    if args.dataset == "mnist":
        # reference mnist feeds lr a flat 784 vector and CNN_DropOut 28x28
        # images (standalone main_fedavg.py:318-325) — flatten by model
        extra_load["flatten"] = args.model in ("lr", "mlp")
    if args.dataset == "tokens" and getattr(args, "model_config", None):
        # the token surrogate draws its ids over the model's vocabulary, in
        # the shapes the file's `data` group gives, where it has one
        import json

        with open(args.model_config) as f:
            spec = json.load(f)
        extra_load["vocab"] = spec["vocab_size"]
        extra_load.update({k: v for k, v in spec.get("data", {}).items()
                           if k in ("seq_len", "zipf_a", "train_sequences",
                                    "test_sequences")})
    ds = load_dataset(
        args.dataset,
        data_dir=args.data_dir,
        client_num_in_total=args.client_num_in_total,
        partition_method=args.partition_method,
        partition_alpha=args.partition_alpha,
        seed=args.seed,
        **extra_load,
    )
    return cfg, ds, build_trainer(args, cfg, ds)
