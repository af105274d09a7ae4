"""FedAvg experiment main (reference fedml_experiments/distributed/fedavg/
main_fedavg.py:262-328 — the north-star entry). Subsumes the standalone main
(standalone/fedavg/main_fedavg.py:216-366): backend=vmap is the standalone
simulator, backend=shard_map is the distributed deployment on a mesh.

Usage:
  python -m fedml_tpu.experiments.main_fedavg --dataset mnist --model lr \
      --client_num_in_total 1000 --client_num_per_round 10 --comm_round 100
"""

from __future__ import annotations

import argparse

from fedml_tpu import telemetry
from fedml_tpu.algorithms.fedavg import FedAvgAPI
from fedml_tpu.experiments.common import (
    add_args,
    bank_from_args,
    ledger_from_args,
    robustness_from_args,
    setup_run,
    tracer_from_args,
)
from fedml_tpu.utils.logging import MetricsLogger


def run(args, aggregator_name: str = "fedavg"):
    """Everything `main` does with parsed arguments. Returns (api, history):
    a caller that must look at the trained state or at how cohorts were
    staged (chip_smoke.py --multichip) drives exactly the CLI's path."""
    # the tracer comes first and is installed at once, so that set-up is on
    # the record too: the data_load span, and every compile before the drive
    logger = MetricsLogger(run_dir=args.run_dir, config=vars(args))
    tracer = tracer_from_args(args, metrics_logger=logger)
    telemetry.install(tracer)
    ledger = bank = None
    try:
        with tracer.span("data_load"):   # seeds, logging, data, model
            cfg, ds, trainer = setup_run(args)
        api = FedAvgAPI(ds, cfg, trainer, aggregator_name=aggregator_name)
        chaos, guard = robustness_from_args(args)
        ledger = ledger_from_args(args, ds.client_num)
        bank = bank_from_args(args, ds.client_num, api)
        history = api.train(ckpt_dir=args.ckpt_dir, metrics_logger=logger,
                            chaos=chaos, guard=guard, tracer=tracer,
                            ledger=ledger, bank=bank)
        if getattr(args, "profile_rounds", None):
            # the round program's scope map, for tools/trace_report.py
            # --profile to join the profile's device ops with
            api.program_scopes(tracer)
    finally:
        telemetry.uninstall(tracer)
        tracer.close()
        if ledger is not None:
            ledger.close()
        if bank is not None:
            bank.close()
    logger.finish()
    if getattr(args, "trace_summary", 0):
        print(tracer.summary_table(), flush=True)
    return api, history


def main(argv=None, aggregator_name: str = "fedavg", extra_args=None):
    parser = add_args(argparse.ArgumentParser())
    if extra_args:
        extra_args(parser)
    _, history = run(parser.parse_args(argv), aggregator_name)
    return history


if __name__ == "__main__":
    main()
