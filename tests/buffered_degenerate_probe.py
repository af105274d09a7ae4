"""Subprocess probe for the ISSUE-9 degenerate bit-identity acceptance pin.

Run OUTSIDE the fast suite's --xla_backend_optimization_level=0 hack: at
opt-0, XLA CPU duplicates the optax momentum subexpression into the params
output and contracts the two copies differently between the fused synchronous
round program and the standalone commit program — a 1-ULP params drift with
bitwise-equal momenta. Default codegen contracts both the same way, and the
degenerate buffered config (buffer_size = cohort, staleness_alpha = 0, no
stragglers) is then bit-identical to the synchronous loop for fedavg AND
fedopt-with-momentum, eager and depth-2 pipelined.

tests/test_buffered.py::test_degenerate_fedopt_bitwise_at_default_codegen
runs this file in a subprocess with the opt-0 flag stripped and asserts the
BITWISE OK line. Exit code 0 = all comparisons bitwise-equal.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np


def _run(ds, aggregator_name, **kw):
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.core.trainer import ClassificationTrainer
    from fedml_tpu.models.registry import create_model

    cfg = FedConfig(dataset="mnist", model="lr", batch_size=8, lr=0.05,
                    client_num_in_total=8, client_num_per_round=8, seed=0,
                    comm_round=3, server_optimizer="sgd", server_lr=1.0,
                    server_momentum=0.9, **kw)
    trainer = ClassificationTrainer(
        create_model("lr", output_dim=ds.class_num))
    api = FedAvgAPI(ds, cfg, trainer, aggregator_name=aggregator_name)
    api.train()
    return api


def _bitwise(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def main() -> int:
    from fedml_tpu.data.registry import load_dataset

    from fedml_tpu.utils.cache import enable_compile_cache

    enable_compile_cache(min_compile_secs=0.5)
    ds = load_dataset("mnist", client_num_in_total=8,
                      partition_method="homo", seed=0)
    for agg in ("fedavg", "fedopt"):
        sync = _run(ds, agg)
        for depth in (0, 2):
            buf = _run(ds, agg, buffer_size=8, staleness_alpha=0.0,
                       pipeline_depth=depth)
            if not _bitwise(sync.global_variables, buf.global_variables):
                print(f"FAIL params {agg} depth={depth}")
                return 1
            if not _bitwise(sync.agg_state, buf.agg_state):
                print(f"FAIL agg_state {agg} depth={depth}")
                return 1
    print("BITWISE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
