"""`ssm.scan_roofline`: the selective scan's Pallas calls in the device
trace, by the name pattern in the metric's file, against the HBM bytes the
traced rounds need them to move (the configuration's reference module counts
them) at the chip's bandwidth. Nothing where the program has no such call."""

import importlib
import re

from benchmarks.layer_metrics.attention_kernel_roofline import (
    kernel_seconds, traced_sequences)


def needed_bytes(config: dict, sequences: int, x_bytes: int) -> int:
    ref = importlib.import_module("benchmarks.reference." + config["reference"])
    cfg = ref.published(config["sizes"])
    once = ref.scan_bytes(config["sizes"]["seq_len"],
                          cfg["mamba_expand"] * cfg["hidden_size"],
                          cfg["mamba_d_state"], x_bytes)
    return once * ref.mixers(config["sizes"])["mamba"] * sequences


def read(ctx, params):
    import jax.numpy as jnp

    seconds = kernel_seconds(ctx, re.compile(params["ops"]))
    sequences = traced_sequences(ctx)
    if not seconds or not sequences or ctx["peaks"] is None:
        return None
    need = needed_bytes(ctx["spec"]["config"], sequences,
                        jnp.dtype(ctx["dtype"]).itemsize)
    return 100.0 * need / seconds / ctx["peaks"]["hbm_bytes_per_s"]
