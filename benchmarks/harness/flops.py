"""Operations a model needs, from its shapes: 2 per multiply-add of every
convolution and dense layer (the reference model's `layers()` list), nothing
for normalisation, pooling or the optimizer. Training = forward + backward =
3 x forward (one product for the activations' gradient, one for the
weights'); the first layer's input gradient is counted too, which overstates
by under 1 %."""

from __future__ import annotations


def forward_flops_per_sample(layers: list[dict]) -> int:
    total = 0
    for layer in layers:
        if layer["kind"] == "conv":
            total += (2 * layer["out_hw"] ** 2 * layer["k"] ** 2
                      * layer["cin"] * layer["cout"])
        elif layer["kind"] == "dense":
            total += 2 * layer["cin"] * layer["cout"]
        else:
            raise ValueError(f"unknown layer kind {layer['kind']!r}")
    return total


def train_flops_per_sample(layers: list[dict]) -> int:
    return 3 * forward_flops_per_sample(layers)
