"""Operations a model needs, from its shapes: the reference model's
`layers()` list, one entry a thing that multiplies, nothing for
normalisation, pooling or the optimizer.

A layer is `{"kind": ...}` with, as it needs them:

  the sizes of a kind known here   `conv` (out_hw, k, cin, cout) and `dense`
                                   (cin, cout): 2 per multiply-add
  "flops"    forward operations of ONE application, for a kind this file
             does not know (attention's score and context products, a
             scan): computed by a function kept in the configuration's own
             `benchmarks/reference/` module. A layer of an unknown kind
             without it is an error, never nought
  "times"    applications a sample (default 1): a decoder applies each
             matrix once a position, a looped one once a position and loop

Training = forward + backward = 3 x forward (one product for the
activations' gradient, one for the weights'); the first layer's input
gradient is counted too, which overstates by under 1 %. Every weight is taken
as trained: under a frozen base, whose weight-gradient products are never
needed, this over-counts, and the `benchmark` PR that brings the first such
configuration brings the count for it (PERF.md section 3).
These are the operations the model NEEDS: what a program recomputes
(rematerialised activations, a blocked attention backward that rebuilds its
scores) is never counted, so a share of the peak built on this count falls
when a program recomputes more, as it should."""

from __future__ import annotations

KNOWN = {
    "conv": lambda l: 2 * l["out_hw"] ** 2 * l["k"] ** 2 * l["cin"] * l["cout"],
    "dense": lambda l: 2 * l["cin"] * l["cout"],
}


def _forward(layer: dict) -> int:
    """Forward operations a sample of one layer, all its applications."""
    if "flops" in layer:
        once = layer["flops"]
    elif layer["kind"] in KNOWN:
        once = KNOWN[layer["kind"]](layer)
    else:
        raise ValueError(f"layer kind {layer['kind']!r} is not known here "
                         f"and carries no `flops` of its own")
    return once * layer.get("times", 1)


def forward_flops_per_sample(layers: list[dict]) -> int:
    return sum(_forward(layer) for layer in layers)


def train_flops_per_sample(layers: list[dict]) -> int:
    return 3 * forward_flops_per_sample(layers)
