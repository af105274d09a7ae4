"""fednas mini-ladder (VERDICT weak #5): decompose the DARTS search rung.

The headline fednas number (one federated search round, 4 silos
x 256 CIFAR) is a single opaque figure. This ladder times the pieces of
ONE local search step at the same geometry (channels=8, layers=4, batch
64, 32x32x3), under both f32 and the PR 1 bf16 knob:

  fwd          supernet forward only (all |PRIMITIVES| candidate ops run
               per edge — the mixed-op weighted sum needs every branch)
  single_op    same depth/width but PRIMITIVES reduced to sep_conv_3x3 —
               the cost a DISCRETIZED architecture would pay; the gap to
               `fwd` is the mixed-op overhead
  w_fwd_bwd    weight loss fwd+bwd (value_and_grad over params)
  alpha_step   first-order arch gradient: grad_alpha(L_val) +
               lambda_train * grad_alpha(L_train), plus the adam update
  full_step    the real build_search_step step (arch step + weight step)

Emits one JSON line per rung: {"rung", "dtype", "ms", "samples_per_sec"}.
Knobs: LADDER_BS / LADDER_CHANNELS / LADDER_LAYERS / LADDER_INNER.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import fedml_tpu.models.darts as darts_mod
from fedml_tpu.algorithms.fednas import NASState, build_search_step
from fedml_tpu.core.config import FedConfig
from fedml_tpu.models.darts import DARTSNetwork, init_alphas

BS = int(os.environ.get("LADDER_BS", 64))
CH = int(os.environ.get("LADDER_CHANNELS", 8))
LAYERS = int(os.environ.get("LADDER_LAYERS", 4))
REPS = int(os.environ.get("LADDER_REPS", 3))
INNER = int(os.environ.get("LADDER_INNER", 2))
LAMBDA_TRAIN = 1.0


def _time(fn, *args):
    jax.block_until_ready(fn(*args))  # compile + warmup
    best = float("inf")
    for _ in range(REPS):
        t0 = time.perf_counter()
        for _ in range(INNER):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / INNER)
    return best


def _build(dtype, primitives=None):
    saved = darts_mod.PRIMITIVES
    if primitives is not None:
        darts_mod.PRIMITIVES = primitives
    try:
        net = DARTSNetwork(output_dim=10, channels=CH, layers=LAYERS,
                           dtype=dtype)
        rng = jax.random.PRNGKey(0)
        an, ar = init_alphas(jax.random.fold_in(rng, 1))
        x = jax.random.normal(jax.random.fold_in(rng, 2), (BS, 32, 32, 3),
                              jnp.float32)
        y = jax.random.randint(jax.random.fold_in(rng, 3), (BS,), 0, 10)
        params = net.init({"params": rng}, x, an, ar, train=True)["params"]
    finally:
        darts_mod.PRIMITIVES = saved
    return net, params, (an, ar), x, y


def _ce(net, params, alphas, x, y):
    logits = net.apply({"params": params}, x, alphas[0], alphas[1],
                       train=True)
    per = optax.softmax_cross_entropy_with_integer_labels(logits, y)
    return per.mean()


def _emit(rung, dtype_name, dt):
    print(json.dumps({"rung": rung, "dtype": dtype_name,
                      "ms": round(dt * 1e3, 2),
                      "samples_per_sec": round(BS / dt, 1)}))


def run(dtype_name):
    dtype = jnp.bfloat16 if dtype_name == "bfloat16" else None
    net, params, alphas, x, y = _build(dtype)

    fwd = jax.jit(lambda p, a: _ce(net, p, a, x, y))
    _emit("fwd", dtype_name, _time(fwd, params, alphas))

    # mixed-op overhead probe: same macro-architecture, ONE op per edge.
    # PRIMITIVES is reduced for both init and trace, so the single-op net's
    # params are its own — this is the discretized-net cost, not a share of
    # the supernet's params. sep_conv_3x3 is DARTS's workhorse op.
    saved = darts_mod.PRIMITIVES
    darts_mod.PRIMITIVES = ("sep_conv_3x3",)
    try:
        net1, params1, alphas1, _, _ = _build(dtype,
                                              primitives=("sep_conv_3x3",))
        single = jax.jit(lambda p, a: _ce(net1, p, a, x, y))
        _emit("single_op", dtype_name, _time(single, params1, alphas1))
    finally:
        darts_mod.PRIMITIVES = saved

    wfb = jax.jit(lambda p, a: jax.value_and_grad(
        lambda pp: _ce(net, pp, a, x, y))(p))
    _emit("w_fwd_bwd", dtype_name, _time(wfb, params, alphas))

    a_opt = optax.chain(optax.add_decayed_weights(1e-3),
                        optax.adam(3e-4, b1=0.5, b2=0.999))

    def alpha_step(p, a, a_opt_state):
        g_val = jax.grad(lambda aa: _ce(net, p, aa, x, y))(a)
        g_tr = jax.grad(lambda aa: _ce(net, p, aa, x, y))(a)
        g = jax.tree.map(lambda gv, gt: gv + LAMBDA_TRAIN * gt, g_val, g_tr)
        upd, a_opt_state = a_opt.update(g, a_opt_state, a)
        return optax.apply_updates(a, upd), a_opt_state

    astep = jax.jit(alpha_step)
    _emit("alpha_step", dtype_name,
          _time(astep, params, alphas, a_opt.init(alphas)))

    cfg = FedConfig(batch_size=BS, epochs=1, lr=0.025, momentum=0.9,
                    wd=3e-4, dtype=dtype_name)
    step, w_opt, a_opt2 = build_search_step(net, cfg,
                                            lambda_train=LAMBDA_TRAIN)
    state = NASState(params, alphas, w_opt.init(params),
                     a_opt2.init(alphas))
    mask = jnp.ones((BS,), jnp.float32)
    full = jax.jit(lambda s: step(s, (x, y, mask), (x, y),
                                  jnp.float32(0.025)))
    _emit("full_step", dtype_name, _time(full, state))


def main():
    print(f"# devices: {jax.devices()}  bs={BS} ch={CH} layers={LAYERS}")
    for dtype_name in ("float32", "bfloat16"):
        run(dtype_name)


if __name__ == "__main__":
    main()
