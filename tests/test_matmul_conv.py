"""`ops.matmul_conv.MatmulConv`: `nn.Conv` with a second lowering.

`as_matmul=True` must be the same convolution written as matrix products
(outputs and per-client gradients under `vmap` equal `nn.Conv`'s), over the
same variables tree, so that under the client `vmap` CNN_DropOut's training
step holds batched `dot_general`s and no `conv_general_dilated`; `as_matmul=
False`, which every eval program takes, must be `nn.Conv` to the jaxpr. The
engine-level tests hold a packed, dropout-on federation to the same run with
the convolutions swapped back, and the ResNets to `nn.Conv`.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms import fedavg
from fedml_tpu.algorithms.aggregators import make_aggregator
from fedml_tpu.algorithms.engine import build_round_fn, packed_lanes
from fedml_tpu.analysis.jaxpr_engine import walk_eqns
from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.trainer import ClassificationTrainer
from fedml_tpu.data import FederatedDataset, PackedClients
from fedml_tpu.models import cnn as cnn_models
from fedml_tpu.models import create_model
from fedml_tpu.ops import matmul_conv
from fedml_tpu.ops.matmul_conv import MatmulConv

CLIENTS, ROWS = 3, 4

#: kernel, padding, cin, image side
SHAPES = {
    "k3_valid_cin1": ((3, 3), "VALID", 1, 12),     # no contraction: the VPU
    "k3_valid_cin32": ((3, 3), "VALID", 32, 10),   # K = 96 a row tap
    "k5_same_cin1": ((5, 5), "SAME", 1, 9),
    "k5_same_cin8": ((5, 5), "SAME", 8, 8),        # 5 row taps of K = 40
}


def _names(jaxpr):
    return [e.primitive.name for e in walk_eqns(jaxpr)]


def _pair(shape, use_bias, dtype, features=16):
    kernel, padding, cin, side = SHAPES[shape]
    kw = dict(features=features, kernel_size=kernel, padding=padding,
              use_bias=use_bias, dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (CLIENTS, ROWS, side, side, cin))
    return MatmulConv(**kw), nn.Conv(**kw), x


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("use_bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("shape", SHAPES)
def test_matmul_form_is_the_convolution(shape, use_bias, dtype):
    ours, flax_conv, x = _pair(shape, use_bias, dtype)
    variables = jax.vmap(lambda k: flax_conv.init(k, x[0]))(
        jax.random.split(jax.random.PRNGKey(1), CLIENTS))
    target = jax.random.normal(jax.random.PRNGKey(2),
                               flax_conv.apply(jax.tree.map(
                                   lambda a: a[0], variables), x[0]).shape)

    def loss(apply):
        def f(v, xc):
            out = apply(v, xc)
            assert out.dtype == dtype
            return jnp.sum(jnp.square(out.astype(jnp.float32) - target)), out
        return jax.jit(jax.vmap(jax.value_and_grad(f, has_aux=True)))

    (_, want), g_want = loss(flax_conv.apply)(variables, x)
    (_, got), g_got = loss(lambda v, xc: ours.apply(v, xc, as_matmul=True))(
        variables, x)
    # float32 within 1e-5 of the largest value; bfloat16 within its rounding
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    for u, v in zip(jax.tree.leaves((got, g_got)),
                    jax.tree.leaves((want, g_want))):
        u, v = np.asarray(u, np.float32), np.asarray(v, np.float32)
        assert u.shape == v.shape
        np.testing.assert_allclose(u, v, rtol=0, atol=tol * np.abs(v).max())
    # the unbatched call, and the other lowering of the same module
    np.testing.assert_array_equal(
        np.asarray(ours.apply(jax.tree.map(lambda a: a[0], variables), x[0]),
                   np.float32),
        np.asarray(want[0], np.float32))


@pytest.mark.parametrize("as_matmul", [False, True])
@pytest.mark.parametrize("use_bias", [True, False], ids=["bias", "nobias"])
@pytest.mark.parametrize("shape", SHAPES)
def test_variables_tree_is_flax_convs(shape, use_bias, as_matmul):
    ours, flax_conv, x = _pair(shape, use_bias, jnp.bfloat16)
    key = jax.random.PRNGKey(7)
    want = flax_conv.init(key, x[0])
    got = ours.init(key, x[0], as_matmul)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert sorted(got["params"]) == (["bias", "kernel"] if use_bias
                                     else ["kernel"])
    for u, v in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (u.shape, u.dtype) == (v.shape, v.dtype) and u.dtype == (
            jnp.float32)
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


@pytest.mark.parametrize("kw", [
    dict(strides=(2, 2)), dict(kernel_dilation=2), dict(padding=1),
    dict(feature_group_count=2), dict(kernel_size=(3,))])
def test_what_the_matmul_form_does_not_take_raises(kw):
    module = MatmulConv(**{**dict(features=4, kernel_size=(3, 3)), **kw})
    x = jnp.zeros((2, 8, 8, 2) if len(module.kernel_size) == 2 else (2, 8, 2))
    variables = module.init(jax.random.PRNGKey(0), x)   # nn.Conv takes all
    with pytest.raises(ValueError, match="as_matmul"):
        module.apply(variables, x, as_matmul=True)


# ------------------------------------------------------------- CNN_DropOut

class _PlainConv(nn.Conv):
    """`nn.Conv` under MatmulConv's call signature: the parent's model."""

    def __call__(self, x, as_matmul=False):
        return super().__call__(x)


def _cnn_step_jaxpr(train, dtype=jnp.float32):
    model = cnn_models.CNN_DropOut(output_dim=5, dtype=dtype)
    x = jnp.zeros((CLIENTS, ROWS, 28, 28, 1))
    y = jnp.zeros((CLIENTS, ROWS), jnp.int32)
    variables = jax.vmap(lambda k: model.init(k, x[0]))(
        jax.random.split(jax.random.PRNGKey(0), CLIENTS))

    def loss(v, xc, yc, key):
        logits = model.apply(v, xc, train=train, rngs={"dropout": key})
        return -jnp.take_along_axis(jax.nn.log_softmax(logits), yc[:, None],
                                    axis=-1).mean()

    keys = jax.random.split(jax.random.PRNGKey(1), CLIENTS)
    return variables, jax.make_jaxpr(jax.vmap(jax.grad(loss)))(
        variables, x, y, keys)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_training_step_is_batched_matmuls(dtype):
    variables, jaxpr = _cnn_step_jaxpr(True, dtype)
    assert {k: sorted(v) for k, v in variables["params"].items()} == {
        k: ["bias", "kernel"]
        for k in ("conv2d_1", "conv2d_2", "linear_1", "linear_2")}
    assert variables["params"]["conv2d_2"]["kernel"].shape == (
        CLIENTS, 3, 3, 32, 64)
    names = _names(jaxpr)
    assert "conv_general_dilated" not in names
    dots = [e for e in walk_eqns(jaxpr) if e.primitive.name == "dot_general"]
    # conv2d_1 (one input channel): broadcast multiply-adds in float32, else
    # three row taps x (forward, weight gradient; its input is data);
    # conv2d_2: three row taps x (forward, input gradient, weight gradient);
    # the two dense layers: 3 + 3
    assert len(dots) == (0 if dtype == jnp.float32 else 6) + 9 + 6
    for e in dots:
        (_, _), (lhs_batch, rhs_batch) = e.params["dimension_numbers"]
        assert len(lhs_batch) == len(rhs_batch) == 1
        assert e.invars[0].aval.shape[lhs_batch[0]] == CLIENTS
        assert e.invars[1].aval.shape[rhs_batch[0]] == CLIENTS
        assert e.params["precision"] is None   # the context's, as nn.Conv
        if dtype == jnp.bfloat16:
            assert all(v.aval.dtype == jnp.bfloat16 for v in e.invars)


def test_eval_step_is_the_parents_jaxpr(monkeypatch):
    model = cnn_models.CNN_DropOut(output_dim=5)
    x = jnp.zeros((ROWS, 28, 28, 1))
    variables = model.init(jax.random.PRNGKey(0), x)

    def jaxprs():
        fwd = jax.make_jaxpr(lambda v, x: model.apply(v, x, train=False))(
            variables, x)
        return str(fwd), str(_cnn_step_jaxpr(False)[1])

    ours = jaxprs()
    monkeypatch.setattr(cnn_models, "MatmulConv", _PlainConv)
    parents = jaxprs()
    assert ours == parents
    assert ours[0].count("conv_general_dilated") == 2
    # ...and with the swap the training step is the grouped convolution again
    assert "conv_general_dilated" in _names(_cnn_step_jaxpr(True)[1])


# ------------------------------------------------------------ engine level

def _writers(clients=12, n_max=24, side=12, classes=5):
    """A tiny `flagship`: ragged writers, one of them full."""
    rng = np.random.RandomState(3)
    counts = np.clip(rng.lognormal(2.0, 0.6, clients), 2, n_max).astype(
        np.int32)
    counts[0] = n_max
    y = rng.randint(0, classes, (clients, n_max)).astype(np.int32)
    protos = rng.randn(classes, side, side, 1).astype(np.float32)
    x = 0.6 * protos[y] + 0.35 * rng.randn(
        clients, n_max, side, side, 1).astype(np.float32)
    train = PackedClients(x, y, counts)
    return FederatedDataset(
        name="writers", train=train, test=train,
        train_global=(x[:, 0], y[:, 0]), test_global=(x[:, 0], y[:, 0]),
        class_num=classes)


def test_packed_federation_trains_as_with_flax_convs(monkeypatch):
    """Two rounds of packed lanes, uneven counts and dropout through
    `FedAvgAPI.train()`: the global model of the same run with CNN_DropOut's
    convolutions swapped back to `nn.Conv`."""
    ds = _writers()
    cfg = FedConfig(client_num_in_total=12, client_num_per_round=6,
                    comm_round=2, batch_size=4, epochs=1, lr=0.1,
                    frequency_of_the_test=100, pipeline_depth=2)

    def run():
        api = fedavg.FedAvgAPI(ds, cfg, ClassificationTrainer(
            create_model("cnn", output_dim=ds.class_num)))
        start = jax.tree.map(np.asarray, api.global_variables)
        api.train()
        assert api._lanes == packed_lanes(ds.train.counts, 6, 24, 4) < 6
        return start, api.global_variables

    start, got = run()
    monkeypatch.setattr(cnn_models, "MatmulConv", _PlainConv)
    _, want = run()
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for u, v, w0 in zip(*map(jax.tree.leaves, (got, want, start))):
        np.testing.assert_allclose(np.asarray(u), np.asarray(v), rtol=0,
                                   atol=1e-5)
        assert np.abs(np.asarray(v) - w0).max() > 1e-4   # two rounds trained


def test_resnet56_round_keeps_flax_convs(monkeypatch):
    """`cross_silo`'s model is not a user of the new module in this PR: its
    round program's convolutions are `conv_general_dilated`, as the parent's."""
    def boom(*a, **k):
        raise AssertionError("the ResNets keep nn.Conv / GroupableConv")

    monkeypatch.setattr(matmul_conv, "conv_as_matmul", boom)
    trainer = ClassificationTrainer(create_model(
        "resnet56", output_dim=10, dtype=jnp.bfloat16))
    cfg = FedConfig(model="resnet56", batch_size=2, epochs=1, lr=0.001,
                    wd=0.001, client_num_per_round=2, dtype="bfloat16")
    agg = make_aggregator("fedavg", cfg)
    x = jnp.zeros((2, 4, 8, 8, 3))
    y = jnp.zeros((2, 4), jnp.int32)
    gv = jax.eval_shape(lambda: trainer.init(jax.random.PRNGKey(0), x[0, :1]))
    state = jax.eval_shape(agg.init_state, gv)
    jaxpr = jax.make_jaxpr(build_round_fn(trainer, cfg, agg))(
        gv, state, x, y, jnp.full((2,), 4, jnp.int32), jax.random.PRNGKey(0))
    names = _names(jaxpr)
    convs = sum(1 for leaf in jax.tree.leaves(gv["params"])
                if len(leaf.shape) == 4)
    # forward, input gradient, weight gradient of every kernel but the
    # first's input gradient
    assert names.count("conv_general_dilated") == 3 * convs - 1
