"""`models/mellum.py` against `benchmarks/reference/mellum.py` (plain float32
`jax.numpy`, nothing of the program) on seeded weights at a tiny size, and
what the configuration decides: the band and the rotary setting a layer
takes from `layer_types`, YaRN's frequencies and factor, what is refused,
the MoE without a shared expert, where adapters go, and what the
`model_built` event says."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import mellum as ref  # noqa: E402
from fedml_tpu.core.trainer import NWPTrainer  # noqa: E402
from fedml_tpu.models import create_model  # noqa: E402
from fedml_tpu.models import mellum as model  # noqa: E402
from fedml_tpu.models.lora import LoRATrainer, init_lora_adapters  # noqa: E402

CELL = "tests/benchmark/cells/tiny_mellum2_lora.json"
with open(os.path.join(ROOT, CELL)) as f:
    SPEC = json.load(f)
SIZES, V, T = SPEC["sizes"], SPEC["vocab_size"], SPEC["sizes"]["seq_len"]
BENCH = "benchmarks/configs/mellum2_lora.json"


@pytest.fixture(scope="module")
def seeded():
    """(trainer, variables from the reference's init, a batch)."""
    variables = jax.jit(lambda k: ref.init(k, SIZES))(jax.random.PRNGKey(3))
    module = create_model("mellum", output_dim=V, config=SPEC)
    trainer = LoRATrainer(NWPTrainer(module, pad_id=0), rank=4, alpha=4)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(1, V, (2, T)), jnp.int32)
    y = jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], axis=1)
    return trainer, variables, {"x": x, "y": y, "mask": jnp.ones((2,), bool)}


def test_logits_loss_and_adapter_gradients_match_the_reference(seeded):
    trainer, variables, batch = seeded
    apply = ref.make_apply(SIZES)

    def ref_loss(params):
        out, _ = apply({"params": params, "lora_base": variables["lora_base"]},
                       batch["x"], True, None, "f32")
        return ref.loss(out, batch["y"], batch["mask"])[0], out

    def prog_loss(params):
        v = {"params": params, "lora_base": variables["lora_base"]}
        return trainer.loss_fn(v, batch, None, True)[0]

    with jax.default_matmul_precision("highest"):
        (want, out), g_ref = jax.value_and_grad(ref_loss, has_aux=True)(
            variables["params"])
        got, g_prog = jax.value_and_grad(prog_loss)(variables["params"])
        logits = trainer.apply(variables, batch["x"])[0]
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    head = variables["lora_base"]["lm_head"]["kernel"]
    assert np.abs(np.asarray(logits - out.states @ head)).max() < 1e-4
    flat_p, _ = jax.tree_util.tree_flatten_with_path(g_prog)
    flat_r = jax.tree.leaves(g_ref)
    assert len(flat_p) == len(flat_r) == 2 * 5 * SPEC["num_hidden_layers"]
    for (path, a), b in zip(flat_p, flat_r):
        scale = float(jnp.abs(b).max())
        assert scale > 0, path
        assert float(jnp.abs(a - b).max()) < 2e-4 * scale, path


def _attention(kind, x, params, cfg=None):
    cfg = cfg or model.MellumConfig.from_dict(SPEC)
    with jax.default_matmul_precision("highest"):
        return model.Attention(cfg, kind, jnp.float32).apply(
            {"params": params}, x)


@pytest.fixture(scope="module")
def one_layer():
    """(input, attention params, zero adapters) of the tiny sizes."""
    cfg = model.MellumConfig.from_dict(SPEC)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, T, 64))
    params = model.Attention(cfg, model.WINDOW, jnp.float32).init(
        jax.random.PRNGKey(7), x)["params"]
    zero = jax.tree.map(lambda k: {"lora_A": jnp.zeros((k.shape[0], 1)),
                                   "lora_B": jnp.zeros((1, k.shape[1]))},
                        params)
    return x, params, zero


@pytest.mark.parametrize("kind", [model.WINDOW, model.FULL])
def test_each_kind_of_layer_is_the_reference_and_not_the_other(one_layer,
                                                               kind):
    """A window layer is the band under the default rotary, a full layer
    causal under YaRN and its factor; the grouped key-value heads reach
    their query heads. Each kind's module against the reference's module of
    the same kind, and far from the other kind's."""
    x, params, zero = one_layer
    got = _attention(kind, x, params)
    with jax.default_matmul_precision("highest"):
        want = {k: ref._pieces(SPEC, 1.0, "f32", 1, T).attention(
            x, params, zero, k) for k in (model.WINDOW, model.FULL)}
    other = model.FULL if kind == model.WINDOW else model.WINDOW
    scale = float(jnp.abs(want[kind]).max())
    assert float(jnp.abs(got - want[kind]).max()) < 1e-5 * scale
    assert float(jnp.abs(got - want[other]).max()) > 1e-2 * scale


def test_the_layers_follow_layer_types():
    """The band and the rotary setting of each layer are its entry's; a
    stage of fewer layers takes the first entries of the published list."""
    cfg = model.MellumConfig.from_file(None)
    assert cfg.num_hidden_layers == 28 and cfg.layer_types == (
        model.WINDOW, model.WINDOW, model.WINDOW, model.FULL) * 7
    assert [model.layer_window(cfg, k) for k in cfg.layer_types[:4]] == [
        1024, 1024, 1024, None]
    assert [model.layer_rotary(cfg, k).rope_type
            for k in cfg.layer_types[:4]] == ["default"] * 3 + ["yarn"]
    with open(os.path.join(ROOT, BENCH)) as f:
        cut = json.load(f)
    lm = create_model("mellum", output_dim=98304, config=cut)
    assert lm.cfg.layer_types == (model.WINDOW,) * 3 + (model.FULL,)
    assert lm.describe() == {
        "layers": 4, "mixers": {"window": 3, "full": 1}, "window": 1024,
        "experts_held": 64, "experts_routed": 64}
    # the band's tile and its live tiles a head against causal's there
    assert lm.attention_tiles(8192) == {"tile": [1024, 1024], "window": 15,
                                        "causal": 36}
    swapped = create_model("mellum", output_dim=V, config={
        **SPEC, "layer_types": [model.FULL, model.WINDOW]})
    variables = swapped.init(jax.random.PRNGKey(0), jnp.ones((1, T), jnp.int32))
    assert set(variables["params"]) >= {"layers_0", "layers_1"}
    assert swapped.describe()["mixers"] == {"window": 1, "full": 1}


def test_yarn_frequencies_and_factor_are_the_reference_s():
    with open(model.PUBLISHED) as f:
        published = json.load(f)["rope_parameters"]
    for rope in (SPEC["rope_parameters"], published):
        cfg = model.MellumConfig.from_dict({**SPEC, "rope_parameters": rope})
        for kind, rotary in zip((model.WINDOW, model.FULL), cfg.rotary):
            np.testing.assert_allclose(rotary.inv_freq(128),
                                       ref.inv_freq(rope[kind], 128),
                                       rtol=1e-6)
            assert rotary.attention_factor == pytest.approx(
                ref.attention_factor(rope[kind]))
    full = model.MellumConfig.from_file(None).rotary[1]
    # the published factor is YaRN's default for a factor of 16
    assert full.attention_factor == pytest.approx(0.1 * np.log(16) + 1.0)
    freqs = full.inv_freq(128)
    base = 500000.0 ** -(np.arange(0, 128, 2) / 128)
    # extrapolated at the top, interpolated by 16 at the bottom
    assert freqs[0] == pytest.approx(base[0])
    assert freqs[-1] == pytest.approx(base[-1] / 16, rel=1e-5)


@pytest.mark.parametrize("key,value", [
    ("rope_parameters", {"sliding_attention": {"rope_type": "linear",
                                               "rope_theta": 1e4},
                         "full_attention": {"rope_type": "default",
                                            "rope_theta": 1e4}}),
    ("layer_types", ["sliding_attention", "chunked_attention"]),
    ("mlp_layer_types", ["sparse", "dense"]),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("num_shared_experts", 1), ("hidden_act", "gelu"),
    ("use_sliding_window", False), ("partial_rotary_factor", 0.5)])
def test_from_dict_refuses_what_it_does_not_build(key, value):
    name = {"rope_parameters": "rope_type",
            "num_shared_experts": "shared_experts"}.get(key, key)
    with pytest.raises(NotImplementedError, match=name):
        model.MellumConfig.from_dict({**SPEC, key: value})


def test_from_dict_needs_every_key_and_enough_layer_types():
    with pytest.raises(KeyError, match="sliding_window"):
        model.MellumConfig.from_dict(
            {k: v for k, v in SPEC.items() if k != "sliding_window"})
    with pytest.raises(ValueError, match="layer_types"):
        model.MellumConfig.from_dict({**SPEC, "num_hidden_layers": 3})


def test_a_moe_without_shared_experts_holds_no_shared_module():
    """`deepseek_v2.MoE` builds the shared SwiGLU only where there is one:
    Mellum's layer holds the router and the three expert stacks alone."""
    from fedml_tpu.models.deepseek_v2 import MoE

    cfg = model.MellumConfig.from_dict(SPEC)
    x = jnp.ones((1, 8, 64))
    params = MoE(cfg, jnp.float32).init(jax.random.PRNGKey(0), x)["params"]
    assert set(params) == {"router", "experts_gate", "experts_up",
                           "experts_down"}


def test_adapters_go_on_attention_and_router_and_nowhere_else():
    """At the benchmark's sizes, by shapes alone: 2,123,976,960 base
    parameters in bfloat16, 1,331,200 trained at rank 16, none on the
    experts, norms, embedding or head; the layout is the reference's."""
    with open(os.path.join(ROOT, BENCH)) as f:
        config = json.load(f)
    lm = create_model("mellum", output_dim=98304, config=config,
                      dtype="bfloat16")
    shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 64), jnp.int32))["params"]
    base = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    assert base == 2123976960
    assert {l.dtype for l in jax.tree.leaves(shapes)} == {jnp.dtype("bfloat16")}
    adapters = jax.eval_shape(
        lambda: init_lora_adapters(shapes, 16, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(l.shape))
               for l in jax.tree.leaves(adapters)) == 4 * 332800 == 1331200
    assert set(adapters) == {f"layers_{i}" for i in range(4)}
    for i in range(4):
        assert set(adapters[f"layers_{i}"]) == {"attn", "moe"}
        assert set(adapters[f"layers_{i}"]["attn"]) == {
            "q_proj", "k_proj", "v_proj", "o_proj"}
        assert set(adapters[f"layers_{i}"]["moe"]) == {"router"}
    ref_shapes = jax.eval_shape(lambda k: ref.init(k, config["sizes"]),
                                jax.random.PRNGKey(0))
    same = jax.tree.map(lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
                        ref_shapes["lora_base"], shapes)
    assert all(jax.tree.leaves(same))
    same = jax.tree.map(lambda a, b: a.shape == b.shape, ref_shapes["params"],
                        adapters)
    assert all(jax.tree.leaves(same))


def test_build_trainer_says_the_model_its_window_and_its_tiles():
    import argparse

    from fedml_tpu import telemetry
    from fedml_tpu.experiments import common
    from fedml_tpu.telemetry.report import fold

    args = common.add_args(argparse.ArgumentParser()).parse_args([
        "--dataset", "tokens", "--model", "mellum", "--lora_rank", "4",
        "--model_config", os.path.join(ROOT, CELL)])
    train = type("P", (), {"x": np.zeros((3, 4, T), np.int32)})
    ds = type("D", (), {"class_num": V, "meta": {"task": "nwp"},
                        "train": train})
    tracer = telemetry.Tracer()
    telemetry.install(tracer)
    try:
        common.build_trainer(args, common.config_from_args(args), ds)
    finally:
        telemetry.uninstall(tracer)
    (built,) = tracer.find_events("model_built")
    assert (built["model"], built["layers"], built["mixers"],
            built["window"]) == ("mellum", 2, {"window": 1, "full": 1}, 16)
    assert built["experts_held"] == built["experts_routed"] == 8
    assert built["attention_tiles"] == {"tile": [64, 64], "window": 1,
                                        "causal": 1}
    report = fold([{"type": "event", **e} for e in tracer.events])
    assert report["model"]["window"] == 16
    assert report["model"]["attention_tiles"]["tile"] == [64, 64]
