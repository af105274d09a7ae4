"""`models/jamba.py` against `benchmarks/reference/jamba.py` (plain float32
`jax.numpy`, nothing of the program) on seeded weights at a tiny size, and
what the configuration decides: the mixer a layer, what is refused, the tied
head, where adapters go, and what the `model_built` event says."""

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

from benchmarks.reference import jamba as ref  # noqa: E402
from fedml_tpu.core.trainer import NWPTrainer  # noqa: E402
from fedml_tpu.models import create_model  # noqa: E402
from fedml_tpu.models import jamba as model  # noqa: E402
from fedml_tpu.models.lora import LoRATrainer, init_lora_adapters  # noqa: E402

CELL = "tests/benchmark/cells/tiny_jamba2_lora.json"
with open(os.path.join(ROOT, CELL)) as f:
    SPEC = json.load(f)
SIZES, V, T = SPEC["sizes"], SPEC["vocab_size"], SPEC["sizes"]["seq_len"]


@pytest.fixture(scope="module")
def seeded():
    """(trainer, variables from the reference's init, a batch)."""
    variables = jax.jit(lambda k: ref.init(k, SIZES))(jax.random.PRNGKey(3))
    module = create_model("jamba", output_dim=V, config=SPEC)
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

    (want, out), g_ref = jax.value_and_grad(ref_loss, has_aux=True)(
        variables["params"])
    got, g_prog = jax.value_and_grad(prog_loss)(variables["params"])
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    # the head is the embedding's transpose: there is no other
    logits = trainer.apply(variables, batch["x"])[0]
    embedding = variables["lora_base"]["embed"]["embedding"]
    assert "lm_head" not in variables["lora_base"]
    assert np.abs(np.asarray(logits - out.states @ embedding.T)).max() < 1e-4
    flat_p, _ = jax.tree_util.tree_flatten_with_path(g_prog)
    flat_r = jax.tree.leaves(g_ref)
    assert len(flat_p) == len(flat_r) == 2 * sum(
        len(ref._kernels(SPEC, i)) for i in range(SPEC["num_hidden_layers"]))
    for (path, a), b in zip(flat_p, flat_r):
        scale = float(jnp.abs(b).max())
        assert scale > 0, path
        assert float(jnp.abs(a - b).max()) < 2e-4 * scale, path


def test_the_mixer_pattern_follows_offset_and_period():
    cfg = model.JambaConfig.from_file(None)
    attn = [i for i in range(cfg.num_hidden_layers)
            if cfg.is_attention_layer(i)]
    assert cfg.num_hidden_layers == 28 and attn == [7, 21]
    assert (cfg.head_dim, cfg.mamba_inner) == (128, 5120)
    with open(os.path.join(ROOT, "benchmarks/configs/jamba2_lora.json")) as f:
        cut = json.load(f)
    assert create_model("jamba", output_dim=65536, config=cut).describe() == {
        "layers": 14, "mixers": {"mamba": 13, "attention": 1}}
    lm = create_model("jamba", output_dim=V, config=SPEC)
    assert lm.describe() == {"layers": 2,
                             "mixers": {"mamba": 1, "attention": 1}}


@pytest.mark.parametrize("key,value", [
    ("num_experts", 16), ("sliding_window", 4096), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("hidden_act", "gelu"),
    ("tie_word_embeddings", False)])
def test_from_dict_refuses_what_it_does_not_build(key, value):
    with pytest.raises(NotImplementedError, match=key):
        model.JambaConfig.from_dict({**SPEC, key: value})
    with pytest.raises(KeyError, match="mamba_dt_rank"):
        model.JambaConfig.from_dict(
            {k: v for k, v in SPEC.items() if k != "mamba_dt_rank"})


def test_the_key_value_heads_reach_every_query_head():
    """One key-value head read by all four query heads: the attention layer
    alone against the reference's, which indexes the shared head per query
    head instead of repeating it."""
    cfg = model.JambaConfig.from_dict(SPEC)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, T, 64))
    attn = model.Attention(cfg, jnp.float32)
    v = attn.init(jax.random.PRNGKey(7), x)
    assert v["params"]["k_proj"]["kernel"].shape == (64, 16)
    zero = jax.tree.map(lambda k: {"lora_A": jnp.zeros((k.shape[0], 1)),
                                   "lora_B": jnp.zeros((1, k.shape[1]))},
                        v["params"])
    with jax.default_matmul_precision("highest"):
        got = attn.apply(v, x)
        want = ref._pieces(SPEC, 1.0, "f32", 1, T).attention(
            x, v["params"], zero)
    assert np.abs(np.asarray(got - want)).max() < 1e-5


def test_adapters_go_on_every_projection_and_nowhere_else():
    """At the benchmark's sizes, by shapes alone: 1,598,556,096 base
    parameters in bfloat16, 13,938,176 trained at rank 16, none on the
    convolution, `A_log`, `D`, `dt_bias`, norms or the (tied) embedding."""
    with open(os.path.join(ROOT, "benchmarks/configs/jamba2_lora.json")) as f:
        config = json.load(f)
    lm = create_model("jamba", output_dim=65536, config=config,
                      dtype="bfloat16")
    shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 64), jnp.int32))["params"]
    base = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    assert base == 1598556096
    assert {l.dtype for l in jax.tree.leaves(shapes)} == {jnp.dtype("bfloat16")}
    adapters = jax.eval_shape(
        lambda: init_lora_adapters(shapes, 16, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(l.shape))
               for l in jax.tree.leaves(adapters)) == 13938176
    assert set(adapters["layers_0"]["mamba"]) == {
        "in_proj", "x_proj", "dt_proj", "out_proj"}
    assert set(adapters["layers_7"]["attn"]) == {
        "q_proj", "k_proj", "v_proj", "o_proj"}
    assert set(adapters["layers_7"]) == {"attn", "mlp"}
    assert set(adapters) == {f"layers_{i}" for i in range(14)}
    ref_shapes = jax.eval_shape(lambda k: ref.init(k, config["sizes"]),
                                jax.random.PRNGKey(0))
    same = jax.tree.map(lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
                        ref_shapes["lora_base"], shapes)
    assert all(jax.tree.leaves(same))


def test_build_trainer_says_the_model_and_its_mixers():
    import argparse

    from fedml_tpu import telemetry
    from fedml_tpu.experiments import common
    from fedml_tpu.telemetry.report import fold

    args = common.add_args(argparse.ArgumentParser()).parse_args([
        "--dataset", "tokens", "--model", "jamba", "--lora_rank", "4",
        "--model_config", os.path.join(ROOT, CELL)])
    ds = type("D", (), {"class_num": V, "meta": {"task": "nwp"}})
    tracer = telemetry.Tracer()
    telemetry.install(tracer)
    try:
        common.build_trainer(args, common.config_from_args(args), ds)
    finally:
        telemetry.uninstall(tracer)
    (built,) = tracer.find_events("model_built")
    assert (built["model"], built["layers"], built["mixers"]) == (
        "jamba", 2, {"mamba": 1, "attention": 1})
    assert built["experts_held"] is None
    report = fold([{"type": "event", **e} for e in tracer.events])
    assert report["model"]["mixers"] == {"mamba": 1, "attention": 1}
