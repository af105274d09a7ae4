"""`models/kimi_linear.py` against `benchmarks/reference/kimi_linear.py`
(plain float32 `jax.numpy`, nothing of the program) on seeded weights at a
tiny size, and what the configuration decides: the mixer a layer, the
router (sigmoid scores, selection by score + bias, renormalised, scaled),
NoPE, what is refused, the share of experts a chip holds, where adapters
go, and what a trace says of it."""

import copy
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

from benchmarks.reference import kimi_linear as ref  # noqa: E402
from fedml_tpu.core.trainer import NWPTrainer  # noqa: E402
from fedml_tpu.models import create_model  # noqa: E402
from fedml_tpu.models import deepseek_v2 as shared  # noqa: E402
from fedml_tpu.models import kimi_linear as model  # noqa: E402
from fedml_tpu.models.lora import LoRATrainer, init_lora_adapters  # noqa: E402

CELL = "tests/benchmark/cells/tiny_kimi_linear_lora.json"
with open(os.path.join(ROOT, CELL)) as f:
    SPEC = json.load(f)
SIZES, V, T = SPEC["sizes"], SPEC["vocab_size"], SPEC["sizes"]["seq_len"]


@pytest.fixture(scope="module")
def seeded():
    """(trainer, variables from the reference's init, a batch)."""
    variables = jax.jit(lambda k: ref.init(k, SIZES))(jax.random.PRNGKey(3))
    module = create_model("kimi_linear", output_dim=V, config=SPEC)
    trainer = LoRATrainer(NWPTrainer(module, pad_id=0), rank=4, alpha=4)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(1, V, (3, T)), jnp.int32)
    y = jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], axis=1)
    return trainer, variables, {"x": x, "y": y, "mask": jnp.ones((3,), bool)}


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
    logits = trainer.apply(variables, batch["x"])[0]
    want_logits = out.states @ variables["lora_base"]["lm_head"]["kernel"]
    assert np.abs(np.asarray(logits - want_logits)).max() < 1e-4
    flat_p, _ = jax.tree_util.tree_flatten_with_path(g_prog)
    flat_r = jax.tree.leaves(g_ref)
    assert len(flat_p) == len(flat_r) == 2 * sum(
        len(ref._kernels(SPEC, i)) for i in range(SPEC["num_hidden_layers"]))
    for (path, a), b in zip(flat_p, flat_r):
        scale = float(jnp.abs(b).max())
        assert scale > 0, path
        assert float(jnp.abs(a - b).max()) < 2e-4 * scale, path


def test_the_mixer_pattern_follows_the_published_lists():
    cfg = model.KimiLinearConfig.from_file(None)
    kda = [cfg.is_kda_layer(i) for i in range(cfg.num_hidden_layers)]
    assert cfg.num_hidden_layers == 27 and sum(kda) == 20
    assert [i + 1 for i, k in enumerate(kda) if not k] == [4, 8, 12, 16, 20,
                                                           24, 27]
    assert [cfg.is_moe_layer(i) for i in range(3)] == [False, True, True]
    assert (cfg.n_routed_experts, cfg.experts_held) == (256, None)
    lm = create_model("kimi_linear", output_dim=V, config=SPEC)
    assert lm.describe() == {"layers": 3, "mixers": {"kda": 2, "mla": 1},
                             "experts_held": 2, "experts_routed": 8,
                             "experts_first": 0}


def test_a_layer_in_both_lists_or_in_neither_is_refused():
    spec = copy.deepcopy(SPEC)
    spec["linear_attn_config"]["full_attn_layers"] = [2, 3]
    with pytest.raises(ValueError, match="exactly one"):
        model.KimiLinearConfig.from_dict(spec)
    spec["linear_attn_config"]["full_attn_layers"] = []
    with pytest.raises(ValueError, match="exactly one"):
        model.KimiLinearConfig.from_dict(spec)


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("num_expert_group", 8),
    ("num_nextn_predict_layers", 1), ("rope_scaling", {"type": "yarn"}),
    ("tie_word_embeddings", True), ("mla_use_nope", False),
    ("hidden_act", "gelu"), ("moe_router_activation_func", "tanh")])
def test_from_dict_refuses_what_it_does_not_build(key, value):
    with pytest.raises(NotImplementedError, match=key):
        model.KimiLinearConfig.from_dict({**SPEC, key: value})
    with pytest.raises(KeyError, match="moe_renormalize"):
        model.KimiLinearConfig.from_dict(
            {k: v for k, v in SPEC.items() if k != "moe_renormalize"})


def _moe(spec, x, params):
    cfg = model.KimiLinearConfig.from_dict(spec)
    return shared.MoE(cfg, jnp.float32).apply({"params": params}, x)


def _moe_params(key, d=64, f=48, e=8):
    ks = jax.random.split(key, 8)
    n = lambda k, s, fan: jax.random.normal(k, s) * fan ** -0.5  # noqa: E731
    return {"router": {"kernel": n(ks[0], (d, e), d)},
            "selection_bias": jax.random.uniform(ks[1], (e,), minval=-0.3,
                                                 maxval=0.3),
            "experts_gate": n(ks[2], (e, d, f), d),
            "experts_up": n(ks[3], (e, d, f), d),
            "experts_down": n(ks[4], (e, f, d), f),
            "shared": {"gate_proj": {"kernel": n(ks[5], (d, f), d)},
                       "up_proj": {"kernel": n(ks[6], (d, f), d)},
                       "down_proj": {"kernel": n(ks[7], (f, d), f)}}}


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


UNCUT = {**SPEC, "num_experts": 8, "expert_share": {"of": 1, "index": 0}}


def test_sigmoid_scores_select_by_score_plus_bias_renormalise_and_scale():
    p = _moe_params(jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64))
    y, load, path = _moe(UNCUT, x, p)
    assert path is None              # every expert held: no share's path
    flat = x.reshape(-1, 64)
    s = np.asarray(jax.nn.sigmoid(flat @ p["router"]["kernel"]), np.float64)
    sel = s + np.asarray(p["selection_bias"], np.float64)
    want = np.zeros((24, 64))
    counts = np.zeros(8)
    picked_differs = False
    for n in range(24):
        top = np.argsort(-sel[n])[:2]
        picked_differs |= set(top) != set(np.argsort(-s[n])[:2])
        w = 2.446 * s[n, top] / (s[n, top].sum() + 1e-20)
        for wi, e in zip(w, top):
            counts[e] += 1
            want[n] += wi * np.asarray(_swiglu(
                flat[n], p["experts_gate"][e], p["experts_up"][e],
                p["experts_down"][e]))
    want += np.asarray(_swiglu(flat, *[p["shared"][k]["kernel"] for k in (
        "gate_proj", "up_proj", "down_proj")]))
    assert picked_differs            # the bias changes who is chosen
    assert np.abs(np.asarray(y[0]) - want).max() < 1e-4
    assert np.asarray(load).tolist() == counts.tolist()


@pytest.mark.parametrize("tokens,room,fallbacks", [
    (16, 2.0, 0),       # 64 pairs: the bound IS the worst case
    (512, 2.0, 0),      # 2,048 pairs: the bounded buffer, 1,280 rows
    (512, 0.01, 3)])    # the same with no room (384 rows): the worst case
def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(
        monkeypatch, tokens, room, fallbacks):
    from fedml_tpu.ops import moe

    monkeypatch.setattr(moe, "SHARE_ROOM", room)
    p = _moe_params(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, tokens, 64))
    whole, load, _ = _moe(UNCUT, x, p)
    once = _swiglu(x, *[p["shared"][k]["kernel"] for k in (
        "gate_proj", "up_proj", "down_proj")])
    parts, took_worst = [], 0
    pairs = 2 * tokens * 2
    assert (moe.share_rows(pairs, 2, 8) < moe._worst_rows(pairs, 2, 128)) == (
        tokens == 512)
    for i in range(4):
        mine = {**p, **{k: p[k][2 * i:2 * i + 2] for k in (
            "experts_gate", "experts_up", "experts_down")}}
        y, share_load, took = _moe(
            {**SPEC, "expert_share": {"of": 4, "index": i}}, x, mine)
        assert np.asarray(share_load).tolist() == np.asarray(load).tolist()
        # the path is the one the held experts' rows (tile filler counted)
        # call for, and the sum is the uncut layer's on either
        rows = sum(-(-int(c) // 128) * 128
                   for c in np.asarray(load)[2 * i:2 * i + 2])
        fell = float(rows > moe.share_rows(pairs, 2, 8))
        assert np.asarray(took).tolist() == [1.0 - fell, fell]
        took_worst += fell
        parts.append(y - once)
    assert np.abs(np.asarray(sum(parts) + once - whole)).max() < 2e-4
    assert took_worst == fallbacks
    # and a share's gradient reaches x and the router through held pairs only
    g = jax.grad(lambda x: _moe({**SPEC, "expert_share": {"of": 4, "index": 3}},
                                x, mine)[0].sum())(x)
    assert bool(jnp.isfinite(g).all())


def test_mla_without_rotation_differs_from_with():
    from benchmarks.probes import kimi_linear_controls as controls

    cfg = model.KimiLinearConfig.from_dict(SPEC)
    mla = shared.MLA(cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, T, 64))
    v = mla.init(jax.random.PRNGKey(7), x)
    nope = mla.apply(v, x)
    assert shared.softmax_scale(cfg) == pytest.approx(24 ** -0.5)
    with controls.broken("rotary"):     # it brings the rope values too
        rotated = mla.apply(v, x)
    assert not hasattr(model.KimiLinearConfig, "rope_factor")
    assert float(jnp.abs(rotated - nope).max()) > 1e-3
    # the first position is turned by no angle
    assert float(jnp.abs(rotated[:, 0] - nope[:, 0]).max()) < 1e-5


def test_moe_load_counts_every_router_output_and_held_the_share(seeded):
    trainer, variables, batch = seeded
    from fedml_tpu.telemetry.records import moe_load_summary

    _, aux = trainer.inner.module.apply(
        trainer.merged_variables(variables), batch["x"], method="hidden")
    from fedml_tpu.algorithms.fedavg import _experts_held

    load, held = np.asarray(aux["moe_load"]), _experts_held(trainer)
    # which experts are held is no data; which path their dispatch took is
    assert set(aux) == {"moe_load", "moe_path"}
    assert np.asarray(aux["moe_path"]).tolist() == [2.0, 0.0]  # 2 expert layers
    assert load.shape == (2, 8) and held == (0, 2)
    assert load.sum(-1).tolist() == [3 * T * 2] * 2    # tokens x top-2 a layer
    said = moe_load_summary(load, held)
    assert said["held"] == load[:, :2].sum() and said["held_max"] == load[:, :2].max()
    assert said["held_mean"] == load[:, :2].mean() and said["max"] == load.max()
    assert "held" not in moe_load_summary(load)
    with_path = moe_load_summary(load, held, aux["moe_path"])
    assert (with_path["bounded"], with_path["fallback"]) == (2.0, 0.0)
    assert not {"bounded", "fallback"} & set(said)


def test_the_record_flush_says_held_and_build_trainer_says_the_model():
    import argparse

    from fedml_tpu import telemetry
    from fedml_tpu.experiments import common
    from fedml_tpu.telemetry.records import RoundRecordLog

    tracer = telemetry.Tracer()
    log = RoundRecordLog(tracer, [], experts_held=(0, 2))
    log.add({"round": 2, "round_time": 0.1,
             "_moe_load": jnp.array([[3.0, 0.0, 5.0, 4.0]]),
             "_moe_path": jnp.array([31.0, 1.0])})
    log.flush(2)
    (event,) = tracer.find_events("moe_load")
    assert (event["held"], event["held_max"], event["held_mean"],
            event["held_empty"], event["max"]) == (3.0, 3.0, 1.5, 1, 5.0)
    assert (event["bounded"], event["fallback"]) == (31.0, 1.0)
    assert "_moe_path" not in log.history[0]
    # a model that holds every expert says neither
    whole = telemetry.Tracer()
    log = RoundRecordLog(whole, [])
    log.add({"round": 0, "round_time": 0.1,
             "_moe_load": jnp.array([[3.0, 0.0, 5.0, 4.0]])})
    log.flush(0)
    assert set(whole.find_events("moe_load")[0]) & {
        "held", "bounded", "fallback"} == set()

    args = common.add_args(argparse.ArgumentParser()).parse_args([
        "--dataset", "tokens", "--model", "kimi_linear", "--lora_rank", "4",
        "--model_config", os.path.join(ROOT, CELL)])
    ds = type("D", (), {"class_num": V, "meta": {"task": "nwp"}})
    telemetry.install(tracer)
    try:
        common.build_trainer(args, common.config_from_args(args), ds)
    finally:
        telemetry.uninstall(tracer)
    (built,) = tracer.find_events("model_built")
    assert (built["model"], built["mixers"], built["experts_held"],
            built["experts_routed"]) == ("kimi_linear", {"kda": 2, "mla": 1},
                                         2, 8)
    from fedml_tpu.telemetry.report import fold
    report = fold([{"type": "event", **e} for e in tracer.events])
    assert report["model"]["mixers"] == {"kda": 2, "mla": 1}
    assert report["moe_load"]["held"] == 3.0
    assert (report["moe_load"]["bounded"],
            report["moe_load"]["fallback"]) == (31.0, 1.0)


def test_a_model_that_holds_every_expert_says_no_share():
    from fedml_tpu.algorithms.fedavg import _experts_held

    uncut = {k: v for k, v in SPEC.items() if k != "expert_share"}
    module = create_model("kimi_linear", output_dim=V, config=uncut)
    assert _experts_held(NWPTrainer(module)) is None
    assert _experts_held(NWPTrainer(create_model("lr", output_dim=4))) is None


@pytest.mark.parametrize("shape,dtype,want", [
    ((20, 8, 4096), np.int32, 8),        # kimi_linear_lora: 32,768 tokens
    ((20, 16, 1024), np.int32, 20),      # dsv2lite_lora keeps its one step
    ((3400, 480, 28, 28, 1), np.float32, 8),     # flagship: by samples
    ((10, 5000, 32, 32, 3), np.float32, 1)])     # cross_silo
def test_an_eval_step_counts_the_tokens_of_long_sequences(shape, dtype, want):
    from fedml_tpu.algorithms.fedavg import _eval_chunk

    assert _eval_chunk(jax.ShapeDtypeStruct(shape, dtype), shape[0]) == want


def test_adapters_go_on_every_projection_and_nowhere_else():
    """At the benchmark's sizes, by shapes alone: 4,418,560 trained
    parameters at rank 16, none on the convolution taps, `A_log`, `dt_bias`,
    norms, the selection bias, routed experts, embedding or head."""
    with open(os.path.join(ROOT, "benchmarks/configs/kimi_linear_lora.json")) as f:
        config = json.load(f)
    lm = create_model("kimi_linear", output_dim=40960, config=config,
                      dtype="bfloat16")
    shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 64), jnp.int32))["params"]
    base = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    assert base == 2282253184
    assert {l.dtype for l in jax.tree.leaves(shapes)} == {jnp.dtype("bfloat16")}
    adapters = jax.eval_shape(
        lambda: init_lora_adapters(shapes, 16, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(l.shape))
               for l in jax.tree.leaves(adapters)) == 4418560
    assert set(adapters["layers_1"]["kda"]) == {
        "q_proj", "k_proj", "v_proj", "o_proj", "f_a_proj", "f_b_proj",
        "g_a_proj", "g_b_proj", "b_proj"}
    assert set(adapters["layers_1"]["moe"]) == {"router", "shared"}
    assert set(adapters) == {f"layers_{i}" for i in range(5)}
    ref_shapes = jax.eval_shape(lambda k: ref.init(k, config["sizes"]),
                                jax.random.PRNGKey(0))
    same = jax.tree.map(lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
                        ref_shapes["lora_base"], shapes)
    assert all(jax.tree.leaves(same))
