"""The DeepSeek-V2 decoder (`fedml_tpu/models/deepseek_v2.py`) at a tiny
size on the CPU (hidden 64, 8 experts top-2, 1 dense + 2 expert layers, T 32,
vocabulary 256): against the plain reference of the benchmark
(`benchmarks/reference/deepseek_v2_lite.py`, which imports nothing of the
program) on the reference's seeded weights — logits, loss, adapter gradients;
the next-token loss over blocks of tokens against the whole batch's logits;
and a bfloat16 base under float32 adapters, bitwise unchanged by two rounds
of the normal FedAvg path."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmarks.reference import deepseek_v2_lite as ref
from fedml_tpu.core.trainer import NWPTrainer
from fedml_tpu.models.deepseek_v2 import (DeepseekV2Config, softmax_scale,
                                          yarn_inv_freq)
from fedml_tpu.models.lora import LoRATrainer
from fedml_tpu.models.registry import create_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tests/benchmark/cells/tiny_dsv2lite_lora.json"
with open(os.path.join(ROOT, CELL)) as f:
    SPEC = json.load(f)
SIZES = SPEC["sizes"]
B, T, V = 4, SIZES["seq_len"], SPEC["vocab_size"]


def trainer(dtype="float32", **blocks):
    module = create_model("deepseek_v2", output_dim=V,
                          config=os.path.join(ROOT, CELL), dtype=dtype)
    inner = NWPTrainer(module)
    vars(inner).update(blocks)
    return LoRATrainer(inner, SIZES["lora_rank"], SIZES["lora_alpha"])


@pytest.fixture(scope="module")
def seeded():
    weights = jax.jit(lambda k: ref.init(k, SIZES))(jax.random.PRNGKey(11))
    x = jax.random.randint(jax.random.PRNGKey(1), (B, T), 1, V)
    y = jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], axis=1)
    mask = jnp.array([1.0, 1.0, 1.0, 0.0])
    return weights, {"x": x, "y": y, "mask": mask}


def test_the_references_weights_are_laid_out_as_the_programs(seeded):
    weights, batch = seeded
    own = jax.eval_shape(lambda: trainer().init(jax.random.PRNGKey(0),
                                                batch["x"][:1]))
    assert jax.tree.structure(own) == jax.tree.structure(weights)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), own) == jax.tree.map(
        lambda a: (a.shape, a.dtype), weights)
    trained = sum(a.size for a in jax.tree.leaves(weights["params"]))
    kernels = [k for i in range(SPEC["num_hidden_layers"])
               for k in ref._kernels(SPEC, i).values()]
    assert trained == sum(SIZES["lora_rank"] * (a + b) for a, b in kernels)
    # the 3-D expert kernels, the head, the embedding and the norms: none
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(weights["params"])[0]]
    assert not any("experts_" in p or "lm_head" in p or "embed" in p
                   or "norm" in p for p in paths)
    assert any("router" in p for p in paths)


def test_logits_loss_and_adapter_gradients_equal_the_references(seeded):
    weights, batch = seeded
    tr = trainer()
    apply = ref.make_apply(SIZES)
    mask = batch["mask"] > 0

    def ref_loss(adapters):
        out, _ = apply({"params": adapters, "lora_base": weights["lora_base"]},
                       batch["x"], True, None, "f32", mask)
        return ref.loss(out, batch["y"], mask)[0], out

    with jax.default_matmul_precision("highest"):
        logits, _ = tr.apply(weights, batch["x"], None, True)
        (loss_r, out), grad_r = jax.value_and_grad(ref_loss, has_aux=True)(
            weights["params"])
        (loss_p, (_, aux)), grad_p = jax.value_and_grad(
            lambda p: tr.loss_fn({**weights, "params": p}, batch, None, True),
            has_aux=True)(weights["params"])
        logits_r = out.states @ out.head
    np.testing.assert_allclose(logits, logits_r, atol=2e-4)
    assert float(loss_p) == pytest.approx(float(loss_r), rel=1e-5)
    assert float(aux["total"]) == 3 * (T - 1)
    flat_p, flat_r = jax.tree.leaves(grad_p), jax.tree.leaves(grad_r)
    scale = np.median([float(jnp.linalg.norm(g)) for g in flat_r])
    for p, r in zip(flat_p, flat_r):
        assert float(jnp.linalg.norm(p - r)) <= 1e-4 * max(
            float(jnp.linalg.norm(r)), scale)
    assert min(float(jnp.linalg.norm(g)) for g in flat_r) > 0   # B is not 0
    # every routed pair is counted: tokens x top-k a layer, pads included
    assert aux["moe_load"].shape == (2, SPEC["n_routed_experts"])
    assert np.asarray(aux["moe_load"].sum(-1)).tolist() == [
        B * T * SPEC["num_experts_per_tok"]] * 2


def test_yarn_frequencies_and_softmax_scale_equal_the_references():
    cfg = DeepseekV2Config.from_file(os.path.join(ROOT, CELL))
    np.testing.assert_allclose(yarn_inv_freq(cfg), ref.yarn_inv_freq(SPEC),
                               rtol=1e-6)
    published = DeepseekV2Config.from_file(None)
    assert published.num_hidden_layers == 27 and published.vocab_size == 102400
    m = 0.1 * 0.707 * np.log(40.0) + 1
    assert softmax_scale(published) == pytest.approx(192 ** -0.5 * m * m)
    with open(os.path.join(ROOT, "benchmarks/configs/dsv2lite_lora.json")) as f:
        cell = json.load(f)
    np.testing.assert_allclose(yarn_inv_freq(published),
                               ref.yarn_inv_freq(cell), rtol=1e-6)


def test_loss_over_blocks_of_tokens_equals_the_whole_batchs(seeded):
    """`NWPTrainer`'s sums as it computed them from the whole batch's
    logits, against blocks that do not divide the batch's tokens."""
    weights, batch = seeded
    tr = trainer(loss_block=48, eval_rows=3, eval_block=20)
    with jax.default_matmul_precision("highest"):
        logits, _ = tr.apply(weights, batch["x"], None, False)
        _, (_, aux) = tr.loss_fn(weights, batch, None, True)
        ev = tr.eval_fn(weights, batch)
    y = batch["y"]
    per = optax.softmax_cross_entropy_with_integer_labels(logits, y)
    mask = (y != 0) * batch["mask"][:, None]
    old = {"loss_sum": (per * mask).sum(), "total": mask.sum(),
           "correct": ((jnp.argmax(logits, -1) == y) * mask).sum()}
    for k, v in old.items():
        assert float(aux[k]) == pytest.approx(float(v), rel=1e-6)
    assert float(ev["test_total"]) == float(old["total"])
    assert float(ev["test_correct"]) == float(old["correct"])
    assert float(ev["test_loss"]) == pytest.approx(
        float(old["loss_sum"] / old["total"] * 3), rel=1e-6)


#: the two tiny decoders at a vocabulary no other width of theirs equals, so
#: a product with the head is a `dot_general` with WORDS among its shapes
TINY = {"deepseek_v2": CELL, "jamba": "tests/benchmark/cells/tiny_jamba2_lora.json"}
WORDS = 320


def checkpointed_ce(inner, variables, batch, train, block):
    """The blockwise loss as `NWPTrainer` computed it before its custom VJP:
    each block `jax.checkpoint`ed, so the backward runs the head's product
    again and then its transpose. (loss, {loss_sum, correct, total})."""
    states, _ = inner.module.apply(variables, batch["x"], train=train,
                                   method="hidden")
    y = batch["y"]
    mask = ((y != inner.pad_id).astype(jnp.float32)
            * batch["mask"].astype(jnp.float32)[:, None])
    n = y.size
    block = min(block, n)
    pad = -n % block
    blocks = [jnp.pad(a.reshape((n,) + a.shape[2:]),
                      [(0, pad)] + [(0, 0)] * (a.ndim - 2)).reshape(
                          (-1, block) + a.shape[2:])
              for a in (states, y, mask)]

    @jax.checkpoint
    def one(sums, inp):
        hb, yb, mb = inp
        logits = inner.module.apply(variables, hb, method="head")
        per = optax.softmax_cross_entropy_with_integer_labels(logits, yb)
        hit = (jnp.argmax(logits, -1) == yb).astype(jnp.float32)
        return (sums[0] + (per.astype(jnp.float32) * mb).sum(),
                sums[1] + (hit * mb).sum()), None

    zero = jnp.zeros((), jnp.float32)
    (loss_sum, correct), _ = jax.lax.scan(one, (zero, zero), blocks)
    total = mask.sum()
    return loss_sum / jnp.maximum(total, 1.0), {
        "loss_sum": loss_sum, "correct": correct, "total": total}


def head_products(jaxpr) -> int:
    """`dot_general`s with the vocabulary among their shapes, in `jaxpr`
    and every jaxpr inside it (a scan's body counts once)."""
    def subs(v):
        if isinstance(v, jax.extend.core.ClosedJaxpr):
            return [v.jaxpr]
        if isinstance(v, jax.extend.core.Jaxpr):
            return [v]
        if isinstance(v, (tuple, list)):
            return [j for x in v for j in subs(x)]
        return []

    count = 0
    for eqn in jaxpr.eqns:
        shapes = [a.aval.shape for a in eqn.invars + eqn.outvars]
        count += eqn.primitive.name == "dot_general" and any(
            WORDS in s for s in shapes)
        count += sum(head_products(j) for v in eqn.params.values()
                     for j in subs(v))
    return count


@pytest.fixture(scope="module", params=sorted(TINY))
def tiny(request):
    """(LoRA trainer over the tiny decoder, variables with adapters that are
    not 0, a batch of 3 x 32 tokens with pad ids and a masked sample)."""
    with open(os.path.join(ROOT, TINY[request.param])) as f:
        spec = dict(json.load(f), vocab_size=WORDS)
    module = create_model(request.param, output_dim=WORDS, config=spec)
    tr = LoRATrainer(NWPTrainer(module), rank=4, alpha=4)
    x = jax.random.randint(jax.random.PRNGKey(2), (3, 32), 1, WORDS)
    y = jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], axis=1)
    y = y.at[1, 5:9].set(0)
    variables = tr.init(jax.random.PRNGKey(5), x[:1])
    leaves, tree = jax.tree.flatten(variables["params"])
    keys = jax.random.split(jax.random.PRNGKey(6), len(leaves))
    variables["params"] = jax.tree.unflatten(tree, [
        0.1 * jax.random.normal(k, a.shape, a.dtype)
        for k, a in zip(keys, leaves)])
    return tr, variables, {"x": x, "y": y, "mask": jnp.array([1.0, 1.0, 0.0])}


def test_blockwise_loss_and_adapter_gradients_equal_the_checkpointed_forms(tiny):
    """Blocks of 40 of 96 tokens: the last block holds 16 and 24 of padding."""
    tr, variables, batch = tiny
    inner = tr.inner
    inner.loss_block = 40

    def new(p):
        return tr.loss_fn({**variables, "params": p}, batch, None, True)

    def old(p):
        merged = tr.merged_variables({**variables, "params": p})
        loss, aux = checkpointed_ce(inner, merged, batch, True, 40)
        return loss, (None, aux)

    with jax.default_matmul_precision("highest"):
        (loss_n, (_, aux_n)), grad_n = jax.value_and_grad(new, has_aux=True)(
            variables["params"])
        (loss_o, (_, aux_o)), grad_o = jax.value_and_grad(old, has_aux=True)(
            variables["params"])
    assert float(loss_n) == pytest.approx(float(loss_o), rel=1e-5)
    assert float(aux_n["loss_sum"]) == pytest.approx(float(aux_o["loss_sum"]),
                                                     rel=1e-5)
    assert float(aux_n["total"]) == float(aux_o["total"]) == 2 * 31 - 4
    assert float(aux_n["correct"]) == float(aux_o["correct"])
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grad_n)[0],
                            jax.tree.leaves(grad_o)):
        assert float(jnp.linalg.norm(b)) > 0, path
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(
            jnp.linalg.norm(b)), path


def test_the_training_gradient_multiplies_by_the_head_twice_a_block(tiny):
    """The forward's logits and the states' gradient from them; the
    checkpointed form also ran the forward's product again."""
    tr, variables, batch = tiny

    def count(loss):
        return head_products(jax.make_jaxpr(jax.grad(
            lambda p: loss({**variables, "params": p})))(
                variables["params"]).jaxpr)

    assert count(lambda v: tr.loss_fn(v, batch, None, True)[0]) == 2
    assert count(lambda v: checkpointed_ce(
        tr.inner, tr.merged_variables(v), batch, True, 40)[0]) == 3


def test_eval_sums_equal_the_checkpointed_forms(tiny):
    tr, variables, batch = tiny
    inner = tr.inner
    inner.eval_rows, inner.eval_block = 2, 20
    with jax.default_matmul_precision("highest"):
        ev = tr.eval_fn(variables, batch)
        _, old = checkpointed_ce(inner, tr.merged_variables(variables), batch,
                                 False, 20)
    assert float(ev["test_total"]) == float(old["total"])
    assert float(ev["test_correct"]) == float(old["correct"])
    assert float(ev["test_loss"]) == pytest.approx(
        float(old["loss_sum"] / old["total"] * 2), rel=1e-5)


def test_a_trained_heads_gradient_equals_the_checkpointed_forms():
    """Jamba without adapters: the head is the embedding, trained, so its
    gradient takes a third product a block, from the same logits."""
    with open(os.path.join(ROOT, TINY["jamba"])) as f:
        spec = dict(json.load(f), vocab_size=WORDS)
    inner = NWPTrainer(create_model("jamba", output_dim=WORDS, config=spec))
    inner.loss_block = 40
    x = jax.random.randint(jax.random.PRNGKey(3), (3, 32), 1, WORDS)
    y = jnp.concatenate([x[:, 1:], jnp.zeros_like(x[:, :1])], axis=1)
    batch = {"x": x, "y": y, "mask": jnp.array([1.0, 0.0, 1.0])}
    variables = inner.init(jax.random.PRNGKey(4), x[:1])

    def new(p):
        return inner.loss_fn({**variables, "params": p}, batch, None, True)[0]

    def old(p):
        return checkpointed_ce(inner, {**variables, "params": p}, batch,
                               True, 40)[0]

    with jax.default_matmul_precision("highest"):
        grad_n = jax.grad(new)(variables["params"])
        grad_o = jax.grad(old)(variables["params"])
        n_products = head_products(jax.make_jaxpr(jax.grad(new))(
            variables["params"]).jaxpr)
    assert n_products == 3
    # the embedding's gradient is the head's and the input's; the final
    # norm's comes from the states' gradient alone
    for name in ("embed", "final_norm"):
        a, b = grad_n[name], grad_o[name]
        assert jax.tree.structure(a) == jax.tree.structure(b)
        for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            assert float(jnp.linalg.norm(u - v)) <= 1e-5 * float(
                jnp.linalg.norm(v)), name
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grad_n)[0],
                            jax.tree.leaves(grad_o)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(
            jnp.linalg.norm(b)), path


def test_a_model_without_hidden_and_head_takes_the_whole_batchs_logits():
    module = create_model("transformer_nwp", output_dim=64)
    assert NWPTrainer(module).blockwise is False
    assert trainer().inner.blockwise is True


def test_bfloat16_base_float32_adapters_base_bitwise_after_two_rounds():
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data import load_dataset

    ds = load_dataset("tokens", client_num_in_total=3, seed=0, vocab=V,
                      seq_len=T, train_sequences=8, test_sequences=2)
    cfg = FedConfig(model="deepseek_v2", client_num_in_total=3,
                    client_num_per_round=2, comm_round=2, batch_size=4,
                    epochs=1, lr=0.03, lora_rank=4, dtype="bfloat16",
                    frequency_of_the_test=100)
    api = FedAvgAPI(ds, cfg, trainer("bfloat16"))
    base0 = jax.device_get(api.global_variables["lora_base"])
    adapters0 = jax.device_get(api.global_variables["params"])
    assert {str(a.dtype) for a in jax.tree.leaves(base0)} == {"bfloat16"}
    assert {str(a.dtype) for a in jax.tree.leaves(adapters0)} == {"float32"}
    before = api.global_variables["lora_base"]
    api.train()
    after = api.global_variables["lora_base"]
    # the same device arrays: the round program does not even return a base
    assert all(a is b for a, b in zip(jax.tree.leaves(before),
                                      jax.tree.leaves(after)))
    for a, b in zip(jax.tree.leaves(base0), jax.tree.leaves(
            jax.device_get(after))):
        assert a.tobytes() == b.tobytes()
    moved = [float(np.abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(adapters0),
        jax.tree.leaves(jax.device_get(api.global_variables["params"])))]
    assert max(moved) > 0
    assert len(api.history) == 2   # vectors never reach the history


def test_full_parameter_training_is_refused():
    import argparse

    from fedml_tpu.experiments import common

    args = common.add_args(argparse.ArgumentParser()).parse_args(
        ["--dataset", "tokens", "--model", "deepseek_v2", "--model_config",
         os.path.join(ROOT, CELL)])
    cfg = common.config_from_args(args)
    ds = type("D", (), {"class_num": V, "meta": {"task": "nwp"}})
    with pytest.raises(SystemExit, match="frozen base only"):
        common.build_trainer(args, cfg, ds)


def test_expert_loads_leave_the_record_flush_as_one_event():
    """A round's [expert layers, experts] counts ride the pending record
    under a reserved key, are fetched with it, and become a `moe_load`
    event: the history keeps scalars only."""
    from fedml_tpu.telemetry import Tracer
    from fedml_tpu.telemetry.records import RoundRecordLog

    tracer, history = Tracer(), []
    log = RoundRecordLog(tracer, history)
    load = jnp.array([[3.0, 0.0, 5.0, 4.0], [2.0, 6.0, 0.0, 4.0]])
    log.add({"round": 7, "round_time": 0.5, "_moe_load": load})
    log.flush(7)
    assert history == [{"round": 7, "round_time": 0.5}]
    (event,) = tracer.find_events("moe_load")
    assert (event["round"], event["max"], event["mean"], event["empty"]) == (
        7, 6.0, 3.0, 2)
    assert [e["round"] for e in tracer.find_events("round_committed")] == [7]
