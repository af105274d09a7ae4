"""The repo's lintable surface — what `python -m fedml_tpu.analysis` checks.

One table (MODEL_EXAMPLES, moved here from tests/test_dtype_registry.py so
the test and the CLI share it) plus builders that trace the repo's actual
jitted programs: engine round runners, the silo-grouped round, every
aggregator's round, the chunked runner's donated chunk dispatch, the DARTS
supernet, and a 3-round retrace drive.

Everything traces abstractly (eval_shape / make_jaxpr on
ShapeDtypeStructs) except the donation and retrace checks, which need the
real jit machinery — those use the tiniest model in the registry.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fedml_tpu.analysis.core import Finding, Report
from fedml_tpu.analysis.jaxpr_engine import (
    check_donation,
    check_retrace,
    lint_jaxpr,
)
from fedml_tpu.analysis.partition import (
    check_partition_coverage,
    model_variable_shapes,
)
from fedml_tpu.core.config import FedConfig
from fedml_tpu.core.trainer import ClassificationTrainer
from fedml_tpu.models.registry import available_models, create_model



def tiny_deepseek_v2(**sizes) -> dict:
    """DeepSeek-V2-Lite's published configuration with every size cut to a
    CPU test's (hidden 64, 8 experts top-2, 1 dense + 2 expert layers): the
    `config` a `deepseek_v2` factory call takes. The vocabulary is 10, the
    `output_dim` every sweep here builds with; `sizes` override."""
    import json

    from fedml_tpu.models.deepseek_v2 import PUBLISHED

    with open(PUBLISHED) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=160,
               moe_intermediate_size=48, num_hidden_layers=3,
               num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               n_routed_experts=8, num_experts_per_tok=2, vocab_size=10,
               max_position_embeddings=256)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
    cfg.update(sizes)
    return cfg


def tiny_kimi_linear(**sizes) -> dict:
    """Kimi-Linear-48B-A3B's published configuration with every size cut to
    a CPU test's (hidden 64, a KDA layer of 2 heads of 16 over a dense MLP,
    then a NoPE MLA layer over 8 experts top-2): the `config` a `kimi_linear`
    factory call takes. Vocabulary 10, as `tiny_deepseek_v2`; `sizes`
    override (`linear_attn_config` whole)."""
    import json

    from fedml_tpu.models.kimi_linear import PUBLISHED

    with open(PUBLISHED) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, intermediate_size=160,
               moe_intermediate_size=48, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               num_experts=8, num_experts_per_token=2, vocab_size=10,
               model_max_length=256)
    cfg["linear_attn_config"] = dict(
        cfg["linear_attn_config"], kda_layers=[1], full_attn_layers=[2],
        num_heads=2, head_dim=16)
    cfg.update(sizes)
    return cfg


# model name -> (example input shape, input dtype, extra factory kwargs).
# Every registered model MUST have a row (enforced by tests/test_lint.py and
# tests/test_dtype_registry.py) — a new factory that drops the dtype knob
# fails the lint, not a bench three rounds later.
MODEL_EXAMPLES = {
    "lr": ((2, 32), jnp.float32, {}),
    "mlp": ((2, 32), jnp.float32, {}),
    "purchasemlp": ((2, 600), jnp.float32, {}),
    "texasmlp": ((2, 6169), jnp.float32, {}),
    "cnn_fedavg": ((2, 28, 28, 1), jnp.float32, {}),
    "cnn": ((2, 28, 28, 1), jnp.float32, {}),
    "cnn_cifar": ((2, 32, 32, 3), jnp.float32, {}),
    "har_cnn": ((2, 128, 9), jnp.float32, {}),
    "resnet20": ((2, 32, 32, 3), jnp.float32, {}),
    "resnet32": ((2, 32, 32, 3), jnp.float32, {}),
    "resnet44": ((2, 32, 32, 3), jnp.float32, {}),
    "resnet56": ((2, 32, 32, 3), jnp.float32, {}),
    "resnet56_s2d": ((2, 32, 32, 3), jnp.float32, {}),
    "resnet110": ((2, 32, 32, 3), jnp.float32, {}),
    "resnet18": ((2, 32, 32, 3), jnp.float32, {}),
    "resnet34": ((2, 32, 32, 3), jnp.float32, {}),
    "resnet50": ((2, 32, 32, 3), jnp.float32, {}),
    "resnet18_gn": ((2, 24, 24, 3), jnp.float32, {}),
    "mobilenet": ((2, 32, 32, 3), jnp.float32, {}),
    "mobilenet_v3": ((2, 32, 32, 3), jnp.float32, {"mode": "SMALL"}),
    "efficientnet": ((2, 32, 32, 3), jnp.float32,
                     {"variant": "efficientnet-b0"}),
    "vgg11": ((2, 32, 32, 3), jnp.float32, {}),
    "vgg16": ((2, 32, 32, 3), jnp.float32, {}),
    "deeplab": ((2, 32, 32, 3), jnp.float32, {}),
    "fcn": ((2, 16, 16, 3), jnp.float32, {}),
    "rnn": ((2, 16), jnp.int32, {"vocab_size": 90}),
    "rnn_stackoverflow": ((2, 12), jnp.int32, {}),
    "transformer_nwp": ((2, 16), jnp.int32, {}),
    "deepseek_v2": ((2, 16), jnp.int32, {"config": tiny_deepseek_v2()}),
    "kimi_linear": ((2, 16), jnp.int32, {"config": tiny_kimi_linear()}),
}


def models_missing_examples() -> List[str]:
    return sorted(set(available_models()) - set(MODEL_EXAMPLES))


def forward_jaxpr(module, shape, in_dtype):
    """Abstract forward trace of a flax module (eval_shape init -> make_jaxpr
    of apply) — zero FLOPs, works for any registry model."""
    rng = jax.random.PRNGKey(0)
    x = jax.ShapeDtypeStruct(shape, in_dtype)
    var_shapes = jax.eval_shape(
        lambda: module.init({"params": rng, "dropout": rng},
                            jnp.zeros(shape, in_dtype), train=False))
    variables = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), var_shapes)
    return jax.make_jaxpr(
        lambda v, xx: module.apply(v, xx, train=False))(variables, x).jaxpr


def model_jaxpr(name: str, dtype: str = "bfloat16"):
    shape, in_dtype, kw = MODEL_EXAMPLES[name]
    module = create_model(name, output_dim=10, dtype=dtype, **kw)
    return forward_jaxpr(module, shape, in_dtype)


def darts_jaxpr():
    """The DARTS supernet is built directly by FedNASAPI (not via the
    registry) — its mixed-op tensordot path gets its own target."""
    from fedml_tpu.models.darts import DARTSNetwork, init_alphas

    net = DARTSNetwork(output_dim=10, channels=4, layers=2,
                       dtype=jnp.bfloat16)
    rng = jax.random.PRNGKey(0)
    an, ar = init_alphas(rng)
    x = jnp.zeros((2, 16, 16, 3))
    var_shapes = jax.eval_shape(
        lambda: net.init({"params": rng}, x, an, ar, train=False))
    variables = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), var_shapes)
    return jax.make_jaxpr(
        lambda v, xx, a, b: net.apply(v, xx, a, b, train=False))(
        variables, jax.ShapeDtypeStruct(x.shape, x.dtype), an, ar).jaxpr


def _tiny_trainer(model: str, dtype: str, **kw):
    shape, in_dtype, extra = MODEL_EXAMPLES[model]
    extra = dict(extra, **kw)
    module = create_model(model, output_dim=10, dtype=dtype, **extra)
    return ClassificationTrainer(module), shape, in_dtype


def _abstract_round_args(trainer, shape, in_dtype, clients: int = 2,
                         n_max: int = 4):
    rng = jax.random.PRNGKey(0)
    var_shapes = jax.eval_shape(
        lambda: trainer.init(rng, jnp.zeros(shape, in_dtype)))
    gv = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), var_shapes)
    x = jax.ShapeDtypeStruct((clients, n_max) + shape[1:], in_dtype)
    y = jax.ShapeDtypeStruct((clients, n_max), jnp.int32)
    counts = jax.ShapeDtypeStruct((clients,), jnp.int32)
    return gv, x, y, counts, rng


def round_jaxpr(model: str = "cnn", dtype: str = "bfloat16",
                aggregator_name: str = "fedavg",
                silo_threshold: int = 0):
    """Traced jaxpr of one full engine round (vmap(local_update) +
    aggregate) — or the silo-grouped round when silo_threshold > 0."""
    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.algorithms.engine import build_round_fn

    trainer, shape, in_dtype = _tiny_trainer(model, dtype)
    cfg = FedConfig(model=model, batch_size=2, epochs=1, dtype=dtype)
    agg = make_aggregator(aggregator_name, cfg)
    if silo_threshold > 0:
        from fedml_tpu.algorithms.silo_grouped import (
            build_silo_round_fn, silo_trainer)

        round_fn = build_silo_round_fn(
            silo_trainer(trainer, silo_threshold), cfg, agg)
    else:
        round_fn = build_round_fn(trainer, cfg, agg)
    gv, x, y, counts, rng = _abstract_round_args(trainer, shape, in_dtype)
    agg_state = agg.init_state(gv)
    return jax.make_jaxpr(round_fn)(gv, agg_state, x, y, counts, rng).jaxpr


_POLICY = {"bfloat16": jnp.bfloat16, "float32": None}

# Aggregators all run on f32 params (the mixed-precision contract keeps
# aggregation full-precision), so their rounds lint without a dtype policy.
AGGREGATOR_NAMES = ("fedavg", "fedopt", "robust", "fednova")


def iter_jaxpr_targets(include_models: bool = True,
                       ) -> Iterator[Tuple[str, object, Optional[object]]]:
    """(target name, jaxpr, dtype policy or None) for every pure-jaxpr
    target. Order: cheap engine targets first, the 29-model sweep last."""
    yield ("engine.round[cnn,bf16,fedavg]",
           round_jaxpr("cnn", "bfloat16", "fedavg"), jnp.bfloat16)
    for agg in AGGREGATOR_NAMES:
        yield (f"engine.round[lr,f32,{agg}]",
               round_jaxpr("lr", "float32", agg), None)
    yield ("silo.round[resnet20,bf16,fedavg]",
           round_jaxpr("resnet20", "bfloat16", "fedavg", silo_threshold=32),
           jnp.bfloat16)
    yield ("darts.supernet[bf16]", darts_jaxpr(), jnp.bfloat16)
    if include_models:
        for name in sorted(MODEL_EXAMPLES):
            if name in available_models():
                yield (f"model:{name}[bf16]", model_jaxpr(name),
                       jnp.bfloat16)


def tensor_step_jaxpr(model: str = "transformer_nwp",
                      constrained: bool = True):
    """Traced jaxpr of the activation-sharded client step (tensor.step,
    parallel/tensor.py build_tensor_step_fn) on the 2x4 mesh, plus the
    tensor-axis size — the unconstrained-intermediate repo-clean pin.
    `constrained=False` builds the step WITHOUT its activation rule table
    (the lint fixture arm: same program, constraint hooks dark)."""
    from jax.sharding import Mesh

    from fedml_tpu.core.trainer import NWPTrainer
    from fedml_tpu.parallel.tensor import (TensorSharding,
                                           build_tensor_step_fn)

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4),
                ("clients", "tensor"))
    cfg = FedConfig(model=model, batch_size=2, epochs=1, dtype="float32",
                    tensor_shards=4)
    trainer = NWPTrainer(create_model(model, output_dim=10))
    step_fn = build_tensor_step_fn(
        trainer, cfg, TensorSharding.for_model(mesh, model),
        activation_rules="auto" if constrained else None)
    rng = jax.random.PRNGKey(0)
    var_shapes = jax.eval_shape(
        lambda: trainer.init(rng, jnp.zeros((2, 16), jnp.int32)))
    gv = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), var_shapes)
    args = (gv, jax.ShapeDtypeStruct((2, 4, 16), jnp.int32),
            jax.ShapeDtypeStruct((2, 4, 16), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32), rng)
    return jax.make_jaxpr(step_fn)(*args).jaxpr, 4


def check_chunked_donation() -> List[Finding]:
    """The chunked runner's (variables, opt_state, steps) carry must lower
    as donated buffers — otherwise every chunk boundary pays a full-carry
    HBM copy and the 'zero device copies' contract in its docstring lies."""
    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.algorithms.engine import build_chunked_round_runner

    trainer, shape, in_dtype = _tiny_trainer("lr", "float32")
    cfg = FedConfig(model="lr", batch_size=2, epochs=2, dtype="float32")
    runner = build_chunked_round_runner(
        trainer, cfg, make_aggregator("fedavg", cfg), epoch_chunk=1)
    rng = jax.random.PRNGKey(0)
    gv = trainer.init(rng, jnp.zeros(shape, in_dtype))
    c, n = 2, 4
    counts = jnp.full((c,), n, jnp.int32)
    stacked, opt_state, steps, erngs = runner.init_fn(gv, counts, rng)
    x = jnp.zeros((c, n) + shape[1:], in_dtype)
    y = jnp.zeros((c, n), jnp.int32)
    args = (stacked, opt_state, steps, gv["params"], x, y, counts,
            erngs[:, 0:1])
    return check_donation(
        runner.chunk_fn, args, "engine.chunked.chunk_fn[lr]",
        argnums=runner.chunk_donate_argnums)


def check_round_retrace(rounds: int = 3) -> List[Finding]:
    """Drive 3 same-shape rounds through build_round_fn and assert ONE
    compile — the compile-once-per-shape contract every bench assumes."""
    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.algorithms.engine import build_round_fn

    trainer, shape, in_dtype = _tiny_trainer("lr", "float32")
    cfg = FedConfig(model="lr", batch_size=2, epochs=1, dtype="float32")
    round_fn = build_round_fn(trainer, cfg, make_aggregator("fedavg", cfg))
    rng = jax.random.PRNGKey(0)
    gv = trainer.init(rng, jnp.zeros(shape, in_dtype))
    c, n = 2, 4
    x = np.zeros((c, n) + shape[1:], np.float32)
    y = np.zeros((c, n), np.int32)
    counts = np.full((c,), n, np.int32)

    state = {"gv": gv, "agg": ()}

    def make_args(i):
        # fresh host arrays each round — exactly how the benches feed it;
        # only the rng VALUE changes, never a shape or dtype
        return (state["gv"], state["agg"], jnp.asarray(x), jnp.asarray(y),
                jnp.asarray(counts), jax.random.PRNGKey(i))

    return check_retrace(round_fn, make_args,
                         "engine.round[lr,f32,fedavg]", rounds=rounds)


def check_model_partitions() -> List[Finding]:
    """Every registry model's full variables tree must match a
    PartitionSpec rule (the match_partition_rules coverage contract)."""
    out: List[Finding] = []
    for name in sorted(MODEL_EXAMPLES):
        if name not in available_models():
            continue
        shape, in_dtype, kw = MODEL_EXAMPLES[name]
        module = create_model(name, output_dim=10, **kw)
        tree = model_variable_shapes(module, shape, in_dtype)
        out += check_partition_coverage(tree, f"model:{name}")
    return out


def check_tensor_rule_coverage(rule_tables=None,
                               family_models=None) -> List[Finding]:
    """100% coverage over the RUNTIME partition-rule tables
    (parallel/tensor.py RULE_TABLES) — the lint-only contract above,
    extended to the tables that actually shard rounds.

    Two directions: every non-scalar leaf of every family model must match
    its family's table (an unmatched leaf would raise inside
    `resolve_param_specs` at round-build time — catch it in lint instead),
    and every rule must match at least one leaf across the family's models
    (a dead rule means the table and the model zoo drifted apart).
    `rule_tables`/`family_models` default to the runtime tables; tests
    inject fixtures."""
    import re

    from fedml_tpu.analysis.partition import _flat_paths
    from fedml_tpu.models.lora import init_lora_adapters
    from fedml_tpu.parallel.tensor import FAMILY_MODELS, RULE_TABLES

    tables = RULE_TABLES if rule_tables is None else rule_tables
    models = FAMILY_MODELS if family_models is None else family_models
    out: List[Finding] = []
    for family in sorted(tables):
        rules = list(tables[family])
        used = [False] * len(rules)

        def mark_used(tree):
            for path, leaf in _flat_paths(tree):
                if getattr(leaf, "ndim", 0) == 0:
                    continue
                for i, (pattern, _) in enumerate(rules):
                    if re.search(pattern, path):
                        used[i] = True
                        break

        for name in models.get(family, ()):
            if name not in available_models():
                continue
            shape, in_dtype, kw = MODEL_EXAMPLES[name]
            module = create_model(name, output_dim=10, **kw)
            tree = model_variable_shapes(module, shape, in_dtype)
            out += check_partition_coverage(
                tree, f"tensor-rules:{family}:{name}", rules=rules)
            mark_used(tree)
            # the LoRA composition these tables explicitly carry rules for
            # (models/lora.py wraps any family model: replicated lora_A/B
            # adapters over the tensor-sharded frozen base) — the adapter
            # leaves must be covered too, and covering them is what keeps
            # the lora_[AB] rule live in the dead-rule direction below
            try:
                adapters = jax.eval_shape(
                    lambda p: init_lora_adapters(p, 8, jax.random.PRNGKey(0)),
                    tree.get("params", tree))
            except ValueError:
                adapters = None  # no 2D kernel eligible in this family model
            if adapters:
                out += check_partition_coverage(
                    adapters, f"tensor-rules:{family}:{name}+lora8",
                    rules=rules)
                mark_used(adapters)
        for hit, (pattern, spec) in zip(used, rules):
            if not hit:
                out.append(Finding(
                    "partition-coverage", f"tensor-rules:{family}",
                    f"rule {pattern!r} ({spec}) matches no leaf of any "
                    f"family model — dead rule; prune it or fix the "
                    f"pattern"))
    return out


# ------------------------------------------------------------------ drives
# The registered drive configs whose XLA program sets COMPILE_BUDGET.json
# pins (compile_engine). The per-drive program LISTS live in the
# declarative spec (core/spec.py DRIVE_SPECS, graft-matrix) — codec twins
# are expanded from the codec axis there, not hand-listed here. This
# module's job is to TRACE every declared point through the real builders,
# so the enumeration still crashes the moment a signature arm drifts.
DRIVE_CONFIGS = ("eager", "pipelined", "buffered", "tensor", "sharded",
                 "hierarchical", "silo", "serving", "finetune")


def _drive_eval_programs(trainer, shape, in_dtype, gv, rng):
    """The three eval programs every FedAvgAPI drive shares: packed global
    eval, chunked per-client eval, and the resident federation eval (two
    signatures — the Train and Test splits pack to different n_max)."""
    from fedml_tpu.algorithms.engine import (build_client_eval_fn,
                                             build_eval_fn,
                                             build_federation_eval_fn)

    feat = shape[1:]
    i32 = lambda s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    xs = lambda s: jax.ShapeDtypeStruct(s + feat, in_dtype)  # noqa: E731
    jax.eval_shape(build_eval_fn(trainer), gv,
                   xs((3, 2)), i32((3, 2)), f32((3, 2)))
    jax.eval_shape(build_client_eval_fn(trainer), gv,
                   xs((2, 4)), i32((2, 4)), i32((2,)))
    fed_eval = build_federation_eval_fn(trainer)
    for n_max in (4, 6):
        jax.eval_shape(fed_eval, gv,
                       xs((1, 2, n_max)), i32((1, 2, n_max)), i32((1, 2)))
    return {"engine.eval[lr,f32]": 1, "engine.client_eval[lr,f32]": 1,
            "engine.federation_eval[lr,f32]": 2}


def _point_codec(point, cfg):
    """The codec a spec ProgramPoint's name tag declares (int8 at the
    config's bit width, topk at the point's pinned k), or None."""
    from fedml_tpu.codecs import make_codec

    level = point.opt("codec")
    if level is None:
        return None
    if level == "int8":
        return make_codec("int8", cfg)
    return make_codec("topk", {"codec_k": point.opt("codec_k")})


def _trace_buffered_programs(trainer, cfg, agg, gv, agg_state, x, y, counts,
                             rng, codecs=()) -> dict:
    """Abstractly trace the buffered drive's three jit programs (client
    step, admit, commit) — shared by the buffered and serving enumerations.
    `codecs` adds the codec-on admit variants (graft-codec): each codec's
    admit takes the trailing replicated delta base, a distinct jit
    signature the budget pins as its own program."""
    from fedml_tpu.algorithms.aggregators import (build_buffer_admit,
                                                  build_buffer_commit,
                                                  make_staleness_discount)
    from fedml_tpu.algorithms.buffered import build_client_step_fn

    programs = {}
    step = build_client_step_fn(trainer, cfg)
    result = jax.eval_shape(step, gv, x, y, counts, rng)
    programs["buffered.client_step[lr,f32]"] = 1
    k = 5
    row = lambda l: jax.ShapeDtypeStruct(  # noqa: E731
        (k,) + l.shape[1:], l.dtype)
    i32 = lambda s=(): jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    buf = {"vars": jax.tree.map(row, result.variables),
           "steps": i32((k,)),
           "weights": jax.ShapeDtypeStruct((k,), jnp.float32),
           "metrics": {name: row(v)
                       for name, v in result.metrics.items()},
           "birth": i32((k,)), "fill": i32()}
    jax.eval_shape(build_buffer_admit(), buf, result.variables,
                   result.num_steps, result.metrics, counts,
                   i32(), i32())
    programs["buffered.admit[lr,f32]"] = 1
    for codec in codecs:
        # the codec delta base mirrors the WIRE tree — adapters-only under
        # LoRA, same strip the drive applies (algorithms/buffered.py)
        from fedml_tpu.models.lora import strip_lora_base

        jax.eval_shape(build_buffer_admit(codec=codec), buf,
                       result.variables, result.num_steps, result.metrics,
                       counts, i32(), i32(), strip_lora_base(gv))
        programs[f"buffered.admit[lr,f32,{codec.name}]"] = 1
    jax.eval_shape(build_buffer_commit(agg, make_staleness_discount(0.5)),
                   gv, agg_state, buf, i32(), rng)
    programs["buffered.commit[lr,f32,fedavg]"] = 1
    return programs


def _trace_engine_round(point, ctx) -> None:
    """Trace one declared engine.round point: the base vmap round, or its
    masked / federated-LoRA / codec-wrapped twin, per the
    point's spec opts."""
    from fedml_tpu.algorithms.engine import build_round_fn

    trainer, cfg, agg = ctx["trainer"], ctx["cfg"], ctx["agg"]
    gv, x, y = ctx["gv"], ctx["x"], ctx["y"]
    counts, rng, agg_state = ctx["counts"], ctx["rng"], ctx["agg_state"]
    if point.opt("pfl"):
        # personalized twin (a --personalize run reaches it): the
        # federated-LoRA round plus trailing [C, ...] personal adapter
        # rows in and out — a distinct jit signature the budget pins as
        # its own program (graft-pfl, models/adapter_bank.py)
        from fedml_tpu.algorithms.engine import build_personal_round_fn
        from fedml_tpu.models.lora import LoRATrainer

        ptrainer = LoRATrainer(trainer, rank=point.opt("lora_rank"))
        pgv, px, py, pcounts, prng = _abstract_round_args(
            ptrainer, ctx["shape"], ctx["in_dtype"])
        round_p = build_personal_round_fn(ptrainer, cfg, agg)
        personal = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((2,) + l.shape, l.dtype),
            pgv["params"])
        jax.eval_shape(round_p, pgv, jax.eval_shape(agg.init_state, pgv),
                       px, py, pcounts, prng, personal)
        return
    if point.opt("lora_rank"):
        # federated-LoRA round (a --lora_rank run reaches it): adapters
        # under "params", frozen base riding as the lora_base collection —
        # a distinct jit signature the budget pins as its own program
        from fedml_tpu.models.lora import LoRATrainer

        ltrainer = LoRATrainer(trainer, rank=point.opt("lora_rank"))
        lgv, lx, ly, lcounts, lrng = _abstract_round_args(
            ltrainer, ctx["shape"], ctx["in_dtype"])
        round_l = build_round_fn(ltrainer, cfg, agg)
        jax.eval_shape(round_l, lgv, jax.eval_shape(agg.init_state, lgv),
                       lx, ly, lcounts, lrng)
        return
    codec = _point_codec(point, cfg)
    if codec is not None:
        # codec-wrapped sync round (a codec-on serving tenant reaches it):
        # the CodecAggregator state is a distinct jit signature
        from fedml_tpu.codecs.transport import CodecAggregator

        wrapped = CodecAggregator(codec, agg, slots=2)
        round_c = build_round_fn(trainer, cfg, wrapped)
        jax.eval_shape(round_c, gv, jax.eval_shape(wrapped.init_state, gv),
                       x, y, counts, rng)
        return
    round_fn = build_round_fn(trainer, cfg, agg)
    args = (gv, agg_state, x, y, counts, rng)
    if point.opt("masked"):
        # chaos is on for this config, so every round carries a
        # participation mask — only the masked arm ever compiles
        args = args + (jax.ShapeDtypeStruct((2,), jnp.bool_),)
    jax.eval_shape(round_fn, *args)


def _trace_superstep(point, ctx) -> None:
    """K rounds scanned in ONE program, chaos-armed + stats-collecting as
    the drive builds it (collect_stats always on in FedAvgAPI)."""
    from fedml_tpu.algorithms.engine import build_superstep_fn

    k = point.opt("rounds")
    scfg = FedConfig(model="lr", batch_size=2, epochs=1,
                     dtype="float32", client_num_per_round=2,
                     rounds_per_dispatch=k)
    super_fn = build_superstep_fn(
        ctx["trainer"], scfg, ctx["agg"], k, client_num_in_total=2,
        collect_stats=True, chaos_armed=True)

    def i32(shape=()):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    per_round = {"round_idx": i32((k,)), "idx": i32((k, 2)),
                 "nan": jax.ShapeDtypeStruct((k, 2), jnp.bool_),
                 "corrupt": jax.ShapeDtypeStruct((k, 2), jnp.bool_),
                 "participation": jax.ShapeDtypeStruct((k, 2), jnp.bool_)}
    jax.eval_shape(super_fn, ctx["gv"], ctx["agg_state"], ctx["x"],
                   ctx["y"], ctx["counts"], ctx["rng"], per_round)


def _trace_tensor_point(point, ctx) -> None:
    """tensor.round (plus its codec twins carrying the wrapped
    {"agg","codec"} state) and the --shard_step tensor.step round."""
    from jax.sharding import Mesh

    from fedml_tpu.parallel.tensor import (TensorSharding,
                                           build_tensor_round_fn,
                                           build_tensor_step_round_fn)

    trainer, cfg, agg = ctx["trainer"], ctx["cfg"], ctx["agg"]
    gv, x, y = ctx["gv"], ctx["x"], ctx["y"]
    counts, rng, agg_state = ctx["counts"], ctx["rng"], ctx["agg_state"]
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(point.opt("mesh")),
                ("clients", "tensor"))
    sharding = TensorSharding.for_model(mesh, "lr")
    if point.family == "tensor.step":
        # --shard_step twin: the GSPMD activation-sharded round
        # (build_tensor_step_round_fn) replacing the shard_map round
        cfg_ss = FedConfig(model="lr", batch_size=2, epochs=1,
                           dtype="float32", tensor_shards=4,
                           shard_step=True)
        round_ss = build_tensor_step_round_fn(
            trainer, cfg_ss, agg, sharding, donate_state=False)
        jax.eval_shape(round_ss, gv, agg_state, x, y, counts, rng)
        return
    codec = _point_codec(point, cfg)
    if codec is None:
        round_fn = build_tensor_round_fn(
            trainer, cfg, agg, sharding, donate_state=True)
        jax.eval_shape(round_fn, gv, agg_state, x, y, counts, rng)
        return
    # graft-codec twins: the codec-on round carries the wrapped
    # {"agg", "codec"} state (per-clients-device residual rows), a
    # distinct signature per codec; k matches the COMMS-budget twin
    round_c = build_tensor_round_fn(
        trainer, cfg, agg, sharding, donate_state=True, codec=codec)

    def init_st(g):
        resid = jax.tree.map(
            lambda l: jnp.zeros(
                (2,) + (l.shape
                        if jnp.issubdtype(l.dtype, jnp.inexact)
                        else ()), l.dtype), g)
        return {"agg": agg.init_state(g), "codec": resid}

    jax.eval_shape(round_c, gv, jax.eval_shape(init_st, gv),
                   x, y, counts, rng)


def _trace_sharded_point(point, ctx) -> None:
    """The shard_map round and its codec twins (CodecAggregator state, one
    residual row per cohort slot, sharded over 'clients'). EVERY codec
    level the spec arms traces here — the hand enumeration's [:1] slice
    was exactly how the topk twin stayed ungated."""
    from jax.sharding import Mesh

    from fedml_tpu.parallel.sharded import build_sharded_round_fn

    trainer, cfg, agg = ctx["trainer"], ctx["cfg"], ctx["agg"]
    gv, rng = ctx["gv"], ctx["rng"]
    c = point.opt("mesh")[0]
    mesh = Mesh(np.array(jax.devices()[:c]), ("clients",))
    sharded_args = (
        jax.ShapeDtypeStruct((c, 4) + ctx["shape"][1:], ctx["in_dtype"]),
        jax.ShapeDtypeStruct((c, 4), jnp.int32),
        jax.ShapeDtypeStruct((c,), jnp.int32), rng)
    codec = _point_codec(point, cfg)
    if codec is None:
        round_fn = build_sharded_round_fn(trainer, cfg, agg, mesh)
        jax.eval_shape(round_fn, gv, ctx["agg_state"], *sharded_args)
        return
    from fedml_tpu.codecs.transport import CodecAggregator

    wrapped = CodecAggregator(codec, agg, slots=c)
    round_c = build_sharded_round_fn(trainer, cfg, wrapped, mesh)
    jax.eval_shape(round_c, gv, jax.eval_shape(wrapped.init_state, gv),
                   *sharded_args)


def _trace_hier_point(point, ctx) -> None:
    from jax.sharding import Mesh

    from fedml_tpu.parallel.hierarchical import (
        build_sharded_hierarchical_round_fn)

    g, c = point.opt("mesh")
    mesh = Mesh(np.array(jax.devices()[:g * c]).reshape(g, c),
                ("groups", "clients"))
    round_fn = build_sharded_hierarchical_round_fn(
        ctx["trainer"], ctx["cfg"], mesh, group_comm_round=2)
    n = 4
    jax.eval_shape(round_fn, ctx["gv"],
                   jax.ShapeDtypeStruct((g, c, n) + ctx["shape"][1:],
                                        ctx["in_dtype"]),
                   jax.ShapeDtypeStruct((g, c, n), jnp.int32),
                   jax.ShapeDtypeStruct((g, c), jnp.int32), ctx["rng"])


def _trace_silo_point(point, ctx) -> None:
    # silo grouping needs convs to group — mirror the jaxpr target
    jaxpr = round_jaxpr(point.opt("model"), point.opt("dtype"), "fedavg",
                        silo_threshold=32)
    del jaxpr


def enumerate_drive_programs(drive: str) -> dict:
    """{program name: distinct signature count} for one registered drive
    config — the static half of the compile budget, DERIVED from the
    declarative spec (core/spec.py DRIVE_SPECS): every declared
    ProgramPoint is traced through the real builders, so the enumeration
    crashes the moment a signature arm drifts, and the budget names are
    the spec's names. All programs trace on the lr/f32/fedavg example
    (signature COUNT does not depend on the model), except silo which
    needs a conv model to group."""
    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.core.spec import DRIVE_SPECS, EVAL_POINTS, drive_points

    if drive not in DRIVE_SPECS:
        raise ValueError(f"unknown drive config {drive!r}; "
                         f"known: {sorted(DRIVE_SPECS)}")
    trainer, shape, in_dtype = _tiny_trainer("lr", "float32")
    cfg = FedConfig(model="lr", batch_size=2, epochs=1, dtype="float32")
    agg = make_aggregator("fedavg", cfg)
    gv, x, y, counts, rng = _abstract_round_args(trainer, shape, in_dtype)
    ctx = {"trainer": trainer, "shape": shape, "in_dtype": in_dtype,
           "cfg": cfg, "agg": agg, "gv": gv, "x": x, "y": y,
           "counts": counts, "rng": rng,
           "agg_state": jax.eval_shape(agg.init_state, gv)}

    tracers = {"engine.round": _trace_engine_round,
               "engine.superstep": _trace_superstep,
               "tensor.round": _trace_tensor_point,
               "tensor.step": _trace_tensor_point,
               "sharded.round": _trace_sharded_point,
               "hier.round": _trace_hier_point,
               "silo.round": _trace_silo_point}
    eval_families = {p.family for p in EVAL_POINTS}

    programs = {}
    buffered_points = []
    for point in drive_points(drive):
        if point.family in eval_families:
            continue  # the shared evals trace once, below
        if point.family.startswith("buffered."):
            buffered_points.append(point)
            continue
        tracers[point.family](point, ctx)
        programs[point.name] = point.signatures
    if buffered_points:
        # the buffered family traces as one group (admit needs the client
        # step's result shapes); codec-on admit twins ride the declared
        # codec levels — k matches the COMMS-budget twin
        codecs = [_point_codec(p, cfg) for p in buffered_points
                  if p.family == "buffered.admit" and p.opt("codec")]
        traced = _trace_buffered_programs(
            trainer, cfg, agg, gv, ctx["agg_state"], x, y, counts, rng,
            codecs=codecs)
        declared = {p.name: p.signatures for p in buffered_points}
        if set(traced) != set(declared):
            raise RuntimeError(
                f"buffered tracer/spec drift for drive {drive!r}: traced "
                f"{sorted(traced)} != declared {sorted(declared)}")
        programs.update(traced)
    if DRIVE_SPECS[drive].evals:
        programs.update(_drive_eval_programs(trainer, shape, in_dtype,
                                             gv, rng))
    return dict(sorted(programs.items()))


def run_all(repo_root: str, include_models: bool = True,
            include_ast: bool = True) -> Report:
    """The full lint pass the CLI and tests/test_lint.py run."""
    from fedml_tpu.analysis.ast_engine import lint_tree

    report = Report()
    missing = models_missing_examples()
    for m in missing:
        report.extend([Finding(
            "dtype-policy", f"model:{m}",
            "registered without a MODEL_EXAMPLES row — the dtype sweep "
            "cannot see it; add one in fedml_tpu/analysis/targets.py")])
    for target, jaxpr, policy in iter_jaxpr_targets(include_models):
        report.extend(lint_jaxpr(jaxpr, target, policy=policy))
        report.mark(target)
    report.extend(check_chunked_donation())
    report.mark("engine.chunked.chunk_fn[lr]")
    report.extend(check_round_retrace())
    report.mark("engine.round.retrace[lr]")
    report.extend(check_model_partitions())
    report.mark("partition-coverage[registry]")
    report.extend(check_tensor_rule_coverage())
    report.mark("partition-coverage[tensor-rules]")
    from fedml_tpu.analysis.jaxpr_engine import (
        check_unconstrained_intermediate)

    step_jaxpr, t_sz = tensor_step_jaxpr()
    report.extend(check_unconstrained_intermediate(
        step_jaxpr, "tensor.step[tformer,f32,2x4]", tensor_axis_size=t_sz))
    report.mark("tensor.step[tformer,f32,2x4]")
    if include_ast:
        report.extend(lint_tree(repo_root, ["fedml_tpu", "tools"]))
        report.mark("ast[fedml_tpu,tools]")
    return report
