"""The main path's programs compile for the chip — asked of the TPU compiler
in this sandbox, for a v5e that is described and not attached
(on-chip-measurement guide, section 2.3). Nothing runs: a pass says that the
chip's compiler accepts the program and that it fits 16 GB, never how fast
it is.

Rules this file keeps (the guide's): the topology is described only inside
the module-scoped `topo` fixture, which skips when it cannot be; nothing
touches `jax.experimental.topologies` while any module is imported; the
fixtures are not autouse and live here, in the ONE file that describes the
chip; every compile happens in the test's own process; the persistent
compile cache is off around them (an entry compiled for an absent chip
cannot be read back and would only warn).

`tests/conftest.py` appends `--xla_backend_optimization_level=0` to
XLA_FLAGS for the whole fast suite. The TPU compile here does not read it:
with and without the flag the flagship round compiles to the same
temp/argument/code bytes (measured while writing this file), so what these
tests accept is what the chip's compiler accepts at its own default level.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16e9  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, tree):
    """ShapeDtypeStructs of `tree`'s leaves, placed on the described chip."""
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sharding),
        tree)


def _fits(compiled) -> float:
    mem = compiled.memory_analysis()
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert need < HBM_BYTES, f"needs {need / 1e9:.2f} GB of a 16 GB chip"
    return need


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("shape,dtype", [
    ((2, 2048, 8, 64), jnp.float32),
    ((2, 8192, 8, 64), jnp.bfloat16),
], ids=["T2048-f32", "T8192-bf16"])
@pytest.mark.parametrize("block", [128, None], ids=["128x128", "own-tiles"])
def test_flash_attention_compiles(one_chip, no_compile_cache, shape, dtype,
                                  direction, block):
    from fedml_tpu.ops.attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, True, block, block, False)

    def loss(q, k, v):
        return jnp.sum(fwd(q, k, v).astype(jnp.float32))

    fn = fwd if direction == "forward" else jax.grad(loss, argnums=(0, 1, 2))
    arg = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(fn).lower(arg, arg, arg).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("block", [128, None], ids=["128x128", "own-tiles"])
def test_flash_attention_compiles_at_latent_attention_widths(
        one_chip, no_compile_cache, block):
    """q/k 192 wide, v 128 wide, an explicit scale: the three kernels as
    DeepSeek-V2-Lite's training step calls them (4 sequences x 16 heads,
    T 1024, bfloat16), on explicit tiles and on the kernel's own choice
    (one 1024 x 1024 tile a head), which is what the model runs. The
    benchmark's roofline reader finds the kernels by these names."""
    from fedml_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, block, block, False,
                                       0.1147).astype(jnp.float32))

    qk = jax.ShapeDtypeStruct((4, 1024, 16, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((4, 1024, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(qk, qk, v).compile()
    text = compiled.as_text()
    assert all(f"flash_attention_{k}" in text for k in ("fwd", "dq", "dkv"))
    _fits(compiled)


@pytest.mark.parametrize("trans_rhs", [False, True])
def test_grouped_matmul_compiles_at_the_cells_widths(one_chip,
                                                     no_compile_cache,
                                                     trans_rhs):
    """`ops/moe.py`'s grouped product at DeepSeek-V2-Lite's widths: 64
    experts of 2048 x 1408 bfloat16, both lanes' 2 x 4096 x 6 pairs in 448
    tiles of 128 rows, one expert's whole matrix a block (11.5 MB of VMEM
    double-buffered)."""
    from fedml_tpu.ops.moe import grouped_matmul

    rows, tiles = 57344, 448
    width = 1408 if trans_rhs else 2048
    args = (jax.ShapeDtypeStruct((rows, width), jnp.bfloat16),
            jax.ShapeDtypeStruct((64, 2048, 1408), jnp.bfloat16),
            jax.ShapeDtypeStruct((tiles,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32))
    compiled = jax.jit(lambda lhs, rhs, grp, nt: grouped_matmul(
        lhs, rhs, grp, nt, trans_rhs=trans_rhs, interpret=False)).lower(
            *_on(one_chip, args)).compile()
    assert "moe_grouped_matmul" in compiled.as_text()
    _fits(compiled)


def _round_program(one_chip, model, output_dim, sample_shape, clients,
                   samples, lanes=None, **cfg_kw):
    """The round program `FedAvgAPI` builds for the CLI's default drive
    (pipelined: cohort buffers donated, ledger stats collected), lowered
    from eval_shape'd variables on the described chip."""
    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.algorithms.engine import build_round_fn
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.core.trainer import ClassificationTrainer
    from fedml_tpu.models.registry import create_model

    cfg = FedConfig(model=model, client_num_in_total=clients,
                    client_num_per_round=clients, epochs=1, **cfg_kw)
    trainer = ClassificationTrainer(
        create_model(model, output_dim=output_dim, dtype=cfg.dtype))
    agg = make_aggregator("fedavg", cfg)
    gv = jax.eval_shape(lambda: trainer.init(
        jax.random.PRNGKey(0), jnp.zeros((1,) + sample_shape)))
    state = jax.eval_shape(agg.init_state, gv)
    round_fn = build_round_fn(trainer, cfg, agg, donate_data=True,
                              collect_stats=True, lanes=lanes)
    args = _on(one_chip, (
        gv, state,
        jax.ShapeDtypeStruct((clients, samples) + sample_shape, jnp.float32),
        jax.ShapeDtypeStruct((clients, samples), jnp.int32),
        jax.ShapeDtypeStruct((clients,), jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))))
    return round_fn.jitted.lower(*args).compile()


@pytest.mark.parametrize("lanes", [None, 5])
def test_flagship_round_compiles(one_chip, no_compile_cache, lanes):
    """engine.round for CNN_DropOut, 10 clients x 480 x 28x28, bs 20, f32 —
    what `chip_smoke.py`'s flagship phase dispatches every round: a lane a
    client, and packed onto the 5 lanes `FedAvgAPI` derives for FEMNIST's
    writers (engine.packed_lanes)."""
    compiled = _round_program(
        one_chip, "cnn", 62, (28, 28, 1), clients=10, samples=480,
        lanes=lanes, batch_size=20, lr=0.1)
    _fits(compiled)


def test_flagship_federation_eval_fits_beside_its_split(one_chip,
                                                        no_compile_cache):
    """The resident all-clients eval at 3400 FEMNIST writers, in the chunk
    geometry `FedAvgAPI` picks (fedavg._eval_chunk). At the old 64-client
    chunk this program needed 19.1 GB (8.7 GB of conv outputs per step on
    top of the split and XLA's converted copy of it) and the README's first
    command could not evaluate on a 16 GB chip."""
    from fedml_tpu.algorithms.engine import build_federation_eval_fn
    from fedml_tpu.algorithms.fedavg import _eval_chunk
    from fedml_tpu.core.trainer import ClassificationTrainer
    from fedml_tpu.models.registry import create_model

    clients, n_max = 3400, 480
    chunk = _eval_chunk(jax.ShapeDtypeStruct(
        (clients, n_max, 28, 28, 1), jnp.float32), clients)
    nc = -(-clients // chunk)
    trainer = ClassificationTrainer(create_model("cnn", output_dim=62))
    gv = jax.eval_shape(lambda: trainer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1))))
    args = _on(one_chip, (
        gv,
        jax.ShapeDtypeStruct((nc, chunk, n_max, 28, 28, 1), jnp.float32),
        jax.ShapeDtypeStruct((nc, chunk, n_max), jnp.int32),
        jax.ShapeDtypeStruct((nc, chunk), jnp.int32)))
    compiled = build_federation_eval_fn(trainer).lower(*args).compile()
    # leave room for the test split, the params and the staged cohorts
    assert _fits(compiled) < 14e9


@pytest.mark.slow  # ~50 s of TPU compile
def test_cross_silo_round_compiles(one_chip, no_compile_cache):
    """engine.round for ResNet-56 in bf16, 10 silos x 500 x 32x32x3, bs 64 —
    `chip_smoke.py`'s cross_silo phase."""
    compiled = _round_program(
        one_chip, "resnet56", 10, (32, 32, 3), clients=10, samples=500,
        batch_size=64, dtype="bfloat16")
    _fits(compiled)


@pytest.mark.slow  # ~60 s of TPU compile
def test_dsv2lite_lora_round_compiles_and_never_returns_its_base(
        one_chip, no_compile_cache):
    """engine.round for `benchmarks/configs/dsv2lite_lora.json` (5 layers of
    DeepSeek-V2-Lite, bfloat16 base, rank-16 adapters, 2 lanes x 4 x 1,024
    tokens a step): fits beside its 5.7 GB base, and its outputs hold no
    base (a second one would not fit with two rounds in flight)."""
    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.algorithms.engine import build_round_fn
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.core.trainer import NWPTrainer
    from fedml_tpu.models.lora import LoRATrainer
    from fedml_tpu.models.registry import create_model
    from fedml_tpu.ops import attention, moe

    cfg = FedConfig(model="deepseek_v2", client_num_in_total=20,
                    client_num_per_round=2, epochs=1, batch_size=4, lr=0.03,
                    lora_rank=16, dtype="bfloat16")
    trainer = LoRATrainer(NWPTrainer(create_model(
        "deepseek_v2", output_dim=102400, dtype="bfloat16",
        config="benchmarks/configs/dsv2lite_lora.json")), rank=16)
    agg = make_aggregator("fedavg", cfg)
    gv = jax.eval_shape(lambda: trainer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1024), jnp.int32)))
    tokens = jax.ShapeDtypeStruct((2, 16, 1024), jnp.int32)
    args = _on(one_chip, (
        gv, jax.eval_shape(agg.init_state, gv), tokens, tokens,
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))))
    # the test steers the kernels' CPU branch (the guide's section 2)
    was = moe.interpret_off_chip, attention.interpret_off_chip
    moe.interpret_off_chip = attention.interpret_off_chip = lambda k: False
    try:
        compiled = build_round_fn(
            trainer, cfg, agg, donate_data=True,
            collect_stats=True).jitted.lower(*args).compile()
    finally:
        moe.interpret_off_chip, attention.interpret_off_chip = was
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 5.6e9 > 1e8 > mem.output_size_in_bytes
    assert _fits(compiled) < 10e9


def test_share_dispatch_compiles_with_both_paths_at_the_cells_shapes(
        one_chip, no_compile_cache):
    """`ops/moe.py::routed_experts_share` as Kimi Linear's training step runs
    it: 64 of 256 experts of 2304 x 1024 bfloat16 held, 2 lanes x 8,192
    tokens x top-8 in one joint call, forward and backward. The bounded path
    (73,728 rows) and the worst-case one (139,264) are both in the program,
    each behind one conditional a direction: 3 + 3 grouped products forward,
    3 + 5 backward (the worst case makes g, u again)."""
    from fedml_tpu.ops import moe

    def step(x, idx, gate, wg, wu, wd):
        def loss(x, gate):
            y, worst = moe.routed_experts_share(x, idx, gate, wg, wu, wd,
                                                0, 256)
            return jnp.sum(y.astype(jnp.float32) ** 2), worst
        return jax.value_and_grad(loss, (0, 1), has_aux=True)(x, gate)

    assert moe.share_rows(2 * 8192 * 8, 64, 256) == 73728
    args = _on(one_chip, (
        jax.ShapeDtypeStruct((2, 8192, 2304), jnp.bfloat16),
        jax.ShapeDtypeStruct((2, 8192, 8), jnp.int32),
        jax.ShapeDtypeStruct((2, 8192, 8), jnp.float32),
        jax.ShapeDtypeStruct((64, 2304, 1024), jnp.bfloat16),
        jax.ShapeDtypeStruct((64, 2304, 1024), jnp.bfloat16),
        jax.ShapeDtypeStruct((64, 1024, 2304), jnp.bfloat16)))
    was = moe.interpret_off_chip
    moe.interpret_off_chip = lambda k: False    # the guide's section 2
    try:
        compiled = jax.jit(jax.vmap(
            step, in_axes=(0, 0, 0, None, None, None))).lower(*args).compile()
    finally:
        moe.interpret_off_chip = was
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 14
    assert text.count(" conditional(") == 2
    assert "bf16[73728,1024]" in text and "bf16[139264,1024]" in text
    assert _fits(compiled) < 6e9


def test_kda_kernels_compile_at_the_cells_shapes(one_chip, no_compile_cache):
    """`ops/kda.py`'s two Pallas calls as Kimi Linear's training step runs
    them: 2 lanes x 2 sequences x 4,096 tokens x 32 heads of 128, bfloat16
    q, k, v, float32 g and beta, forward and backward under the lanes' vmap,
    on the kernel's own chunk. The benchmark's readers find them by name."""
    from fedml_tpu.ops.kda import kda

    def loss(q, k, v, g, beta):
        o = jax.vmap(lambda *a: kda(*a, interpret=False))(q, k, v, g, beta)
        return jnp.sum(o.astype(jnp.float32))

    wide = (2, 2, 4096, 32, 128)
    args = _on(one_chip, (
        *[jax.ShapeDtypeStruct(wide, jnp.bfloat16)] * 3,
        jax.ShapeDtypeStruct(wide, jnp.float32),
        jax.ShapeDtypeStruct(wide[:-1], jnp.float32)))
    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    assert "kda_fwd" in text and "kda_bwd" in text
    _fits(compiled)


@pytest.mark.slow  # ~2 min of TPU compile
def test_kimi_linear_lora_round_compiles_beside_its_base(one_chip,
                                                         no_compile_cache):
    """engine.round for `benchmarks/configs/kimi_linear_lora.json` (5 layers
    of Kimi-Linear-48B-A3B, 64 of 256 experts held, bfloat16 base, rank-16
    adapters, 2 lanes x 2 x 4,096 tokens a step): fits beside its 4.6 GB
    base and returns none of it."""
    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.algorithms.engine import build_round_fn
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.core.trainer import NWPTrainer
    from fedml_tpu.models.lora import LoRATrainer
    from fedml_tpu.models.registry import create_model
    from fedml_tpu.ops import attention, kda, moe

    cfg = FedConfig(model="kimi_linear", client_num_in_total=20,
                    client_num_per_round=2, epochs=1, batch_size=2, lr=0.03,
                    lora_rank=16, dtype="bfloat16")
    trainer = LoRATrainer(NWPTrainer(create_model(
        "kimi_linear", output_dim=40960, dtype="bfloat16",
        config="benchmarks/configs/kimi_linear_lora.json")), rank=16)
    agg = make_aggregator("fedavg", cfg)
    gv = jax.eval_shape(lambda: trainer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))
    tokens = jax.ShapeDtypeStruct((2, 8, 4096), jnp.int32)
    args = _on(one_chip, (
        gv, jax.eval_shape(agg.init_state, gv), tokens, tokens,
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))))
    mods = (moe, attention, kda)
    was = [m.interpret_off_chip for m in mods]
    for m in mods:
        m.interpret_off_chip = lambda k: False
    try:
        compiled = build_round_fn(
            trainer, cfg, agg, donate_data=True,
            collect_stats=True).jitted.lower(*args).compile()
    finally:
        for m, f in zip(mods, was):
            m.interpret_off_chip = f
    mem = compiled.memory_analysis()
    print(mem)
    assert mem.argument_size_in_bytes > 4.5e9 > 1e8 > mem.output_size_in_bytes
    assert _fits(compiled) < 13e9


def test_ssm_kernels_compile_at_the_cells_shapes(one_chip, no_compile_cache):
    """`ops/ssm.py`'s two Pallas calls as the Jamba training step runs them:
    2 lanes x 1 sequence x 8,192 tokens x 5,120 channels x 16 states,
    bfloat16 x, float32 delta, A, B and C, forward and backward under the
    lanes' vmap, on the kernel's own chunk and block. The benchmark's
    readers find them by name."""
    from fedml_tpu.ops.ssm import selective_scan

    def loss(x, delta, A, B, C):
        y = jax.vmap(lambda x, d, b, c: selective_scan(
            x, d, A, b, c, interpret=False))(x, delta, B, C)
        return jnp.sum(y)

    wide, narrow = (2, 1, 8192, 5120), (2, 1, 8192, 16)
    args = _on(one_chip, (
        jax.ShapeDtypeStruct(wide, jnp.bfloat16),
        jax.ShapeDtypeStruct(wide, jnp.float32),
        jax.ShapeDtypeStruct((5120, 16), jnp.float32),
        *[jax.ShapeDtypeStruct(narrow, jnp.float32)] * 2))
    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(*args).compile()
    text = compiled.as_text()
    assert "ssm_scan_fwd" in text and "ssm_scan_bwd" in text
    _fits(compiled)


@pytest.mark.slow  # minutes of TPU compile
def test_jamba2_lora_round_compiles_beside_its_base(one_chip,
                                                    no_compile_cache):
    """engine.round for `benchmarks/configs/jamba2_lora.json` (14 layers of
    AI21-Jamba2-3B, bfloat16 base, rank-16 adapters, 2 lanes x 1 x 8,192
    tokens a step): fits beside its 3.2 GB base and returns none of it."""
    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.algorithms.engine import build_round_fn
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.core.trainer import NWPTrainer
    from fedml_tpu.models.lora import LoRATrainer
    from fedml_tpu.models.registry import create_model
    from fedml_tpu.ops import attention, ssm

    cfg = FedConfig(model="jamba", client_num_in_total=20,
                    client_num_per_round=2, epochs=1, batch_size=1, lr=0.03,
                    lora_rank=16, dtype="bfloat16")
    trainer = LoRATrainer(NWPTrainer(create_model(
        "jamba", output_dim=65536, dtype="bfloat16",
        config="benchmarks/configs/jamba2_lora.json")), rank=16)
    agg = make_aggregator("fedavg", cfg)
    gv = jax.eval_shape(lambda: trainer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))
    tokens = jax.ShapeDtypeStruct((2, 4, 8192), jnp.int32)
    args = _on(one_chip, (
        gv, jax.eval_shape(agg.init_state, gv), tokens, tokens,
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))))
    mods = (attention, ssm)
    was = [m.interpret_off_chip for m in mods]
    for m in mods:
        m.interpret_off_chip = lambda k: False
    try:
        compiled = build_round_fn(
            trainer, cfg, agg, donate_data=True,
            collect_stats=True).jitted.lower(*args).compile()
    finally:
        for m, f in zip(mods, was):
            m.interpret_off_chip = f
    mem = compiled.memory_analysis()
    print(mem)
    assert mem.argument_size_in_bytes > 3.1e9 > 1e8 > mem.output_size_in_bytes
    assert _fits(compiled) < 15.5e9


@pytest.mark.parametrize("block", [256, 512, None],
                         ids=["256", "512", "own-tiles"])
def test_the_band_compiles_at_the_window_layers_widths(one_chip,
                                                       no_compile_cache,
                                                       block):
    """The three band kernels (`window=1024`) as `mellum2_lora`'s window
    layers call them: 2 lanes x 8,192 tokens x 32 heads of 128, bfloat16,
    forward and backward, on explicit tiles and on the kernel's own
    (1,024, no wider than the band). The benchmark's band roofline finds them by these
    names."""
    from fedml_tpu.ops.attention import flash_attention

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, block, block, False,
                                       0.088, 1024).astype(jnp.float32))

    arg = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16,
                               sharding=one_chip)
    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(arg, arg, arg).compile()
    text = compiled.as_text()
    assert all(f"flash_window_{k}" in text for k in ("fwd", "dq", "dkv"))
    assert "flash_attention_" not in text
    _fits(compiled)


@pytest.mark.slow  # a minute of TPU compile
def test_mellum2_lora_round_compiles_beside_its_base(one_chip,
                                                     no_compile_cache):
    """engine.round for `benchmarks/configs/mellum2_lora.json` (4 layers of
    Mellum2-12B-A2.5B, bfloat16 base, rank-16 adapters, 2 lanes x 1 x 8,192
    tokens a step): fits beside its 4.25 GB base, returns none of it, and
    holds both kinds of flash call and the grouped expert product."""
    from fedml_tpu.algorithms.aggregators import make_aggregator
    from fedml_tpu.algorithms.engine import build_round_fn
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.core.trainer import NWPTrainer
    from fedml_tpu.models.lora import LoRATrainer
    from fedml_tpu.models.registry import create_model
    from fedml_tpu.ops import attention, moe

    cfg = FedConfig(model="mellum", client_num_in_total=20,
                    client_num_per_round=2, epochs=1, batch_size=1, lr=0.03,
                    lora_rank=16, dtype="bfloat16")
    trainer = LoRATrainer(NWPTrainer(create_model(
        "mellum", output_dim=98304, dtype="bfloat16",
        config="benchmarks/configs/mellum2_lora.json")), rank=16)
    agg = make_aggregator("fedavg", cfg)
    gv = jax.eval_shape(lambda: trainer.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))
    tokens = jax.ShapeDtypeStruct((2, 4, 8192), jnp.int32)
    args = _on(one_chip, (
        gv, jax.eval_shape(agg.init_state, gv), tokens, tokens,
        jax.ShapeDtypeStruct((2,), jnp.int32),
        jax.eval_shape(lambda: jax.random.PRNGKey(0))))
    mods = (attention, moe)
    was = [m.interpret_off_chip for m in mods]
    for m in mods:
        m.interpret_off_chip = lambda k: False
    try:
        compiled = build_round_fn(
            trainer, cfg, agg, donate_data=True,
            collect_stats=True).jitted.lower(*args).compile()
    finally:
        for m, f in zip(mods, was):
            m.interpret_off_chip = f
    mem = compiled.memory_analysis()
    print(mem)
    assert mem.argument_size_in_bytes > 4.2e9 > 1e8 > mem.output_size_in_bytes
    assert _fits(compiled) < 15.5e9
    text = compiled.as_text()
    assert all(k in text for k in ("flash_window_fwd", "flash_attention_fwd",
                                   "moe_grouped_matmul"))
