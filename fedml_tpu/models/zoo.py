"""Name -> module registration (reference create_model, main_fedavg.py:224-260)."""

from __future__ import annotations

from fedml_tpu.models.registry import register_model
from fedml_tpu.models.linear import LogisticRegression, DenseMLP, ReferenceMLP
from fedml_tpu.models.cnn import CNN_OriginalFedAvg, CNN_DropOut, CNNCifar, HAR_CNN
from fedml_tpu.models import resnet as _resnet
from fedml_tpu.models.mobilenet import MobileNet
from fedml_tpu.models.rnn import RNN_OriginalFedAvg, RNN_StackOverFlow
from fedml_tpu.models.vgg import VGG


def _compute_dtype(kw):
    """'bfloat16' -> jnp.bfloat16 (MXU-native), else None (flax promotes to
    f32 against f32 params) — one mapping for every dtype-aware factory."""
    import jax.numpy as jnp

    return jnp.bfloat16 if kw.get("dtype") == "bfloat16" else None


@register_model("lr")
def _lr(output_dim, **kw):
    return LogisticRegression(output_dim=output_dim, flatten=kw.get("flatten", True),
                              dtype=_compute_dtype(kw))


@register_model("mlp")
def _mlp(output_dim, **kw):
    return DenseMLP(output_dim=output_dim,
                    hidden=tuple(kw.get("hidden", (1024, 512, 256, 128))),
                    dtype=_compute_dtype(kw))


@register_model("purchasemlp")
def _purchasemlp(output_dim, **kw):
    # reference dense_mlp.py:11 PurchaseMLP(input_dim=600, n_classes=100)
    return ReferenceMLP(output_dim=output_dim, hidden=(256,),
                        dtype=_compute_dtype(kw))


@register_model("texasmlp")
def _texasmlp(output_dim, **kw):
    # reference dense_mlp.py:53 TexasMLP(input_dim=6169, n_classes=100)
    return ReferenceMLP(output_dim=output_dim, hidden=(1024, 512),
                        dtype=_compute_dtype(kw))


@register_model("cnn_fedavg")
def _cnn_fedavg(output_dim, **kw):
    import jax.numpy as jnp

    return CNN_OriginalFedAvg(output_dim=output_dim,
                              dtype=_compute_dtype(kw) or jnp.float32)


@register_model("cnn")
def _cnn(output_dim, **kw):
    # reference "cnn" for femnist = CNN_DropOut (main_fedavg.py:233-236)
    import jax.numpy as jnp

    return CNN_DropOut(output_dim=output_dim,
                       dtype=_compute_dtype(kw) or jnp.float32)


@register_model("cnn_cifar")
def _cnn_cifar(output_dim, **kw):
    return CNNCifar(output_dim=output_dim, dtype=_compute_dtype(kw))


@register_model("har_cnn")
def _har_cnn(output_dim, **kw):
    return HAR_CNN(output_dim=output_dim, dtype=_compute_dtype(kw))


# CIFAR ResNets (reference resnet.py:218,241 / resnet_cifar.py) ---------------
for _name in ("resnet20", "resnet32", "resnet44", "resnet56", "resnet56_s2d",
              "resnet110", "resnet18", "resnet34", "resnet50"):
    def _make(output_dim, _f=getattr(_resnet, _name), **kw):
        return _f(output_dim=output_dim, group_norm=kw.get("group_norm", 0),
                  dtype=_compute_dtype(kw))

    register_model(_name)(_make)


@register_model("resnet18_gn")
def _resnet18_gn(output_dim, **kw):
    # fed_cifar100 model: GroupNorm replaces BN for FL (BASELINE.md 44.7 target)
    return _resnet.resnet18(output_dim=output_dim, group_norm=kw.get("group_norm", 2),
                            dtype=_compute_dtype(kw))


@register_model("mobilenet")
def _mobilenet(output_dim, **kw):
    return MobileNet(output_dim=output_dim, alpha=kw.get("alpha", 1.0),
                     dtype=_compute_dtype(kw))


@register_model("rnn")
def _rnn(output_dim, **kw):
    # shakespeare next-char model (reference main_fedavg.py "rnn" -> vocab 90)
    return RNN_OriginalFedAvg(vocab_size=kw.get("vocab_size", output_dim),
                              per_position=kw.get("per_position", False),
                              dtype=_compute_dtype(kw))


@register_model("rnn_stackoverflow")
def _rnn_so(output_dim, **kw):
    return RNN_StackOverFlow(vocab_size=kw.get("vocab_size", 10000),
                             dtype=_compute_dtype(kw))


@register_model("vgg11")
def _vgg11(output_dim, **kw):
    return VGG(variant="vgg11", output_dim=output_dim, dtype=_compute_dtype(kw))


@register_model("vgg16")
def _vgg16(output_dim, **kw):
    return VGG(variant="vgg16", output_dim=output_dim, dtype=_compute_dtype(kw))


@register_model("deeplab")
def _deeplab(output_dim, **kw):
    # FedSeg encoder-decoder (reference fedseg ships the algorithm without a
    # bundled model; DeepLabV3+ is the upstream family it targets)
    from fedml_tpu.models.segmentation import DeepLabV3Plus

    return DeepLabV3Plus(output_dim=output_dim, width=kw.get("width", 32),
                         dtype=_compute_dtype(kw))


@register_model("fcn")
def _fcn(output_dim, **kw):
    from fedml_tpu.models.segmentation import SimpleFCN

    return SimpleFCN(output_dim=output_dim, width=kw.get("width", 16),
                     dtype=_compute_dtype(kw))


@register_model("transformer_nwp")
def _transformer_nwp(output_dim, **kw):
    # long-context NWP model (per-position logits like rnn_stackoverflow);
    # flash-attention core, ring-attention-ready across a mesh
    from fedml_tpu.models.transformer import TransformerLM

    return TransformerLM(vocab_size=kw.get("vocab_size", output_dim),
                         d_model=kw.get("d_model", 128),
                         heads=kw.get("heads", 4),
                         num_layers=kw.get("num_layers", 2),
                         max_len=kw.get("max_len", 512),
                         dtype=_compute_dtype(kw))


@register_model("deepseek_v2")
def _deepseek_v2(output_dim, **kw):
    # DeepSeek-V2 decoder (latent attention, routed + shared experts). Its
    # sizes are a configuration of the published keys, never arguments:
    # `config` is that dict or the path of a JSON that holds it
    # (--model_config); None is DeepSeek-V2-Lite as published
    from fedml_tpu.models.deepseek_v2 import DeepseekV2Config, DeepseekV2LM

    config = kw.get("config")
    cfg = (DeepseekV2Config.from_dict(config) if isinstance(config, dict)
           else DeepseekV2Config.from_file(config))
    if output_dim != cfg.vocab_size:
        raise ValueError(f"the data has {output_dim} token ids, the model "
                         f"configuration a vocabulary of {cfg.vocab_size}")
    import jax.numpy as jnp

    return DeepseekV2LM(cfg, dtype=_compute_dtype(kw) or jnp.float32)


@register_model("kimi_linear")
def _kimi_linear(output_dim, **kw):
    # Kimi Linear decoder (KDA layers beside NoPE latent attention, sigmoid-
    # routed experts, optionally a share of them). Sizes as `deepseek_v2`'s:
    # `config` is the published keys' dict or the path of a JSON that holds
    # them (--model_config); None is Kimi-Linear-48B-A3B as published
    from fedml_tpu.models.kimi_linear import KimiLinearConfig, KimiLinearLM

    config = kw.get("config")
    cfg = (KimiLinearConfig.from_dict(config) if isinstance(config, dict)
           else KimiLinearConfig.from_file(config))
    if output_dim != cfg.vocab_size:
        raise ValueError(f"the data has {output_dim} token ids, the model "
                         f"configuration a vocabulary of {cfg.vocab_size}")
    import jax.numpy as jnp

    return KimiLinearLM(cfg, dtype=_compute_dtype(kw) or jnp.float32)


@register_model("jamba")
def _jamba(output_dim, **kw):
    # Jamba decoder (Mamba-1 selective-scan layers beside multi-query NoPE
    # attention, dense SwiGLU, tied head). Sizes as `deepseek_v2`'s: `config`
    # is the published keys' dict or the path of a JSON that holds them
    # (--model_config); None is AI21-Jamba2-3B as published
    from fedml_tpu.models.jamba import JambaConfig, JambaLM

    config = kw.get("config")
    cfg = (JambaConfig.from_dict(config) if isinstance(config, dict)
           else JambaConfig.from_file(config))
    if output_dim != cfg.vocab_size:
        raise ValueError(f"the data has {output_dim} token ids, the model "
                         f"configuration a vocabulary of {cfg.vocab_size}")
    import jax.numpy as jnp

    return JambaLM(cfg, dtype=_compute_dtype(kw) or jnp.float32)


@register_model("mellum")
def _mellum(output_dim, **kw):
    # Mellum 2 decoder (grouped-query attention, sliding-window and full
    # layers by `layer_types` with a rotary setting each, routed experts top-k
    # renormalised). Sizes as `deepseek_v2`'s: `config` is the published keys'
    # dict or the path of a JSON that holds them (--model_config); None is
    # Mellum2-12B-A2.5B as published
    from fedml_tpu.models.mellum import MellumConfig, MellumLM

    config = kw.get("config")
    cfg = (MellumConfig.from_dict(config) if isinstance(config, dict)
           else MellumConfig.from_file(config))
    if output_dim != cfg.vocab_size:
        raise ValueError(f"the data has {output_dim} token ids, the model "
                         f"configuration a vocabulary of {cfg.vocab_size}")
    import jax.numpy as jnp

    return MellumLM(cfg, dtype=_compute_dtype(kw) or jnp.float32)


@register_model("mobilenet_v3")
def _mobilenet_v3(output_dim, **kw):
    # reference main_fedavg.py "mobilenet_v3" -> MobileNetV3(model_mode=...)
    from fedml_tpu.models.mobilenet_v3 import MobileNetV3

    return MobileNetV3(output_dim=output_dim,
                       mode=kw.get("mode", "LARGE"),
                       multiplier=kw.get("multiplier", 1.0),
                       dropout_rate=kw.get("dropout_rate", 0.0),
                       dtype=_compute_dtype(kw))


@register_model("efficientnet")
def _efficientnet(output_dim, **kw):
    # reference main_fedavg.py "efficientnet" -> EfficientNet.from_name
    from fedml_tpu.models.efficientnet import EfficientNet

    return EfficientNet.from_name(kw.get("variant", "efficientnet-b0"),
                                  output_dim=output_dim,
                                  dtype=_compute_dtype(kw))
