"""FedSeg tests: losses, metrics, LR schedules, end-to-end segmentation FL."""


import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.algorithms.fedseg import (
    confusion_matrix,
    evaluator_scores,
    make_lr_schedule,
    segmentation_ce,
    segmentation_focal,
    SegmentationTrainer,
)
from fedml_tpu.models.segmentation import SimpleFCN


def test_segmentation_ce_ignores_index():
    logits = jnp.zeros((1, 2, 2, 3))
    target = jnp.array([[[0, 255], [1, 2]]])
    per, m = segmentation_ce(logits, target)
    assert float(m.sum()) == 3.0  # the 255 pixel is masked out
    np.testing.assert_allclose(np.asarray(per[0, 0, 1]), 0.0, atol=1e-6)


def test_focal_loss_downweights_easy_pixels():
    easy = jnp.array([[[[10.0, 0.0, 0.0]]]])  # confident correct
    hard = jnp.array([[[[0.1, 0.0, 0.0]]]])
    target = jnp.zeros((1, 1, 1), jnp.int32)
    le, _ = segmentation_focal(easy, target)
    lh, _ = segmentation_focal(hard, target)
    ce_e, _ = segmentation_ce(easy, target)
    ce_h, _ = segmentation_ce(hard, target)
    # focal shrinks easy-pixel loss far more than hard-pixel loss
    assert float(le.sum()) / max(float(ce_e.sum()), 1e-9) < float(lh.sum()) / float(ce_h.sum())


def test_confusion_matrix_and_scores():
    pred = jnp.array([[0, 1], [1, 1]])
    target = jnp.array([[0, 1], [255, 0]])
    cm = confusion_matrix(pred, target, 2)
    np.testing.assert_array_equal(np.asarray(cm), [[1, 1], [0, 1]])
    s = evaluator_scores(cm)
    assert abs(s["Acc"] - 2 / 3) < 1e-6
    assert 0 <= s["mIoU"] <= 1
    assert 0 <= s["FWIoU"] <= 1


def test_perfect_prediction_scores_one():
    t = jnp.array([[0, 1, 2]])
    cm = confusion_matrix(t, t, 3)
    s = evaluator_scores(cm)
    assert abs(s["Acc"] - 1.0) < 1e-9
    assert abs(s["mIoU"] - 1.0) < 1e-9


@pytest.mark.parametrize("mode", ["cos", "poly", "step"])
def test_lr_schedules(mode):
    sched = make_lr_schedule(mode, 0.1, num_epochs=10, iters_per_epoch=5,
                             lr_step=3, warmup_epochs=1)
    lrs = [float(sched(t)) for t in range(50)]
    assert lrs[0] < lrs[5]  # warmup ramps
    assert lrs[-1] <= lrs[6] + 1e-9  # decays after warmup
    assert all(l >= 0 for l in lrs)


@pytest.mark.slow  # ~28s segmentation drive; ci_smoke's fedseg CLI step runs
# the same end-to-end path on every push
def test_fedseg_end_to_end():
    """Tiny FCN learns a synthetic segmentation task through FedAvgAPI with
    SegmentationTrainer (per-pixel labels + ignore_index)."""
    from fedml_tpu.algorithms.fedavg import FedAvgAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.packing import PackedClients
    from fedml_tpu.data.registry import FederatedDataset

    rng = np.random.RandomState(0)
    C, n, h, w = 4, 24, 16, 16
    # low-frequency task (so it survives the encoder's 4x downsampling):
    # a 4x4 sign field upsampled to 16x16; segment = sign > 0
    seed_field = rng.normal(size=(C, n, 4, 4)).astype(np.float32)
    field = np.kron(seed_field, np.ones((1, 1, 4, 4), np.float32))
    x = (field + 0.1 * rng.normal(size=(C, n, h, w)).astype(np.float32))[..., None]
    y = (field > 0).astype(np.int32)
    ignore = rng.rand(C, n, h, w) < 0.05
    y[ignore] = 255
    counts = np.full(C, n, np.int32)
    packed = PackedClients(x, y, counts)
    flat_x = x.reshape(-1, h, w, 1)
    flat_y = y.reshape(-1, h, w)
    ds = FederatedDataset(name="synthseg", train=packed, test=packed,
                          train_global=(flat_x, flat_y),
                          test_global=(flat_x[:32], flat_y[:32]), class_num=2)
    # lr scaled by the batch size: the trainer reproduces the reference's
    # batch_average loss scale (mean-CE / n), under which the old 0.1 is
    # effectively 0.1/8
    cfg = FedConfig(comm_round=8, batch_size=8, lr=0.8, epochs=5, momentum=0.9,
                    client_num_in_total=C, client_num_per_round=C, ci=1,
                    frequency_of_the_test=7)
    api = FedAvgAPI(ds, cfg, SegmentationTrainer(SimpleFCN(output_dim=2, width=8)))
    hist = api.train()
    assert hist[-1]["Test/Acc"] > 0.75  # pixel accuracy on the easy task
    assert hist[-1]["Test/Loss"] < hist[0]["Test/Loss"]


def test_fedseg_api_evaluate_metrics():
    """FedSegAPI.evaluate (the fused confusion-matrix eval path) runs and
    returns sane segmentation metrics — direct unit coverage for cm_batches,
    which a past refactor broke while only the CLI smoke exercised it."""
    from fedml_tpu.algorithms.fedseg import FedSegAPI, SegmentationTrainer
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.packing import PackedClients
    from fedml_tpu.data.registry import FederatedDataset

    rng = np.random.RandomState(2)
    C, n, h, w = 2, 8, 16, 16
    x = rng.rand(C, n, h, w, 1).astype(np.float32)
    y = rng.randint(0, 2, size=(C, n, h, w)).astype(np.int32)
    y[0, 0, :2, :2] = 255
    packed = PackedClients(x, y, np.full(C, n, np.int32))
    ds = FederatedDataset(name="synthseg", train=packed, test=packed,
                          train_global=(x.reshape(-1, h, w, 1), y.reshape(-1, h, w)),
                          test_global=(x.reshape(-1, h, w, 1)[:8], y.reshape(-1, h, w)[:8]),
                          class_num=2)
    cfg = FedConfig(comm_round=1, batch_size=4, lr=0.1, epochs=1,
                    client_num_in_total=C, client_num_per_round=C)
    api = FedSegAPI(ds, cfg, SegmentationTrainer(SimpleFCN(output_dim=2, width=4)))
    api.train_one_round(0)
    keeper = api.evaluate()  # reference-parity EvaluationMetricsKeeper
    for v in (keeper.accuracy, keeper.accuracy_class, keeper.mIoU,
              keeper.FWIoU, keeper.loss):
        assert np.isfinite(v), vars(keeper)
    assert 0.0 <= keeper.mIoU <= 1.0


def test_fedseg_checkpoint_resume_exact(tmp_path):
    """A FedSeg run interrupted mid-run resumes exactly (model + aggregator
    state + eval history) — previously FedSegAPI only SAVED checkpoints and
    restarted from round 0 on rerun."""
    import jax

    from fedml_tpu.algorithms.fedseg import FedSegAPI, SegmentationTrainer
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.packing import PackedClients
    from fedml_tpu.data.registry import FederatedDataset

    rng = np.random.RandomState(5)
    C, n, h, w = 2, 8, 16, 16
    x = rng.rand(C, n, h, w, 1).astype(np.float32)
    y = (x[..., 0] > 0.5).astype(np.int32)
    packed = PackedClients(x, y, np.full(C, n, np.int32))
    ds = FederatedDataset(name="synthseg", train=packed, test=packed,
                          train_global=(x.reshape(-1, h, w, 1), y.reshape(-1, h, w)),
                          test_global=(x.reshape(-1, h, w, 1)[:8], y.reshape(-1, h, w)[:8]),
                          class_num=2)
    cfg = FedConfig(comm_round=3, batch_size=4, lr=0.1, epochs=1,
                    client_num_in_total=C, client_num_per_round=C, seed=0)

    def fresh():
        return FedSegAPI(ds, cfg, SegmentationTrainer(SimpleFCN(output_dim=2, width=4)))

    straight = fresh()
    straight.train()

    ck = str(tmp_path / "ck")
    first = fresh()
    for r in range(2):
        m = first._inner.train_one_round(r)
        first.history.append({"round": r, **{k: float(v) for k, v in m.items()}})
    first._inner.history = first.history
    first._inner.save_checkpoint(ck, 2)

    resumed = fresh()
    resumed.train(ckpt_dir=ck)
    for a, b in zip(jax.tree.leaves(straight.global_variables),
                    jax.tree.leaves(resumed.global_variables)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert len(resumed.history) == 3


def test_fedseg_default_model_honors_config_dtype():
    """FedSegAPI's default DeepLab build must respect config.dtype (the r5
    silent-f32 lesson: an absent knob means f32)."""
    from fedml_tpu.algorithms.fedseg import FedSegAPI
    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.registry import load_dataset

    ds = load_dataset("pascal_voc", client_num_in_total=2, image_size=16)
    cfg = FedConfig(batch_size=2, epochs=1, lr=0.01, comm_round=1,
                    client_num_in_total=2, client_num_per_round=2,
                    dtype="bfloat16")
    api = FedSegAPI(ds, cfg)
    assert api.trainer.module.dtype == jnp.bfloat16
    cfg32 = cfg.replace(dtype="float32")
    assert FedSegAPI(ds, cfg32).trainer.module.dtype is None
