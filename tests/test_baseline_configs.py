"""examples/baseline config matrix (VERDICT r4 missing #2).

Every reference examples/baseline/*.sh has a named YAML twin under
experiments/configs/baseline/. These tests keep the matrix honest: each
twin must exist, parse, and resolve to a loadable dataset + constructible
model; representatives of each new model/dataset family train a round.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CONFIG_DIR = os.path.join(
    os.path.dirname(__file__), "..", "fedml_tpu", "experiments", "configs", "baseline")

# the reference's script inventory, name-for-name
REFERENCE_BASELINES = [
    "adult_heter", "adult_homo", "chmnist_heter", "chmnist_homo",
    "cifar10_cnn", "cifar10_heter_res20", "cifar10_homo_res20",
    "cifar10_vgg11", "emnist", "femnist", "fmnist", "har_class_heter",
    "har_class_homo", "har_hetero", "har_homo", "mnist", "purchase_heter",
    "purchase_homo", "texas_heter", "texas_homo",
]


def _load(name):
    from fedml_tpu.experiments.fed_launch import _load_yaml

    return _load_yaml(os.path.join(CONFIG_DIR, f"{name}.yaml"))


def test_every_reference_baseline_has_a_twin():
    for name in REFERENCE_BASELINES:
        assert os.path.exists(os.path.join(CONFIG_DIR, f"{name}.yaml")), name


@pytest.mark.parametrize("name", REFERENCE_BASELINES)
def test_baseline_config_resolves(name):
    """Parse + resolve: dataset loads (surrogate), the program's own
    dispatch (`build_trainer`) constructs the model at the dataset's
    class_num, config round-trips through FedConfig."""
    import argparse

    from fedml_tpu.core.config import FedConfig
    from fedml_tpu.data.registry import load_dataset
    from fedml_tpu.experiments.common import build_trainer

    conf = _load(name)
    assert conf["algorithm"] == "fedavg"
    args = conf["args"]
    cfg = FedConfig.from_dict(args)
    assert cfg.comm_round >= 10
    load_kw = {}
    if args["dataset"] == "mnist":  # flatten by model, as setup_run does
        load_kw["flatten"] = args["model"] in ("lr", "mlp")
    ds = load_dataset(args["dataset"],
                      client_num_in_total=args["client_num_in_total"],
                      partition_method=args["partition_method"],
                      partition_alpha=args.get("partition_alpha", 0.5),
                      **load_kw)
    assert ds.client_num == args["client_num_in_total"]
    module = build_trainer(argparse.Namespace(**args), cfg, ds).module
    v = module.init({"params": jax.random.PRNGKey(0),
                     "dropout": jax.random.PRNGKey(1)},
                    jnp.asarray(ds.train.x[:1, 0]), train=False)
    assert jax.tree.leaves(v)


@pytest.mark.parametrize("name", [
    # har_hetero (~76s) and texas_heter (~53s) are the two heaviest tests
    # in tier-1 — nightly + the ci_smoke har_hetero step cover them;
    # purchase_homo keeps one end-to-end fed_launch round in the fast suite
    pytest.param("har_hetero", marks=pytest.mark.slow),
    "purchase_homo",
    pytest.param("texas_heter", marks=pytest.mark.slow),
])
def test_new_baseline_families_train_a_round(name):
    """The families this matrix introduced (har_subject partition,
    purchasemlp/texasmlp) run one fed_launch round end to end."""
    from fedml_tpu.experiments.fed_launch import main

    hist = main(["--config", os.path.join(CONFIG_DIR, f"{name}.yaml"),
                 "--override", "comm_round=1", "--override", "epochs=1"])
    assert np.isfinite(hist[-1]["Test/Loss"])


def test_har_subject_partition_groups_by_subject():
    """p-hetero over SUBJECT labels: with alpha=1 every client's windows
    come from (a slice of) one subject group — the reference subject
    loader's dense case (subject_dataloader.py:275-310)."""
    from fedml_tpu.data.registry import load_dataset

    ds = load_dataset("har_subject", client_num_in_total=21,
                      partition_method="p-hetero", partition_alpha=1.0, seed=3)
    assert ds.client_num == 21
    counts = np.asarray(ds.train.counts)
    assert counts.sum() > 0
