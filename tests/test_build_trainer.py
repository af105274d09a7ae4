"""`experiments/common.py::build_trainer`: which model and which task trainer
an argv means for a dataset, decided in one place and from the dataset's
`class_num` and `meta` alone (no data is loaded here)."""

import argparse
from types import SimpleNamespace

import pytest

from fedml_tpu.experiments.common import (add_args, build_trainer,
                                          config_from_args)


@pytest.mark.parametrize("argv, meta, module, trainer, lora", [
    (["--dataset", "femnist", "--model", "cnn"], {},
     "CNN_DropOut", "ClassificationTrainer", False),
    (["--dataset", "cifar10", "--model", "cnn"], {},
     "CNNCifar", "ClassificationTrainer", False),
    (["--dataset", "har", "--model", "cnn"], {},
     "HAR_CNN", "ClassificationTrainer", False),
    (["--dataset", "fed_shakespeare", "--model", "rnn"], {},
     "RNN_OriginalFedAvg", "NWPTrainer", False),
    (["--dataset", "stackoverflow_lr", "--model", "lr"], {},
     "LogisticRegression", "TagPredictionTrainer", False),
    (["--dataset", "unnamed", "--model", "lr"], {"task": "nwp"},
     "LogisticRegression", "NWPTrainer", False),
    (["--dataset", "unnamed", "--model", "lr"], {"task": "tag_prediction"},
     "LogisticRegression", "TagPredictionTrainer", False),
    (["--dataset", "femnist", "--model", "cnn", "--lora_rank", "4"], {},
     "CNN_DropOut", "ClassificationTrainer", True),
])
def test_build_trainer_pairs_model_and_trainer(argv, meta, module, trainer,
                                               lora):
    args = add_args(argparse.ArgumentParser()).parse_args(argv)
    cfg = config_from_args(args)
    got = build_trainer(args, cfg, SimpleNamespace(class_num=7, meta=meta))
    assert type(got.module).__name__ == module
    if module != "RNN_OriginalFedAvg":        # sized by its vocabulary
        assert got.module.output_dim == 7
    assert (type(got).__name__ == "LoRATrainer") is lora
    task = got.inner if lora else got
    assert type(task).__name__ == trainer
    if lora:
        assert got.rank == 4
    if trainer == "NWPTrainer":
        assert task.pad_id == 0
    if args.dataset == "fed_shakespeare":
        assert got.module.vocab_size == 90 and got.module.per_position is True
