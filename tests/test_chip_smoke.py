"""chip_smoke.py on the CPU, tiny: every phase function runs end to end (argv
assembly, `main_fedavg.main`, the history / TRACE.jsonl / compile checks)
on a few clients read from tiny files in the real on-disk formats, the
kernel phase runs in interpret mode because the TEST says so, and `main()`
refuses to report success without a TPU.
"""

import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _prototype_images(rng, n, shape, classes):
    """Separable samples (class prototype + noise), so a few SGD steps
    visibly lower the test loss."""
    protos = np.random.RandomState(99).normal(size=(classes,) + shape)
    y = rng.randint(0, classes, n)
    x = protos[y] * 0.6 + rng.normal(0, 0.35, (n,) + shape)
    return x, y


@pytest.fixture
def femnist_dir(tmp_path):
    """20 writers x 16 train / 4 test samples in the TFF h5 layout."""
    h5py = pytest.importorskip("h5py")
    rng = np.random.RandomState(0)
    d = tmp_path / "femnist"
    d.mkdir()
    for name, n in (("fed_emnist_train.h5", 16), ("fed_emnist_test.h5", 4)):
        with h5py.File(d / name, "w") as f:
            ex = f.create_group("examples")
            for w in range(20):
                x, y = _prototype_images(rng, n, (28, 28), 4)
                g = ex.create_group(f"f{w:04d}")
                g.create_dataset("pixels", data=x.astype(np.float32))
                g.create_dataset("label", data=y.astype(np.int64))
    return str(d)


@pytest.fixture
def cifar_dir(tmp_path):
    """32 train / 16 test images in the cifar-10-batches-py pickles: two
    homo silos of 16 samples."""
    rng = np.random.RandomState(1)
    base = tmp_path / "cifar" / "cifar-10-batches-py"
    base.mkdir(parents=True)

    def write(path, n):
        x, y = _prototype_images(rng, n, (3072,), 4)
        data = np.clip(128 + 48 * x, 0, 255).astype(np.uint8)
        with open(path, "wb") as f:
            pickle.dump({b"data": data, b"labels": y.tolist()}, f)

    for i, n in zip(range(1, 6), (7, 7, 6, 6, 6)):
        write(base / f"data_batch_{i}", n)
    write(base / "test_batch", 16)
    return str(tmp_path / "cifar")


def test_flagship_phase_tiny(tmp_path, femnist_dir):
    out = chip_smoke.flagship_phase(
        str(tmp_path / "run"), rounds=4, test_every=3, min_test_acc=None,
        extra_argv=("--data_dir", femnist_dir, "--client_num_in_total", "20",
                    "--client_num_per_round", "4"))
    assert out["ok"] and out["phase"] == "flagship" and out["rounds"] == 4
    assert out["last"]["Test/Loss"] < out["first"]["Test/Loss"]
    assert out["compiles"] > 0
    timing = out["smoke_timing"]
    assert len(timing["later_rounds_s"]) == 3
    assert 0 < timing["data_build_s"] <= timing["setup_s"] < timing["total_s"]
    json.dumps(out)  # the phase line must serialize


def test_cross_silo_phase_tiny(tmp_path, cifar_dir):
    out = chip_smoke.cross_silo_phase(
        str(tmp_path / "run"), rounds=2, test_every=1,
        # resnet20: the same ResNetCifar family at a third of the depth —
        # ResNet-56 costs this test a minute of CPU compile, and its round
        # is compiled at full size in tests/test_chip_compile.py
        extra_argv=("--data_dir", cifar_dir, "--client_num_in_total", "2",
                    "--client_num_per_round", "2", "--batch_size", "8",
                    "--model", "resnet20"))
    assert out["ok"] and out["phase"] == "cross_silo" and out["rounds"] == 2
    assert np.isfinite(out["last"]["Train/Loss"])


def test_multichip_flagship_phase_tiny(tmp_path, femnist_dir):
    """--multichip (a) on conftest's 8 virtual devices: a cohort of 4 padded
    to 8 rows, one per device, and every round equal to the vmap engine."""
    out = chip_smoke.multichip_flagship_phase(
        str(tmp_path / "run"), rounds=2,
        # lr: what is under test is the mesh, the staging and the
        # comparison; the CNN already ran in test_flagship_phase_tiny
        extra_argv=("--data_dir", femnist_dir, "--client_num_in_total", "20",
                    "--client_num_per_round", "4", "--model", "lr"))
    assert out["ok"] and out["mesh"] == {"clients": 8}
    assert [(r["cohort_rows"], r["cohort_devices"])
            for r in out["sharded_vs_one_chip"]] == [(8, 8), (8, 8)]


def test_multichip_tensor_phase_tiny():
    out = chip_smoke.multichip_tensor_phase(
        tensor_shards=2, clients=4, samples=4, seq=16, batch_size=4)
    assert out["ok"] and out["mesh"] == {"clients": 4, "tensor": 2}
    assert out["tensor_sharded_leaves"] > 0
    for precision in ("default", "highest"):
        assert out[precision]["param_max_abs_diff"] < chip_smoke.PARAM_TOL


def test_same_round_rejects_a_divergent_round():
    good = ({"w": np.ones(3, np.float32)}, {"total": 5.0, "loss_sum": 1.0})
    off = ({"w": np.ones(3, np.float32) + 1e-4}, good[1])
    short = (good[0], {"total": 4.0, "loss_sum": 1.0})
    assert chip_smoke.same_round("t", good, good)["param_max_abs_diff"] == 0
    for bad in (off, short):
        with pytest.raises(AssertionError, match="one-chip vmap round"):
            chip_smoke.same_round("t", bad, good)
    # not held: parameters may differ, sample counts still may not
    assert chip_smoke.same_round("t", off, good, hold=False)
    with pytest.raises(AssertionError, match="one-chip vmap round"):
        chip_smoke.same_round("t", short, good, hold=False)


def test_kernel_phase_interpreted_by_the_test():
    out = chip_smoke.kernel_phase(shape=(1, 256, 2, 64), interpret=True)
    assert out["ok"] and out["interpret"] is True
    # interpret mode lowers to plain HLO: the custom-call assertion is the
    # chip's, and kernel_phase() holds it whenever interpret is False
    assert out["tpu_custom_calls"] == {"fwd": 0, "grad": 0}
    assert out["fwd_max_abs_err"] < 2e-4 and out["grad_max_abs_err"] < 2e-3


def test_compiles_after_round_sees_a_late_compile():
    """The no-compile-after-round check must be able to fail."""
    span = {"t0": 10.0, "dur_s": 2.0}
    ends = lambda *ts: [{"kind": "compile", "t": t, "dur_s": 0.5} for t in ts]
    assert chip_smoke.compiles_after_round(ends(9.0, 12.0), span) == []
    assert chip_smoke.compiles_after_round(ends(11.0, 12.5, 20.0),
                                           span) == [0.5, 8.0]


def test_main_fails_off_chip_without_reporting_ok(capsys):
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--multichip"]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_script_alone_fails_without_the_program(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the repo
    the script exits non-zero and prints no result."""
    (tmp_path / "chip_smoke.py").write_text(
        open(os.path.join(REPO, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_kda_phase_interpreted_by_the_test():
    """The KDA phase as the chip runs it, at a smaller shape and with the
    kernels interpreted: the same comparison, the same tolerances."""
    out = chip_smoke.kda_phase(shape=(1, 256, 2, 32), interpret=True)
    assert out["ok"] and out["phase"] == "kda" and out["interpret"]
    assert out["fwd_max_abs_err"] < 2e-5 and out["grad_max_abs_err"] < 1e-4


def test_moe_share_phase_interpreted_on_the_cpu():
    """The share-dispatch phase as the chip runs it, smaller (off the chip
    the grouped product is interpreted): the router's own routing fits the
    bounded buffer, every pair on held experts takes the worst-case path,
    and both equal the dense computation."""
    out = chip_smoke.moe_share_phase(tokens=128, d=32, f=16, held=2,
                                     routed=8, k=2, tile=8)
    assert out["ok"] and out["phase"] == "moe_share"
    assert out["rows_bounded"] == 144 < out["rows_worst"] == 272
    assert {n: p["took"] for n, p in out["paths"].items()} == {
        "bounded": "bounded", "fallback": "fallback"}
    assert out["paths"]["bounded"]["held_rows"] <= 144
    assert out["paths"]["fallback"]["held_rows"] == 256
    for path in out["paths"].values():
        assert max(path["max_abs_err"].values()) < 3e-2
