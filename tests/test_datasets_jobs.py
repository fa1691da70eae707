"""Datasets, job deployment, and example-workflow smoke tests."""

import sys

import numpy as np

from distkeras_tpu.datasets import cifar10, imdb, mnist, synthetic_lm
from distkeras_tpu.job_deployment import Job, Punchcard


def test_mnist_shapes():
    df = mnist(n=256)
    assert df["features"].shape == (256, 28, 28, 1)
    assert df["features"].min() >= 0 and df["features"].max() <= 1
    assert set(np.unique(df["label"])) <= set(range(10))
    assert df.synthetic is True
    flat = mnist(n=64, flat=True)
    assert flat["features"].shape == (64, 784)


def test_cifar10_shapes():
    df = cifar10(n=128)
    assert df["features"].shape == (128, 32, 32, 3)


def test_imdb_shapes_and_signal():
    df = imdb(n=512, vocab_size=500, seq_len=40)
    assert df["features"].shape == (512, 40)
    assert df["features"].max() < 500
    # sentiment token ranges must differ by class (learnable signal)
    pos = df["features"][df["label"] == 1]
    neg = df["features"][df["label"] == 0]
    pos_frac = ((pos >= 10) & (pos < 60)).mean()
    neg_frac = ((neg >= 10) & (neg < 60)).mean()
    assert pos_frac > neg_frac + 0.1


def test_synthetic_lm_is_predictable():
    df = synthetic_lm(n=64, vocab_size=32, seq_len=16)
    assert df["features"].shape == (64, 15)
    assert df["label"].shape == (64, 15)
    np.testing.assert_array_equal(df["features"][:, 1:], df["label"][:, :-1])


def test_dataset_determinism():
    a, b = mnist(n=32), mnist(n=32)
    np.testing.assert_array_equal(a["features"], b["features"])


def test_punchcard_roundtrip_and_job_render():
    pc = Punchcard(job_name="train", script="train.py",
                   hosts=["10.0.0.1", "10.0.0.2"], env={"FOO": "bar"},
                   args=["--epochs", "3"], coordinator_port=8476)
    pc2 = Punchcard.from_json(pc.to_json())
    assert pc2.hosts == ["10.0.0.1", "10.0.0.2"]

    cmds = Job(pc).launch(dry_run=True)
    assert len(cmds) == 2
    assert "JAX_COORDINATOR_ADDRESS=10.0.0.1:8476" in cmds[0]
    assert "JAX_PROCESS_ID=0" in cmds[0] and "JAX_PROCESS_ID=1" in cmds[1]
    assert "JAX_NUM_PROCESSES=2" in cmds[1]
    assert "FOO=bar" in cmds[0] and "--epochs 3" in cmds[0]


def _run_example(monkeypatch, module_name, argv):
    import importlib

    monkeypatch.setattr(sys, "argv", argv)
    sys.path.insert(0, "examples")
    try:
        mod = importlib.import_module(module_name)
        mod.main()
    finally:
        sys.path.remove("examples")


def test_mnist_workflow_example(monkeypatch, capsys):
    _run_example(monkeypatch, "mnist_workflow",
                 ["x", "--trainer", "adag", "--workers", "4", "--epochs", "1",
                  "--rows", "1024", "--batch-size", "16", "--window", "4"])
    out = capsys.readouterr().out
    assert "test accuracy" in out


def test_transformer_spmd_example(monkeypatch, capsys):
    _run_example(monkeypatch, "transformer_spmd",
                 ["x", "--steps", "4", "--layers", "1", "--d-model", "32",
                  "--seq-len", "16", "--vocab", "64", "--batch-per-dp", "2"])
    out = capsys.readouterr().out
    assert "loss" in out
