"""Experiment grid (reference C9): registry well-formed, runner launches."""

import numpy as np
import pytest

from experiments import EXPERIMENTS
from experiments.run import main
from gtopkssgd_tpu.models import get_model
from gtopkssgd_tpu.modes import ALL_MODES


def test_registry_covers_all_six_workloads():
    dnns = {spec["dnn"] for spec in EXPERIMENTS.values()}
    assert {"vgg16", "resnet20", "resnet50", "alexnet",
            "lstm", "lstman4"} <= dnns


def test_registry_entries_are_valid_configs():
    from gtopkssgd_tpu.trainer import TrainConfig

    for name, spec in EXPERIMENTS.items():
        clean = {k: v for k, v in spec.items() if not k.startswith("_")}
        cfg = TrainConfig(**clean).resolved()
        assert cfg.compression in ALL_MODES, name
        get_model(cfg.dnn)  # resolves or raises
        assert 0 < cfg.density <= 1.0, name
        assert spec["_desc"] and spec["_baseline"], name


def test_runner_list(capsys):
    assert main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "cifar10_resnet20_gtopk" in out
    # config #5 is a benchmark: perfbench's density cells, not an entry
    assert "sweep" not in out


def test_runner_launches_ci_scale():
    rc = main(["cifar10_resnet20_gtopk", "--nworkers", "2",
               "--batch-size", "4", "--num-iters", "2",
               "--eval-batches", "1", "--log-interval", "1"])
    assert rc == 0


def test_runner_rejects_unknown():
    with pytest.raises(SystemExit):
        main(["no_such_experiment"])
