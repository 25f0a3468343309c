"""Config override/round-trip tests (the `--set key=value` machinery)."""

import pytest

from repro.config_cli import OverrideError, apply_overrides, load, save
from repro.configs import get_config
from repro.fl.trainer import FLConfig
from repro.launch.train import TrainConfig
from repro.models.config import ModelConfig


def test_override_basic_types():
    cfg = apply_overrides(FLConfig(), ["lr=0.1", "rounds=7",
                                       "topology=ring", "t=3"])
    assert cfg.lr == 0.1 and cfg.rounds == 7
    assert cfg.topology == "ring" and cfg.t == 3


def test_override_bool_and_unknown():
    cfg = apply_overrides(TrainConfig(), ["reduced=false"])
    assert cfg.reduced is False
    with pytest.raises(OverrideError, match="unknown field"):
        apply_overrides(TrainConfig(), ["nope=1"])
    with pytest.raises(OverrideError, match="key=value"):
        apply_overrides(TrainConfig(), ["oops"])
    # in-scan metrics are FLConfig's; the LM trainer refuses them
    with pytest.raises(ValueError, match="metrics"):
        TrainConfig(metrics=object())


def test_override_model_config_literal():
    cfg = apply_overrides(get_config("yi-9b"),
                          ["num_layers=2", "mlp_act=gelu"])
    assert cfg.num_layers == 2 and cfg.mlp_act == "gelu"
    with pytest.raises(OverrideError):
        apply_overrides(get_config("yi-9b"), ["mlp_act=tanh"])


def test_json_round_trip(tmp_path):
    cfg = apply_overrides(get_config("granite-moe-1b-a400m"),
                          ["num_layers=3"])
    p = tmp_path / "cfg.json"
    save(cfg, p)
    back = load(ModelConfig, p)
    assert back == cfg


def test_fl_config_round_trip(tmp_path):
    cfg = FLConfig(topology="multigraph", t=8, lr=0.02)
    p = tmp_path / "fl.json"
    save(cfg, p)
    assert load(FLConfig, p) == cfg
