import pytest

from lightgbm_tpu.config import Config, key_alias_transform, parse_line_params


def test_defaults_match_reference():
    c = Config()
    # reference config.h:91-262
    assert c.max_bin == 256
    assert c.num_leaves == 127
    assert c.learning_rate == 0.1
    assert c.min_data_in_leaf == 100
    assert c.min_sum_hessian_in_leaf == 10.0
    assert c.top_k == 20
    assert c.num_iterations == 10
    assert c.bagging_freq == 0
    assert c.tree_learner == "serial"


def test_alias_transform():
    p = key_alias_transform({"num_tree": "50", "lr": 1, "sub_row": "0.5"})
    assert p["num_iterations"] == "50"
    assert p["bagging_fraction"] == "0.5"
    # canonical key wins over alias
    p = key_alias_transform({"num_iterations": "10", "num_tree": "99"})
    assert p["num_iterations"] == "10"


def test_from_dict_types():
    c = Config.from_dict(
        {
            "num_trees": "25",
            "shrinkage_rate": "0.2",
            "is_training_metric": "true",
            "metric": "binary_logloss,auc",
            "ndcg_at": "1,3,5",
            "application": "binary",
        }
    )
    assert c.num_iterations == 25
    assert c.learning_rate == 0.2
    assert c.is_training_metric is True
    assert c.metric == ["binary_logloss", "auc"]
    assert c.ndcg_eval_at == [1, 3, 5]
    assert c.objective == "binary"


def test_parse_line_params():
    p = parse_line_params(["task=train", "# comment", "data = foo.txt # trailing"])
    assert p == {"task": "train", "data": "foo.txt"}


def test_reference_example_conf_parses(reference_examples):
    from lightgbm_tpu.config import parse_config_file

    p = parse_config_file(
        f"{reference_examples}/binary_classification/train.conf"
    )
    c = Config.from_dict(p)
    assert c.objective == "binary"
    assert c.task == "train"
    assert c.num_leaves > 0


def test_invalid_config_rejected():
    with pytest.raises(ValueError):
        Config.from_dict({"tree_learner": "bogus"})
    with pytest.raises(ValueError):
        Config.from_dict({"boosting_type": "bogus"})


def test_unknown_param_warns(capsys):
    """A typo'd key must warn, not silently train with the default
    (reference src/io/config.cpp unknown-param warning)."""
    c = Config.from_dict({"num_leavs": "255", "objective": "binary"})
    err = capsys.readouterr().err
    assert "Unknown parameter: num_leavs" in err
    assert c.num_leaves == 127  # default untouched


@pytest.mark.parametrize("key,value", [
    ("tree_growth", "depthwise"),
    ("tree_growth", "hybrid"),
    ("grid_feature_shards", "4"),
    ("sparse_hist_density", "0.5"),
])
def test_growth_settings_are_unknown_and_trees_leafwise(key, value, capsys,
                                                        monkeypatch):
    """Trees are leaf-wise only, as the reference grows them: a params
    dict that still names a growth or grid setting warns by name, once,
    and grows the trees the same params grow without it."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu import config as config_mod

    monkeypatch.setattr(config_mod, "_warned_unknown_params", set())
    rng = np.random.RandomState(3)
    X = rng.randn(400, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
              "verbose": -1}

    def model(p):
        return lgb.train(p, lgb.Dataset(X, label=y),
                         num_boost_round=3).model_to_string()

    want = model(params)
    capsys.readouterr()
    got = model({**params, key: value})
    assert capsys.readouterr().err.count(f"Unknown parameter: {key}") == 1
    assert got == want


@pytest.mark.parametrize("bad", [
    {"num_leaves": 1},
    {"feature_fraction": 0.0},
    {"feature_fraction": 1.5},
    {"bagging_fraction": 2.5},
    {"learning_rate": 0.0},
    {"lambda_l1": -1.0},
    {"num_iterations": -3},
    {"min_data_in_leaf": 0, "min_sum_hessian_in_leaf": 0.5},
    {"metric_freq": -1},
    {"drop_rate": 2.0},
    {"skip_drop": -0.1},
    {"tree_learner": "grid"},  # the reference's four learners only
])
def test_value_range_checks(bad):
    """Reference CHECK()s (config.cpp:270-317) are enforced."""
    with pytest.raises(ValueError):
        Config.from_dict(bad)


def test_value_range_valid_edges():
    Config.from_dict({"max_depth": -1, "num_leaves": 2})
    # the reference has NO max_depth CHECK (config.cpp:270-317);
    # <= 0 means unlimited (config.h:182) and any positive value is
    # accepted, so direct construction must accept these too
    Config(max_depth=0)
    Config(max_depth=1)
    # CHECKs fire on the constructor path as well, not only from_dict
    with pytest.raises(ValueError):
        Config(num_leaves=1)
