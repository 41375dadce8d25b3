"""Tests for eventstreamgpt_tpu.utils (enums, serialization, config tool)."""

import dataclasses
import enum
import json
from pathlib import Path

import jax
import pytest

from eventstreamgpt_tpu.utils import (
    CONFIG_STORE,
    JSONableMixin,
    StrEnum,
    config_dataclass,
    count_or_proportion,
    load_config,
    lt_count_or_proportion,
    parse_overrides,
    resolve_interpolations,
    to_dict_flat,
    unstructure,
)


class Color(StrEnum):
    RED = enum.auto()
    DARK_BLUE = enum.auto()


def test_str_enum():
    assert Color.RED.value == "red"
    assert str(Color.DARK_BLUE) == "dark_blue"
    assert Color("red") is Color.RED
    assert Color.values() == ["red", "dark_blue"]
    assert json.dumps(Color.RED) == '"red"'


def test_count_or_proportion():
    assert count_or_proportion(100, 0.1) == 10
    assert count_or_proportion(None, 11) == 11
    assert count_or_proportion(100, 0.116) == 12
    with pytest.raises(ValueError):
        count_or_proportion(None, 0)
    with pytest.raises(ValueError):
        count_or_proportion(None, 1.3)
    with pytest.raises(TypeError):
        count_or_proportion(None, "a")


def test_lt_count_or_proportion():
    assert not lt_count_or_proportion(10, 0.1, 100)
    assert lt_count_or_proportion(10, 0.11, 100)
    assert lt_count_or_proportion(10, 11)
    assert not lt_count_or_proportion(10, 9)
    assert not lt_count_or_proportion(10, None)


@dataclasses.dataclass
class _Inner(JSONableMixin):
    x: int = 1
    color: Color = Color.RED


@dataclasses.dataclass
class _Outer(JSONableMixin):
    name: str = "hi"
    inner: _Inner = dataclasses.field(default_factory=_Inner)


def test_jsonable_roundtrip(tmp_path: Path):
    obj = _Outer(name="yo", inner=_Inner(x=5, color=Color.DARK_BLUE))
    d = obj.to_dict()
    assert d == {"name": "yo", "inner": {"x": 5, "color": "dark_blue"}}
    fp = tmp_path / "o.json"
    obj.to_json_file(fp)
    loaded = json.loads(fp.read_text())
    assert loaded == d
    with pytest.raises(FileExistsError):
        obj.to_json_file(fp)


@config_dataclass
class MySweepConfig:
    lr: float = 1e-3
    steps: int = 100
    name: str = "run"
    nested: dict = dataclasses.field(default_factory=dict)


def test_config_store_registration():
    assert "my_sweep_config" in CONFIG_STORE
    assert CONFIG_STORE["my_sweep_config"] is MySweepConfig


def test_parse_overrides():
    out = parse_overrides(["a.b=3", "c=hello", "d=[1,2]", "e=null", "f=0.5"])
    assert out == {"a": {"b": 3}, "c": "hello", "d": [1, 2], "e": None, "f": 0.5}


def test_load_config_with_yaml_and_overrides(tmp_path: Path):
    yaml_fp = tmp_path / "cfg.yaml"
    yaml_fp.write_text("lr: 0.01\nname: from_yaml\nnested:\n  k: v\n")
    cfg = load_config(MySweepConfig, yaml_file=yaml_fp, overrides=["steps=7", "lr=0.1"])
    assert cfg.lr == 0.1
    assert cfg.steps == 7
    assert cfg.name == "from_yaml"
    assert cfg.nested == {"k": "v"}


def test_timeable_timing_summary():
    from eventstreamgpt_tpu.utils import TimeableMixin

    class T(TimeableMixin):
        @TimeableMixin.TimeAs
        def work(self):
            return 1

    t = T()
    assert t.timing_summary() == "(no timed phases)"
    t.work()
    t.work()
    out = t.timing_summary()
    assert "work" in out and "calls" in out
    assert t._duration_stats()["work"][1] == 2


def test_load_config_declared_defaults_vs_factory_kwargs():
    """Two regressions around nested-dataclass default seeding:

    1. A plain default factory (OptimizationConfig()) must seed from declared
       field defaults so __post_init__-derived values (end_lr) don't conflict
       with overrides of their inputs (init_lr).
    2. A customizing factory (MetricsConfig(do_skip_all_metrics=True)) must
       keep its baked-in kwargs.
    """
    from eventstreamgpt_tpu.training import PretrainConfig

    cfg = load_config(PretrainConfig, overrides=["optimization_config.init_lr=1e-3"])
    assert cfg.optimization_config.init_lr == 1e-3
    # end_lr re-derived from end_lr_frac_of_init_lr, not stale from defaults.
    assert cfg.optimization_config.end_lr == pytest.approx(1e-6)
    # The customized metrics factory default survives.
    assert cfg.pretraining_metrics_config.do_skip_all_metrics is True
    assert cfg.final_validation_metrics_config.do_skip_all_metrics is False


def test_interpolation():
    d = {"base": "/tmp/x", "sub": "${base}/y", "deep": {"z": "${sub}/z"}}
    out = resolve_interpolations(d)
    assert out["sub"] == "/tmp/x/y"
    assert out["deep"]["z"] == "/tmp/x/y/z"


def test_now_interpolation():
    out = resolve_interpolations({"d": "${now:%Y}"})
    assert len(out["d"]) == 4 and out["d"].isdigit()


def test_unstructure_and_flat():
    obj = _Outer()
    assert unstructure(obj) == {"name": "hi", "inner": {"x": 1, "color": "red"}}
    assert to_dict_flat({"a": {"b": 1}, "c": 2}) == {"a.b": 1, "c": 2}


def test_compile_cache_is_one_fixed_place(monkeypatch):
    """`JAX_COMPILATION_CACHE_DIR` wins untouched; otherwise one fixed path
    inside the checkout, never a tempfile/pid/time."""
    from eventstreamgpt_tpu.utils.config_tool import configure_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert configure_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == was  # nothing set in code
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        expected = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert configure_compile_cache() == configure_compile_cache() == expected
        assert jax.config.jax_compilation_cache_dir == expected
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
