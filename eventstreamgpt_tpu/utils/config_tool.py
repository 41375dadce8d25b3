"""A minimal structured-config system (Hydra-style, dependency-free).

The reference drives every entry point through Hydra structured configs
registered via its ``hydra_dataclass`` decorator
(``/root/reference/EventStream/utils.py:395-414``) plus YAML files with
``${...}`` interpolations. Hydra/omegaconf are not available in this
environment, so this module re-implements the slice of behavior the framework
needs, keeping YAML configs written for the reference working unchanged:

* ``config_dataclass`` — decorator registering a dataclass in a global store
  under its snake_case name (Hydra ``ConfigStore`` analog).
* ``load_config`` — build a registered config from an optional YAML file plus
  dotted-key command line overrides (``a.b.c=value``), with type coercion
  driven by dataclass annotations.
* ``${key}`` / ``${now:%fmt}`` interpolation on string fields.
"""

from __future__ import annotations

import dataclasses
import datetime
import enum
import re
import sys
import types
import typing
from pathlib import Path
from typing import Any, Callable, TypeVar, Union

import yaml

T = TypeVar("T")

CONFIG_STORE: dict[str, type] = {}


def _snake_case(name: str) -> str:
    return re.sub(r"(?<!^)(?=[A-Z])", "_", name).lower()


def config_dataclass(cls: type[T]) -> type[T]:
    """Registers ``cls`` (made a dataclass if not already) in the config store.

    The store key is the snake_case class name, mirroring the reference's
    ``hydra_dataclass`` registration contract so e.g. ``PretrainConfig``
    resolves as ``pretrain_config``.
    """
    if not dataclasses.is_dataclass(cls):
        cls = dataclasses.dataclass(cls)
    CONFIG_STORE[_snake_case(cls.__name__)] = cls
    return cls


def _strip_optional(tp: Any) -> Any:
    origin = typing.get_origin(tp)
    if origin in (typing.Union, types.UnionType):
        args = [a for a in typing.get_args(tp) if a is not type(None)]
        if len(args) == 1:
            return args[0]
    return tp


def _coerce(value: Any, tp: Any) -> Any:
    """Coerces a YAML/CLI value to the annotated type where unambiguous."""
    tp = _strip_optional(tp)
    if value is None:
        return None
    origin = typing.get_origin(tp)
    if origin in (typing.Union, types.UnionType):
        return value
    if tp is Any or tp is dataclasses.MISSING:
        return value
    if isinstance(tp, type):
        if issubclass(tp, enum.Enum):
            return tp(value) if not isinstance(value, tp) else value
        if dataclasses.is_dataclass(tp):
            if isinstance(value, tp):
                return value
            if isinstance(value, dict):
                return structure(value, tp)
            return value
        if tp is Path:
            return Path(value)
        if tp is bool and isinstance(value, str):
            return value.lower() in ("true", "1", "yes")
        if tp in (int, float, str) and not isinstance(value, (dict, list)):
            return tp(value)
    if origin in (list, tuple) and isinstance(value, (list, tuple)):
        args = typing.get_args(tp)
        if args:
            return list(_coerce(v, args[0]) for v in value)
        return list(value)
    if origin is dict and isinstance(value, dict):
        args = typing.get_args(tp)
        if len(args) == 2:
            return {k: _coerce(v, args[1]) for k, v in value.items()}
        return value
    return value


def structure(d: dict[str, Any], cls: type[T]) -> T:
    """Builds dataclass ``cls`` from a (possibly nested) plain dictionary."""
    fields = {f.name: f for f in dataclasses.fields(cls)}
    kwargs = {}
    for k, v in d.items():
        if k in fields:
            kwargs[k] = _coerce(v, fields[k].type if not isinstance(fields[k].type, str) else _resolve_annotation(cls, k))
        else:
            kwargs[k] = v
    return cls(**kwargs)


def _resolve_annotation(cls: type, field_name: str) -> Any:
    try:
        hints = typing.get_type_hints(cls)
        return hints.get(field_name, Any)
    except Exception:
        return Any


def unstructure(obj: Any) -> Any:
    """Inverse of `structure`: dataclass tree → plain dict/JSON primitives."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: unstructure(getattr(obj, f.name)) for f in dataclasses.fields(obj)}
    if isinstance(obj, enum.Enum):
        return obj.value
    if isinstance(obj, dict):
        return {k: unstructure(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [unstructure(v) for v in obj]
    if isinstance(obj, Path):
        return str(obj)
    return obj


_INTERP_RE = re.compile(r"\$\{([^}]+)\}")


def _interpolate_str(s: str, root: dict[str, Any]) -> Any:
    def lookup(expr: str) -> Any:
        if expr.startswith("now:"):
            return datetime.datetime.now().strftime(expr[4:])
        if expr.startswith("oc.env:"):
            import os

            spec = expr[len("oc.env:") :]
            var, _, default = spec.partition(",")
            val = os.environ.get(var)
            if val is not None:
                return val
            if _:
                return default
            raise KeyError(f"Environment variable '{var}' (from ${{{expr}}}) is not set")
        node: Any = root
        for part in expr.split("."):
            if isinstance(node, dict) and part in node:
                node = node[part]
            else:
                return None
        return node

    full = _INTERP_RE.fullmatch(s)
    if full:
        resolved = lookup(full.group(1))
        return s if resolved is None else resolved

    def sub_one(m: re.Match) -> str:
        resolved = lookup(m.group(1))
        return m.group(0) if resolved is None else str(resolved)

    return _INTERP_RE.sub(sub_one, s)


def resolve_interpolations(d: dict[str, Any], root: dict[str, Any] | None = None) -> dict[str, Any]:
    """Resolves ``${...}`` interpolations in all string values, in place-order.

    Repeats until fixpoint (bounded) so chained references resolve.
    """
    root = root if root is not None else d

    def _resolve(node: Any) -> Any:
        if isinstance(node, dict):
            return {k: _resolve(v) for k, v in node.items()}
        if isinstance(node, list):
            return [_resolve(v) for v in node]
        if isinstance(node, str) and "${" in node:
            return _interpolate_str(node, root)
        return node

    for _ in range(5):
        new = _resolve(d)
        if new == d:
            break
        d = new
        root = d
    return d


def set_dotted(d: dict[str, Any], key: str, value: Any) -> None:
    """Sets ``d["a"]["b"] = value`` for dotted key ``"a.b"``, creating levels."""
    parts = key.split(".")
    node = d
    for p in parts[:-1]:
        node = node.setdefault(p, {})
        if not isinstance(node, dict):
            raise ValueError(f"Cannot set {key}: {p} is not a mapping")
    node[parts[-1]] = value


def parse_override_value(raw: str) -> Any:
    """Parses a CLI override value using YAML rules (ints, floats, lists, null).
    YAML 1.1 reads ``1e-05`` (Python's own spelling of that float) as a string:
    a mantissa without a point is a float here too."""
    try:
        value = yaml.safe_load(raw)
    except yaml.YAMLError:
        return raw
    if isinstance(value, str) and re.fullmatch(r"[+-]?\d+[eE][+-]?\d+", value):
        return float(value)
    return value


def deep_merge(dst: dict, src: dict) -> dict:
    """Recursively merges ``src`` into ``dst`` in place (src wins); returns dst."""
    for k, v in src.items():
        if isinstance(v, dict) and isinstance(dst.get(k), dict):
            deep_merge(dst[k], v)
        else:
            dst[k] = v
    return dst


def configure_compile_cache() -> str:
    """Points JAX's persistent compilation cache at ONE fixed place.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set in code. Otherwise the cache lives at
    ``<checkout>/.jax_cache`` (derived from this package's location,
    git-ignored): the path is part of the cache key, so it is never a
    tempfile, pid, or timestamp. Called by the device-running entry points
    (``chip_smoke.py``, ``scripts/*.py`` ``__main__``), not
    at import. Returns the directory in effect.
    """
    import os

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    cache_dir = str(Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def split_config_arg(argv: list[str]) -> tuple[str | None, list[str]]:
    """Extracts a ``--config <yaml>`` pair from CLI args; returns (path, rest)."""
    argv = list(argv)
    yaml_fp = None
    if "--config" in argv:
        i = argv.index("--config")
        if i + 1 >= len(argv):
            raise ValueError("--config requires a YAML file path argument")
        yaml_fp = argv[i + 1]
        del argv[i : i + 2]
    return yaml_fp, argv


def parse_overrides(argv: list[str]) -> dict[str, Any]:
    """Parses ``key=value`` CLI args (Hydra syntax) into a nested dict.

    Hydra's bare ``~key`` deletion syntax sets the key to None; other
    ``=``-less tokens are rejected loudly rather than silently dropped.
    """
    out: dict[str, Any] = {}
    for arg in argv:
        if "=" not in arg:
            if arg.startswith("~"):
                set_dotted(out, arg[1:], None)
                continue
            raise ValueError(f"Override {arg!r} is not of the form key=value")
        key, _, raw = arg.partition("=")
        key = key.lstrip("+~")  # hydra's +key= / ~key syntax: treat as plain set
        set_dotted(out, key, parse_override_value(raw))
    return out


def load_config(
    config_cls: type[T] | str,
    yaml_file: Path | str | None = None,
    overrides: list[str] | dict[str, Any] | None = None,
    defaults: dict[str, Any] | None = None,
) -> T:
    """Builds a structured config: defaults ← YAML ← CLI overrides.

    Args:
        config_cls: The registered dataclass (or its store name).
        yaml_file: Optional YAML file of base values.
        overrides: Either pre-parsed nested dict or ``key=value`` strings.
        defaults: Optional extra base-layer values below the YAML file.
    """
    from .scopes import host_span  # brings JAX in: imported here, where a job reads its config, and not with the module

    with host_span("startup/config", id="startup"):
        return _load_config(config_cls, yaml_file, overrides, defaults)


def _load_config(config_cls, yaml_file, overrides, defaults):
    if isinstance(config_cls, str):
        config_cls = CONFIG_STORE[config_cls]

    # Seed with *declared* dataclass defaults so ${...} interpolations can
    # reference them even when neither YAML nor CLI set the referenced key.
    # Nested dataclasses seed from their declared field defaults rather than
    # an instantiated object: __post_init__-derived values (e.g.
    # OptimizationConfig.end_lr computed from init_lr) must not be baked in,
    # or overriding one of their inputs later would conflict (hydra's
    # ConfigStore has the same declared-defaults semantics).
    def declared_defaults(cls: type) -> dict[str, Any]:
        out: dict[str, Any] = {}
        for f in dataclasses.fields(cls):
            if f.default is not dataclasses.MISSING:
                v = f.default
            elif f.default_factory is not dataclasses.MISSING:  # type: ignore[misc]
                v = f.default_factory()
            else:
                continue
            if dataclasses.is_dataclass(v) and not isinstance(v, type):
                # A factory instance indistinguishable from the plain default
                # seeds from declared field defaults (so __post_init__-derived
                # values don't get baked in); a factory that customized any
                # field keeps its instance state verbatim — structure() will
                # re-run __post_init__ and re-derive consistently.
                try:
                    is_plain_default = unstructure(type(v)()) == unstructure(v)
                except TypeError:
                    is_plain_default = False
                out[f.name] = declared_defaults(type(v)) if is_plain_default else unstructure(v)
            else:
                out[f.name] = unstructure(v)
        return out

    merged: dict[str, Any] = declared_defaults(config_cls)

    def merge(dst: dict, src: dict) -> None:
        for k, v in src.items():
            if isinstance(v, dict) and isinstance(dst.get(k), dict):
                merge(dst[k], v)
            else:
                dst[k] = v

    if defaults:
        merge(merged, defaults)
    if yaml_file is not None:
        with open(yaml_file) as f:
            loaded = yaml.safe_load(f) or {}
        loaded.pop("defaults", None)  # hydra defaults-list: handled by caller
        loaded.pop("hydra", None)  # hydra runtime block: not config values
        merge(merged, loaded)
    if overrides:
        if isinstance(overrides, list):
            overrides = parse_overrides(overrides)
        merge(merged, overrides)

    merged = resolve_interpolations(merged)
    return structure(merged, config_cls)


def main_entry(config_cls: type[T], fn: Callable[[T], Any], yaml_file: Path | str | None = None) -> Any:
    """CLI driver: parse ``sys.argv[1:]`` as overrides and invoke ``fn(cfg)``."""
    cfg = load_config(config_cls, yaml_file=yaml_file, overrides=sys.argv[1:])
    return fn(cfg)
