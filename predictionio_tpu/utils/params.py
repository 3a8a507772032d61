"""Engine-variant JSON -> typed params extraction (the JsonExtractor role).

The reference extracts per-component params from engine.json into typed Params
case classes via json4s/Gson (workflow/JsonExtractor.scala:39,
WorkflowUtils.extractParams:89).  Here params are plain dataclasses and one
codec suffices: dict -> dataclass with nested coercion, unknown-field
detection, and round-trip back to JSON for the engine-instance registry.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import typing
from typing import Any, Mapping, Type, TypeVar

T = TypeVar("T")


class ParamsError(ValueError):
    """Bad engine params JSON."""


class Params:
    """Marker base class for component parameters (controller/Params.scala:26)."""


@dataclasses.dataclass(frozen=True)
class EmptyParams(Params):
    pass


def extract_params(cls: Type[T], payload: Mapping[str, Any] | None) -> T:
    """Build a params dataclass from a JSON object, coercing nested fields.

    Every field also answers to its camelCase, the reference's JSON spelling
    (``numIterations`` -> ``num_iterations``); a ``params_aliases`` classvar
    (dict json-name -> field-name) names the spellings that are not that rule
    (``lambda`` -> ``reg``, which cannot be a Python field name).
    """
    payload = dict(payload or {})
    if not dataclasses.is_dataclass(cls):
        raise ParamsError(f"{cls!r} is not a dataclass params type")
    aliases, hints, fields, names = _class_info(cls)
    for json_name, field_name in aliases.items():
        if json_name in payload:
            payload[field_name] = payload.pop(json_name)
    unknown = set(payload) - names
    if unknown:
        raise ParamsError(
            f"unknown fields {sorted(unknown)} for {cls.__name__}; "
            f"expected a subset of {sorted(names)}"
        )
    kwargs: dict[str, Any] = {}
    for f in fields:
        if f.name in payload:
            kwargs[f.name] = _coerce(payload[f.name], hints.get(f.name), f.name)
        elif (
            f.default is dataclasses.MISSING
            and f.default_factory is dataclasses.MISSING
        ):
            raise ParamsError(f"missing required param {f.name!r} for {cls.__name__}")
    return cls(**kwargs)  # type: ignore[return-value]


@functools.lru_cache(maxsize=None)
def _class_info(cls):
    """Per-class introspection cache (type-hint resolution is ~40us; the
    serving hot path extracts a Query per request)."""
    fields = dataclasses.fields(cls)
    return (
        {_camel(f.name): f.name for f in fields if "_" in f.name}
        | dict(getattr(cls, "params_aliases", {})),
        typing.get_type_hints(cls),
        fields,
        frozenset(f.name for f in fields),
    )


def _camel(name: str) -> str:
    """``num_iterations`` -> ``numIterations``."""
    head, *rest = name.split("_")
    return head + "".join(part.capitalize() for part in rest)


def _coerce(value: Any, typ: Any, name: str) -> Any:
    if typ is None or typ is Any:
        return value
    origin = typing.get_origin(typ)
    if origin is typing.Union:
        args = [a for a in typing.get_args(typ) if a is not type(None)]
        if value is None:
            return None
        if len(args) == 1:
            return _coerce(value, args[0], name)
        return value
    if origin in (list, tuple, set):
        args = typing.get_args(typ)
        elem = args[0] if args else Any
        if not isinstance(value, (list, tuple)):
            raise ParamsError(f"param {name!r}: expected list, got {value!r}")
        seq = [_coerce(v, elem, name) for v in value]
        return origin(seq) if origin is not list else seq
    if origin is dict:
        args = typing.get_args(typ)
        elem = args[1] if len(args) == 2 else Any
        return {k: _coerce(v, elem, name) for k, v in dict(value).items()}
    if dataclasses.is_dataclass(typ):
        if not isinstance(value, Mapping):
            raise ParamsError(f"param {name!r}: expected object for {typ.__name__}")
        return extract_params(typ, value)
    if typ is float:
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            raise ParamsError(f"param {name!r}: expected number, got {value!r}")
        return float(value)
    if typ is int:
        if isinstance(value, bool) or not isinstance(value, int):
            raise ParamsError(f"param {name!r}: expected int, got {value!r}")
        return value
    if typ is bool:
        if not isinstance(value, bool):
            raise ParamsError(f"param {name!r}: expected bool, got {value!r}")
        return value
    if typ is str:
        if not isinstance(value, str):
            raise ParamsError(f"param {name!r}: expected str, got {value!r}")
        return value
    return value


def params_to_dict(params: Any) -> dict[str, Any]:
    if params is None:
        return {}
    if dataclasses.is_dataclass(params):
        return dataclasses.asdict(params)
    if isinstance(params, Mapping):
        return dict(params)
    raise ParamsError(f"cannot serialize params {params!r}")


def params_to_json(params: Any) -> str:
    return json.dumps(params_to_dict(params), sort_keys=True)
