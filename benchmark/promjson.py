"""Reading the served process's ``/metrics.json`` (a dict of families, each
with ``series`` of ``{labels, value | sum + count}``)."""

from __future__ import annotations


def series_total(fams: dict, family: str, key: str, labels: dict | None = None) -> float:
    """Sum of ``key`` (``sum``, ``count`` or ``value``) over the family's
    series whose labels include ``labels``."""
    want = labels or {}
    return float(sum(
        s.get(key, 0)
        for s in fams.get(family, {}).get("series", [])
        if all(s.get("labels", {}).get(k) == v for k, v in want.items())
    ))


def series_max(fams: dict, family: str) -> float:
    return float(max(
        (s["value"] for s in fams.get(family, {}).get("series", [])), default=0
    ))


def compile_events(fams: dict) -> int:
    """Backend compilations (or cache retrievals) the process has made."""
    return int(sum(
        s["value"]
        for s in fams.get("pio_jax_compile_total", {}).get("series", [])
        if s["labels"].get("event", "").endswith("backend_compile_duration")
    ))
