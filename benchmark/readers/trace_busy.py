"""Device busy time of the traced span (union of the device's op intervals,
``trace_reduce.py``): in seconds, or with ``args["per_count_of"]`` in
milliseconds per observation of that histogram family inside the span (busy
time a wave, with the wave-size histogram)."""


from benchmark.promjson import series_total


def read(evidence: dict, args: dict):
    trace = evidence.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    family = args.get("per_count_of")
    if not family:
        return trace["busy_s"]
    before = evidence.get("trace_metrics_before")
    after = evidence.get("trace_metrics_after")
    if not before or not after:
        return None

    n = series_total(after, family, "count") - series_total(before, family, "count")
    return trace["busy_s"] * 1e3 / n if n > 0 else None
