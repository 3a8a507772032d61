"""Mean of a histogram family of the served process over the window:
(sum after - sum before) / (count after - count before), times
``args["scale"]`` (1000 for seconds -> ms).  Means only: the families' bucket
quantiles are too coarse to report."""


from benchmark.promjson import series_total


def read(evidence: dict, args: dict):
    before, after = evidence.get("metrics_before"), evidence.get("metrics_after")
    if not before or not after:
        return None

    def total(fams, key):
        return series_total(fams, args["family"], key, args.get("labels"))

    n = total(after, "count") - total(before, "count")
    if n <= 0:
        return None
    return (total(after, "sum") - total(before, "sum")) / n * args.get("scale", 1.0)
