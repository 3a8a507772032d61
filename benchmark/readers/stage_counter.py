"""What the retrain's spans COUNTED (the ``counters`` key of the workflow's
``stages`` extra: a dict of numbers beside the stage seconds), in the retrain
the trace covers (the last one where there is no trace).

``args["key"]``: that counter.  Or ``args["peak"]``, ``args["sum"]`` and
``args["parts"]``: over every suffix the counters carry both names with (a step
and layer: ``.step0.layer2``), the largest of ``peak / (sum / parts)``: the
busiest part's count over the mean of the parts, ``counters[parts]`` of them
(the busiest held expert's pairs over the mean of the held experts').  A
program that counts nothing (the parent's ``stages`` has no ``counters``)
gives nothing to read."""


def read(evidence: dict, args: dict):
    stages = (evidence.get("retrain") or {}).get("stages") or {}
    counters = stages.get("counters")
    if not counters:
        return None
    if "key" in args:
        return counters.get(args["key"])
    peak, total, parts = args["peak"], args["sum"], counters.get(args["parts"])
    ratios = [
        count * parts / counters[total + name[len(peak):]]
        for name, count in counters.items()
        if name.startswith(peak + ".") and counters.get(total + name[len(peak):])
    ]
    return max(ratios) if ratios and parts else None
