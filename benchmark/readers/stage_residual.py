"""What no span of the retrain names: ``stages["total"]`` (the workflow's root
span) minus the seconds of the spans ``args["spans"]`` lists, which must not
overlap one another (a parent OR its children, never both; of spans that ran
side by side on several threads the parent).  In the retrain the trace covers
(the last one where there is no trace).  A program without those spans (none
of the listed names in ``stages``) gives nothing to read."""


def read(evidence: dict, args: dict):
    stages = (evidence.get("retrain") or {}).get("stages")
    if not stages or "total" not in stages:
        return None
    named = [stages[name] for name in args["spans"] if name in stages]
    if not named:
        return None
    return stages["total"] - sum(named)
