"""Seconds of the workflow's stages (``DASE stage breakdown``, host clock)
whose names start with one of ``args["prefixes"]``, summed, in the retrain the
trace covers (the last one where there is no trace)."""


def read(evidence: dict, args: dict):
    stages = (evidence.get("retrain") or {}).get("stages")
    if not stages:
        return None
    picked = [
        secs for name, secs in stages.items()
        if any(name.startswith(p) for p in args["prefixes"])
    ]
    return sum(picked) if picked else None
