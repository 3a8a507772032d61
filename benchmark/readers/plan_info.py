"""One number of ``ops.als.LAST_PLAN_INFO`` as the worker read it after the
retrain (``args["key"]``; ``stage_s`` is the host staging before the device
loop).  A retrain that staged nothing has no such key."""


def read(evidence: dict, args: dict):
    info = (evidence.get("retrain") or {}).get("plan_info") or {}
    return info.get(args["key"])
