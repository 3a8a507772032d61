"""One number of ``ops.als.LAST_PLAN_INFO`` (``readers/plan_info.py``) times
``args["scale"]``: a count in bytes under a unit of GB."""

from benchmark.readers import plan_info


def read(evidence: dict, args: dict):
    value = plan_info.read(evidence, args)
    return None if value is None else value * args["scale"]
