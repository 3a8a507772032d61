"""Device self time, in seconds, of the traced span's operations whose names
start with ``args["prefix"]`` (``trace_reduce.py``: ``ops_by_name``, every
operation by self time).  A Pallas kernel's instruction carries the kernel's
``name=``, one instruction a call site; a program without such an operation
gives nothing to read."""


def matching(evidence: dict, prefix: str) -> list:
    """[[name, seconds], ...] of the operations with that prefix."""
    trace = evidence.get("trace") or {}
    return [op for op in trace.get("ops_by_name", []) if op[0].startswith(prefix)]


def read(evidence: dict, args: dict):
    ops = matching(evidence, args["prefix"])
    return sum(seconds for _, seconds in ops) if ops else None
