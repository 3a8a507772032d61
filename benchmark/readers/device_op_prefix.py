"""Device self time, in seconds, of the traced span's operations whose names
start with ``args["prefix"]`` — among the longest operations the reducer keeps
(``trace_reduce.py``: ten, by self time).  A Pallas kernel's instruction
carries the kernel's ``name=``, one instruction a call site; where none of
them is among the longest there is nothing to read."""


def matching(evidence: dict, prefix: str) -> list:
    """[[name, seconds], ...] of the kept operations with that prefix."""
    trace = evidence.get("trace") or {}
    return [op for op in trace.get("device_ops", []) if op[0].startswith(prefix)]


def read(evidence: dict, args: dict):
    ops = matching(evidence, args["prefix"])
    return sum(seconds for _, seconds in ops) if ops else None
