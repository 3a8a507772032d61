"""One number of the device allocator's statistics as the worker read them
around the retrain the trace covers (``args["key"]`` of
``workers/retrain_worker._memory``), times ``args["scale"]``.
``peak_bytes_reserved`` is what the compiled programs reserved for their
temporaries; a backend that keeps no statistics (the CPU) reads 0, which is
nothing to read."""


def read(evidence: dict, args: dict):
    memory = (evidence.get("retrain") or {}).get("memory") or {}
    value = memory.get(args["key"])
    if not value:
        return None
    return value * args.get("scale", 1.0)
