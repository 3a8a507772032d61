"""Share of the HBM roofline the traced retrain's device time reached:
the least bytes the configured ALS iterations must move, over the device
kind's peak bytes/s (``peaks.json``), over the device's busy seconds, in %.

Least bytes (``als_least_bytes``): per half-step every rating is read once
(4-byte index of the other side + 4-byte value), the other side's factor table
is read once and the updated table written once, f32 — i.e. perfect on-chip
reuse of gathered rows.  The FLOP bound (2 * rank^2 per rating per half-step
over the peak) is ~10x lower at rank 10, so bytes bound the kernel.  Busy time
holds everything the device ran during the retrain, not the ALS step alone:
the kernels carry no names yet."""


def als_least_bytes(nnz: int, num_users: int, num_items: int, rank: int, iterations: int) -> int:
    ratings = 2 * nnz * 8
    tables = 2 * (num_users + num_items) * rank * 4
    return iterations * (ratings + tables)


def read(evidence: dict, args: dict):
    trace = evidence.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    kind = evidence["device"]["kind"]
    peaks = evidence["peaks"]["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    cfg = evidence["config"]
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    data = cfg["data"]
    least = als_least_bytes(
        data["nnz"], data["num_users"], data["num_items"],
        algo["rank"], algo["numIterations"],
    )
    floor_s = least / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * floor_s / trace["busy_s"]
