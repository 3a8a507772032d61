"""A scope's share of its roofline, in %: the least time the device could take
for the work the configuration requires over the device self time the traced
retrain spent under the scope, whatever implements it (a kernel, fusions, the
copies between them) and in every pass (forward, the forward run again under
``jax.checkpoint``, backward).  Required is ONE forward and ONE backward, so
a program that recomputes less reads higher and none can pass 100 %.

Least time of a piece of work = max(FLOPs / the device's bf16 peak, bytes /
its HBM peak), each piece bound on its own; the bf16 peak also where a
kernel's products are float32, so the share errs low."""

from benchmark.readers import device_scope_seconds


def share_pct(evidence: dict, args: dict, work):
    """``work(evidence)`` -> [(FLOPs, bytes), ...] of the required pieces;
    called only where the trace has time under ``args``' scopes."""
    busy_s = device_scope_seconds.read(evidence, args)
    if not busy_s:
        return None
    pieces = work(evidence)
    if not pieces:
        return None
    kind = evidence["device"]["kind"]
    peaks = evidence["peaks"]["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    least_s = sum(
        max(flops / peaks[kind]["bf16_flops_per_s"],
            nbytes / peaks[kind]["hbm_bytes_per_s"])
        for flops, nbytes in pieces
    )
    return 100.0 * least_s / busy_s


def trained_rows(cfg: dict) -> int:
    """Packed rows a retrain trains: the configured steps x rows a step."""
    algo = cfg["engine_json"]["algorithms"][0]["params"]
    return algo["stepsPerRetrain"] * algo["rowsPerStep"]
