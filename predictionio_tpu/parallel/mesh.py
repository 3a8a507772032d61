"""Device-mesh construction and sharding helpers.

The Spark-replacement substrate: where the reference creates a SparkContext
per workflow (workflow/WorkflowContext.scala:28) and distributes via RDD
partitioning, this framework builds a ``jax.sharding.Mesh`` over the TPU
slice (ICI) — multi-host via ``jax.distributed`` — and shards arrays with
NamedSharding/shard_map.  Collectives (psum/all_gather/reduce_scatter) are
inserted by XLA from the sharding annotations.

Axis convention:
  - ``data``  — batch/data parallelism (events, queries, rating rows)
  - ``model`` — parameter sharding (embedding/factor-table rows)
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


@dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape, recorded into EngineInstance.mesh_conf.

    ``axes`` maps axis name -> size; a size of -1 means "all remaining
    devices".  Empty axes = one-device mesh (local/L-flavor compute).
    """

    axes: dict[str, int] = field(default_factory=lambda: {"data": -1})

    def to_dict(self) -> dict[str, Any]:
        return {"axes": dict(self.axes)}

    @classmethod
    def from_dict(cls, d: Mapping[str, Any] | None) -> "MeshConfig":
        if not d or not d.get("axes"):
            return cls()
        return cls(axes=dict(d["axes"]))


def make_mesh(
    config: MeshConfig | None = None, devices: Sequence[jax.Device] | None = None
) -> Mesh:
    """Build a Mesh from a MeshConfig over the given (default: all) devices."""
    config = config or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    axes = dict(config.axes) or {"data": -1}
    names = list(axes)
    sizes = list(axes.values())
    n = len(devices)
    fixed = int(np.prod([s for s in sizes if s != -1])) if sizes else 1
    n_wild = sum(1 for s in sizes if s == -1)
    if n_wild > 1:
        raise ValueError("at most one mesh axis may be -1 (auto)")
    if n_wild == 1:
        if n % fixed != 0:
            raise ValueError(
                f"{n} devices not divisible by fixed axes product {fixed}"
            )
        sizes = [n // fixed if s == -1 else s for s in sizes]
    total = int(np.prod(sizes))
    if total > n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} devices, have {n}")
    mesh_devices = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(mesh_devices, axis_names=tuple(names))


def default_mesh() -> Mesh:
    """All addressable devices on one ``data`` axis."""
    return make_mesh(MeshConfig())


def make_hybrid_mesh(
    ici_axes: Mapping[str, int] | None = None,
    dcn_axes: Mapping[str, int] | None = None,
) -> Mesh:
    """DCN-aware multi-slice mesh (the scaling-book recipe).

    Inner (``ici_axes``) dimensions map onto the fast intra-slice fabric,
    outer (``dcn_axes``) dimensions across slices over the data-center
    network — so bandwidth-hungry collectives (model-axis all-gathers,
    data-axis psums within a batch shard) ride ICI while only the
    low-frequency cross-slice reductions cross DCN.  Defaults: pure data
    parallelism across processes, all local devices on ``data``.
    """
    from jax.experimental import mesh_utils

    n_processes = jax.process_count()
    local = jax.local_device_count()
    ici = dict(ici_axes or {"data": local, "model": 1})
    dcn = dict(dcn_axes or {"data": n_processes, "model": 1})
    names = tuple(ici)
    if tuple(dcn) != names:
        raise ValueError(f"ici/dcn axis names must match: {names} vs {tuple(dcn)}")
    if n_processes == 1:
        # single host: collapse to a plain mesh with the combined shape
        sizes = {k: ici[k] * dcn[k] for k in names}
        return make_mesh(MeshConfig(axes=sizes))
    devices = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=[ici[k] for k in names],
        dcn_mesh_shape=[dcn[k] for k in names],
    )
    return Mesh(devices, axis_names=names)


def named_sharding(mesh: Mesh, *spec: str | None) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


def initialize_distributed() -> None:
    """Multi-host init (jax.distributed.initialize) driven by env vars.

    The NCCL/MPI-free analog of the reference's cluster bootstrap: each TPU-VM
    worker calls this once; XLA then runs collectives over ICI within a slice
    and DCN across slices.  No-op for single-process runs.
    """
    if os.environ.get("PIO_COORDINATOR_ADDRESS"):
        num_processes = int(os.environ.get("PIO_NUM_PROCESSES", "1"))
        if num_processes > 1 and os.environ.get("JAX_PLATFORMS", "").startswith(
            "cpu"
        ):
            # CPU multi-process (the local[*]-style test topology) needs a
            # real collectives implementation; the default 'none' silently
            # builds a single-process client (process_count() == 1)
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=os.environ["PIO_COORDINATOR_ADDRESS"],
            num_processes=num_processes,
            process_id=int(os.environ.get("PIO_PROCESS_ID", "0")),
        )


def balance_local_chunks(
    arrays: Sequence[np.ndarray], multiple: int
) -> tuple[list[np.ndarray], np.ndarray]:
    """Equalize per-process COO chunk lengths for a global data-sharded array.

    Each process holds a different number of locally-read rows (its event
    shards are not perfectly balanced); a global jax.Array needs every
    process to contribute the same length.  All-gathers the local lengths,
    pads every array to the common (chunk-aligned) target with zeros, and
    returns the padded arrays plus a float32 valid-mask (1.0 real rows) —
    the same weight-0-padding trick train_als uses, so padding rows are
    mathematically inert.

    The remainder-on-last-host case — one process read fewer (possibly
    zero) rows than its peers — is exactly what the all-gathered target
    handles: every process pads to the SAME chunk-aligned length, and the
    short host's extra padding carries valid=0.
    """
    if multiple <= 0:
        raise ValueError(f"multiple must be positive, got {multiple}")
    if not arrays:
        raise ValueError("balance_local_chunks needs at least one array")
    n_local = len(arrays[0])
    if any(len(a) != n_local for a in arrays):
        raise ValueError(
            "balance_local_chunks arrays must share one local length, got "
            f"{[len(a) for a in arrays]}"
        )
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        lens = multihost_utils.process_allgather(np.asarray(n_local))
        max_n = int(np.max(lens))
    else:
        max_n = n_local
    target = max((max_n + multiple - 1) // multiple * multiple, multiple)
    out = []
    for a in arrays:
        padded = np.zeros(target, a.dtype)
        padded[:n_local] = a
        out.append(padded)
    valid = np.zeros(target, np.float32)
    valid[:n_local] = 1.0
    return out, valid


def global_data_array(mesh: Mesh, local: np.ndarray, axis: str = "data"):
    """Assemble a global jax.Array sharded along ``axis`` from each
    process's local chunk (single-process: plain sharded device_put)."""
    sharding = NamedSharding(mesh, PartitionSpec(axis))
    if jax.process_count() == 1:
        return jax.device_put(local, sharding)
    return jax.make_array_from_process_local_data(sharding, local)


def global_replicated_array(mesh: Mesh, value) -> jax.Array:
    """Replicate a host array over every device of a (possibly
    multi-process) mesh; every process must pass the same value."""
    value = np.asarray(value)
    sharding = NamedSharding(mesh, PartitionSpec(*([None] * value.ndim)))
    if jax.process_count() == 1:
        return jax.device_put(value, sharding)
    return jax.make_array_from_callback(
        value.shape, sharding, lambda idx: value[idx]
    )


def shard_attribution(tree: Any) -> dict[str, dict[str, float]]:
    """Per-device byte/shard attribution of a pytree of jax.Arrays.

    Walks the leaves' ``addressable_shards`` and sums bytes per device
    label (``platform:id``) — on a sharded mesh each device reports only
    the slice it actually holds, so an imbalanced placement is visible as
    imbalanced bytes.  Host numpy leaves contribute nothing.
    """
    out: dict[str, dict[str, float]] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        if not isinstance(leaf, jax.Array):
            continue
        try:
            for shard in leaf.addressable_shards:
                d = shard.device
                label = f"{d.platform}:{d.id}"
                entry = out.setdefault(label, {"bytes": 0.0, "shards": 0})
                entry["bytes"] += float(shard.data.nbytes)
                entry["shards"] += 1
        except Exception:
            continue  # deleted/donated buffers mid-walk: skip the leaf
    return out


def meter_shards(
    fn: str,
    tree: Any,
    seconds: float | Mapping[str, float] | None = None,
    registry=None,
) -> dict[str, dict[str, float]]:
    """The per-device attribution hook: record where ``fn``'s arrays live.

    Sets ``pio_shard_bytes{fn,device}`` per device and — when ``seconds``
    is given — observes ``pio_shard_seconds{fn,device}``: a scalar means
    one SPMD wall clock spanning every participant (the training-loop
    case), a ``{device: seconds}`` mapping records each device's OWN
    measured time (the per-shard settle clock ``placement.settle_shards``
    produces — what the straggler board skews on).  This is the
    attribution seam sharded serving/training extends: the wave metrics'
    ``device`` label and these families share the ``platform:id``
    labeling.  Returns the attribution map.
    """
    from predictionio_tpu.obs.metrics import REGISTRY, STAGE_BUCKETS

    reg = registry or REGISTRY
    attribution = shard_attribution(tree)
    if not attribution:
        return attribution
    g_bytes = reg.gauge(
        "pio_shard_bytes",
        "Bytes of a named array group held per device",
        labelnames=("fn", "device"),
    )
    h_seconds = reg.histogram(
        "pio_shard_seconds",
        "Wall seconds of a named sharded step, per participating device",
        labelnames=("fn", "device"),
        buckets=STAGE_BUCKETS,
    )
    per_device = seconds if isinstance(seconds, Mapping) else None
    for label, entry in attribution.items():
        g_bytes.labels(fn, label).set(entry["bytes"])
        if per_device is not None:
            if label in per_device:
                h_seconds.labels(fn, label).observe(float(per_device[label]))
        elif seconds is not None:
            h_seconds.labels(fn, label).observe(seconds)
    return attribution


def pad_to_multiple(arr: np.ndarray, multiple: int, axis: int = 0, fill=0):
    """Pad an array along ``axis`` so its size divides evenly for sharding.

    Returns (padded, original_size).  Static-shape-friendly: callers mask with
    the original size inside jit instead of slicing dynamically.

    An EMPTY axis still pads up to one full multiple (each shard must own a
    non-empty equal slice; size 0 reports 0 real rows), and a non-positive
    ``multiple`` is a caller bug surfaced loudly — under sharding these are
    load-bearing, not degenerate, cases.
    """
    if multiple <= 0:
        raise ValueError(f"multiple must be positive, got {multiple}")
    size = arr.shape[axis]
    target = max(((size + multiple - 1) // multiple) * multiple, multiple)
    if target == size:
        return arr, size
    pad_widths = [(0, 0)] * arr.ndim
    pad_widths[axis] = (0, target - size)
    return np.pad(arr, pad_widths, constant_values=fill), size
