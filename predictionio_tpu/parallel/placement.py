"""Shard placement: the declarative layout layer training AND serving consume.

``parallel/mesh.py`` answers "what devices do I have" (mesh construction,
process-local chunk balancing, per-device attribution).  This module answers
"where does each ARRAY live", as data rather than code:

- :class:`ShardPlan` is a serializable description of a model's placement —
  mesh axes plus a PartitionSpec per named array.  It rides inside the
  persisted model AND the lifecycle generation manifest (PR 7), so a sharded
  model permanently records how it was laid out, and ``deploy`` re-binds the
  same plan onto whatever mesh the serving host has (``rebind`` re-shards on
  a device-count mismatch: the spec names axes, never device ids).
- :func:`shard_put` / :func:`replicate` / :func:`gather_rows` wrap
  ``device_put``/pjit so engines never touch raw ``NamedSharding``.
- :func:`build_sharded_topk` is the model-parallel serving kernel recipe of
  arXiv 2004.13336 expressed as one ``shard_map``: each device scores a
  query batch against ONLY the catalog rows it owns, top-ks locally, and the
  shards exchange just the ``k`` winners (an ``all_gather`` of ``[B, k]``
  candidates — never the full score row) before a replicated merge.  The
  fan-out/fan-in shape is the DrJAX MapReduce-over-mesh idiom
  (arXiv 2403.07128): broadcast queries, map per shard, reduce by merge.

Tie-breaking is bit-compatible with a single-device ``lax.top_k``: local
top-k orders equal scores by ascending local row, shards gather in axis
order, and the merge's ``top_k`` prefers earlier positions — so equal scores
resolve to the lowest GLOBAL row id, exactly like the unsharded kernel
(asserted by the tier-1 parity suite, including ties that straddle a shard
boundary).
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from predictionio_tpu.parallel.mesh import (
    MeshConfig,
    make_mesh,
    pad_to_multiple,
)

#: ShardPlan wire-format version (rides inside model blobs and generation
#: manifests; bump on incompatible layout changes)
PLAN_SCHEMA_VERSION = 1

#: trace-time record of the most recent sharded-top-k kernel's PER-SHARD
#: shapes, keyed by kernel name — the test hook proving no device ever
#: materializes a full catalog score row (``rows_local`` < catalog size)
LAST_KERNEL_SHAPES: dict[str, dict[str, int]] = {}


class ShardPlanError(ValueError):
    """A plan cannot be applied (unknown array, bad axes, no such axis)."""


@dataclass(frozen=True)
class ShardPlan:
    """Declarative per-array placement over a named mesh.

    ``axes`` maps mesh axis name -> size; a size of -1 means "all devices
    available at bind time" (the serving default — training records the
    layout, deploy decides the width).  ``specs`` maps array name -> a
    partition tuple with one entry per dimension: an axis name shards that
    dimension, ``None`` leaves it unsharded.  Arrays not named in ``specs``
    are replicated.  ``rows`` optionally records each array's REAL leading
    row count (pre-padding), so re-binding knows how much of a padded table
    is catalog and how much is sharding fill.
    """

    axes: dict[str, int] = field(default_factory=lambda: {"model": -1})
    specs: dict[str, tuple] = field(default_factory=dict)
    rows: dict[str, int] = field(default_factory=dict)

    # -- serialization (model blob + generation manifest) --------------------

    def to_dict(self) -> dict[str, Any]:
        return {
            "schema": PLAN_SCHEMA_VERSION,
            "axes": dict(self.axes),
            "specs": {k: list(v) for k, v in self.specs.items()},
            "rows": dict(self.rows),
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, Any] | None) -> "ShardPlan | None":
        if not d:
            return None
        return cls(
            axes=dict(d.get("axes") or {"model": -1}),
            specs={k: tuple(v) for k, v in (d.get("specs") or {}).items()},
            rows=dict(d.get("rows") or {}),
        )

    @classmethod
    def model_parallel(
        cls,
        sharded: Sequence[str],
        rows: Mapping[str, int] | None = None,
        axis: str = "model",
        ndims: Mapping[str, int] | None = None,
    ) -> "ShardPlan":
        """The standard embedding-table plan: each named table row-sharded
        over ``axis`` (2-D ``(axis, None)`` unless ``ndims`` says 1-D, e.g.
        a per-item bias vector); everything else replicated."""
        specs = {}
        for name in sharded:
            nd = (ndims or {}).get(name, 2)
            specs[name] = (axis,) + (None,) * (nd - 1)
        return cls(axes={axis: -1}, specs=specs, rows=dict(rows or {}))

    # -- binding -------------------------------------------------------------

    def rebind(self, n_devices: int) -> "ShardPlan":
        """Re-shard the plan for ``n_devices``: axis names are kept, sizes
        re-solved.  A single -1 axis absorbs all devices; fixed axes whose
        product no longer divides the device count collapse onto the FIRST
        axis that appears in a spec (the sharding axis) — the layout is a
        property of the mesh you have, not the mesh you trained on."""
        n_devices = max(int(n_devices), 1)
        sizes = dict(self.axes) or {"model": -1}
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ShardPlanError("at most one plan axis may be -1")
        fixed = int(np.prod([v for v in sizes.values() if v != -1] or [1]))
        if wild and n_devices % fixed == 0:
            sizes[wild[0]] = n_devices // fixed
        elif int(np.prod(list(sizes.values()))) != n_devices:
            # device count changed since the plan was recorded: put every
            # device on the sharding axis, collapse the rest
            shard_axis = next(
                (e for spec in self.specs.values() for e in spec if e),
                next(iter(sizes)),
            )
            sizes = {k: 1 for k in sizes}
            sizes[shard_axis] = n_devices
        return ShardPlan(axes=sizes, specs=dict(self.specs), rows=dict(self.rows))

    def mesh(self, devices: Sequence[Any] | None = None) -> Mesh:
        """Build the mesh this plan describes over the given (default: all)
        devices, re-solving sizes for the actual device count first."""
        devices = list(devices if devices is not None else jax.devices())
        plan = self.rebind(len(devices))
        return make_mesh(MeshConfig(axes=dict(plan.axes)), devices=devices)

    def spec(self, name: str) -> PartitionSpec:
        return PartitionSpec(*self.specs.get(name, ()))

    def sharding(self, mesh: Mesh, name: str) -> NamedSharding:
        return NamedSharding(mesh, self.spec(name))

    def shard_multiple(self, mesh: Mesh, name: str) -> int:
        """Leading-dim divisibility requirement for ``name`` on ``mesh``."""
        entries = self.specs.get(name, ())
        if not entries or entries[0] is None:
            return 1
        axis = entries[0]
        if axis not in mesh.shape:
            raise ShardPlanError(
                f"plan shards {name!r} over axis {axis!r} but the mesh has "
                f"axes {dict(mesh.shape)}"
            )
        return int(mesh.shape[axis])


# ---------------------------------------------------------------------------
# placement helpers — the only device_put engines should need


def shard_put(
    mesh: Mesh, plan: ShardPlan, name: str, array: Any
) -> tuple[jax.Array, int]:
    """Pad + place one named array per the plan; returns ``(device_array,
    real_rows)``.  Leading-dim sharding pads rows to the axis size so every
    device owns an equal slice (padding is masked downstream — the sharded
    top-k never surfaces rows past ``real_rows``)."""
    arr = np.asarray(array)
    mult = plan.shard_multiple(mesh, name)
    padded, n = pad_to_multiple(arr, mult, axis=0)
    return jax.device_put(padded, plan.sharding(mesh, name)), n


def replicate(mesh: Mesh, array: Any) -> jax.Array:
    """Place an array replicated on every device of the mesh."""
    arr = jnp.asarray(array)
    return jax.device_put(
        arr, NamedSharding(mesh, PartitionSpec(*([None] * arr.ndim)))
    )


def shard_put_tree(
    mesh: Mesh, plan: ShardPlan, tree: Mapping[str, Any]
) -> tuple[dict[str, Any], dict[str, int]]:
    """Place a flat name->array mapping: named-in-plan arrays shard (rows
    recorded), everything else replicates.  Non-array leaves (lists of MLP
    layer dicts, configs) pass through ``jax.device_put`` untouched only if
    they are arrays; containers recurse leaf-wise replicated."""
    out: dict[str, Any] = {}
    rows: dict[str, int] = {}
    for name, value in tree.items():
        if name in plan.specs:
            out[name], rows[name] = shard_put(mesh, plan, name, value)
        else:
            out[name] = jax.tree_util.tree_map(
                lambda x: replicate(mesh, x)
                if hasattr(x, "shape") or isinstance(x, (int, float))
                else x,
                value,
            )
    return out, rows


@lru_cache(maxsize=16)
def _gather_rows_fn(mesh: Mesh):
    return jax.jit(
        lambda table, idx: table[idx],
        out_shardings=NamedSharding(mesh, PartitionSpec()),
    )


def gather_rows(mesh: Mesh, table: jax.Array, idx: jax.Array) -> jax.Array:
    """Replicated ``table[idx]`` rows from a (row-sharded) table — ONE pjit
    program whose cross-shard gather XLA lowers to the collective lookup
    (the "model-parallel embedding lookup" half of the 2004.13336 recipe).
    """
    return _gather_rows_fn(mesh)(table, idx)


# ---------------------------------------------------------------------------
# the factor-sharded top-k kernel


def build_sharded_topk(
    mesh: Mesh,
    plan: ShardPlan,
    local_scores_fn: Callable[..., jax.Array],
    param_names: Sequence[str],
    n_items: int,
    k: int,
    axis: str = "model",
    name: str = "sharded_topk",
    local_topk_fn: Callable[..., tuple] | None = None,
):
    """Compile a factor-sharded top-k: ``fn(params..., queries) -> [2, B, k]``.

    ``local_scores_fn(*local_params, queries)`` returns ``[B, rows_local]``
    scores for the catalog rows THIS shard owns (``queries`` is replicated —
    typically already-gathered user rows).  The kernel:

    1. masks rows past the real catalog (``n_items``) to -inf (sharding
       padding must never win);
    2. per-shard ``top_k`` of ``min(k, rows_local)`` candidates, offset to
       global row ids, padded to ``k`` with -inf when a shard owns fewer
       than ``k`` rows (``k > per-shard candidates`` stays correct);
    3. ``all_gather`` of the ``[B, k]`` winners along ``axis`` — the ONLY
       cross-device exchange, shard-major so the final merge's top_k
       tie-breaks by lowest global row id exactly like an unsharded kernel;
    4. replicated merge to the packed ``[2, B, k]`` f32 layout (row 0
       scores, row 1 item ids — one D2H transfer, ids exact below 2^24).

    ``local_topk_fn(*local_params, queries, kc, limit)``, when given,
    replaces steps 1-2 with a FUSED per-shard kernel (ops/topk.py): it
    returns ``(values [B, kc], local_ids [B, kc])`` directly, masking local
    rows at or past ``limit`` (a traced scalar — the catalog tail on the
    last shard), so no device ever materializes even its local score block:
    the largest live slab per shard is the fused kernel's tile.  Tie order
    must match ``lax.top_k`` (value desc, id asc) — the fused kernel's
    contract — so the merged result stays bit-identical either way.

    Returns the jitted callable; callers cache per (mesh, shapes, k) the
    same way the engines cache their unsharded kernels.
    """
    in_specs = tuple(plan.spec(p) for p in param_names) + (PartitionSpec(),)
    out_spec = PartitionSpec()
    n_shards = int(mesh.shape[axis])

    def body(*args):
        *params, queries = args
        rows_local = params[0].shape[0]
        kc = min(k, rows_local)
        base = jax.lax.axis_index(axis) * rows_local
        if local_topk_fn is not None:
            # fused per-shard path: the local [B, rows_local] score block
            # never exists — only the kernel's [B, tile] slab does
            limit = jnp.clip(n_items - base, 0, rows_local)
            v, li = local_topk_fn(*params, queries, kc, limit)
            gi = li.astype(jnp.int32) + base
            shapes = {"fused": 1}
        else:
            scores = local_scores_fn(*params, queries)  # [B, rows_local]
            rows_local = scores.shape[-1]
            kc = min(k, rows_local)
            gidx = base + jnp.arange(rows_local, dtype=jnp.int32)
            scores = jnp.where(gidx[None, :] < n_items, scores, -jnp.inf)
            # equal scores: lowest local row
            v, i = jax.lax.top_k(scores, kc)
            gi = (i.astype(jnp.int32) + base)[..., :kc]
            shapes = {"fused": 0}
        # the per-shard shape contract: each device scores only its slice
        LAST_KERNEL_SHAPES[name] = {
            "rows_local": int(rows_local),
            "batch": int(queries.shape[0]),
            "k": int(k),
            "n_shards": n_shards,
            "n_items": int(n_items),
            **shapes,
        }
        if kc < k:  # a shard owns fewer rows than k: pad its candidate list
            v = jnp.pad(v, ((0, 0), (0, k - kc)), constant_values=-jnp.inf)
            gi = jnp.pad(gi, ((0, 0), (0, k - kc)))
        # fan-in: ONLY the k winners cross the mesh, shard-major order
        allv = jax.lax.all_gather(v, axis, axis=1, tiled=True)  # [B, S*k]
        alli = jax.lax.all_gather(gi, axis, axis=1, tiled=True)
        mv, mpos = jax.lax.top_k(allv, k)  # ties: earliest shard/local row
        mi = jnp.take_along_axis(alli, mpos, axis=1)
        return jnp.stack([mv, mi.astype(jnp.float32)])

    return jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
            check_vma=False,  # outputs ARE replicated post-merge; vma can't prove
        )
    )


# ---------------------------------------------------------------------------
# one sharded wave, fully instrumented


#: settle-poll cadence: readiness is sampled every POLL (so per-device
#: resolution is ~200 µs — far below the skew thresholds measured in
#: multi-ms waves), and a wave stuck past MAX_WAIT falls back to blocking
#: so settle measurement can never hang a healthy dispatch path
_SETTLE_POLL_S = 0.0002
_SETTLE_MAX_WAIT_S = 30.0


def settle_shards(result: Any, t0: float) -> dict[str, float]:
    """Per-device **settle clock** of one sharded dispatch: sample every
    device's ``is_ready()`` on a fixed cadence and record the observed
    seconds-since-dispatch at which that device's slices became ready.
    Because readiness is *polled* across all devices rather than blocked on
    one at a time, a straggling device shows a larger settle time no matter
    where it sits in device order — devices that finished earlier were
    already marked ready on an earlier poll round.

    The ``shard.settle`` fault seam rides here as a QUERY
    (:meth:`~predictionio_tpu.resilience.faults.FaultInjector.latency`):
    a ``kind="latency"`` rule matching a device label *defers that device's
    observed readiness* instead of sleeping the poll — how the chaos suite
    manufactures a deterministic straggler on a CPU mesh whose virtual
    devices all finish together.  Returns ``{}`` for unsharded results
    (host arrays, single-device) and for runtimes without per-array
    readiness probes."""
    from predictionio_tpu.resilience import faults

    shards = getattr(result, "addressable_shards", None)
    if not shards:
        return {}
    pending: dict[str, list[Any]] = {}
    for shard in shards:
        d = shard.device
        if not hasattr(shard.data, "is_ready"):
            return {}
        pending.setdefault(f"{d.platform}:{d.id}", []).append(shard.data)
    if len(pending) < 2:
        return {}
    out: dict[str, float] = {}
    give_up = t0 + _SETTLE_MAX_WAIT_S
    # this poll IS the measurement: XLA exposes no per-array completion
    # callback to wait on, so sampling is_ready() on a fixed cadence is
    # the only order-independent way to clock each device's readiness
    # pio: ignore[PIO-CONC002]
    while pending:
        now = time.perf_counter()
        for label in list(pending):
            try:
                ready = all(x.is_ready() for x in pending[label])
            except Exception:
                ready = True  # a failed probe must not wedge the wave
            if ready:
                out[label] = now - t0
                del pending[label]
        if not pending:
            break
        if now > give_up:
            # pathological stall: stop attributing, block like the caller
            # is about to anyway, and charge the stragglers the full wait
            for label in pending:
                out[label] = time.perf_counter() - t0
            break
        time.sleep(_SETTLE_POLL_S)
    if faults.ACTIVE is not None:
        for label in out:
            out[label] += faults.ACTIVE.latency("shard.settle", label)
    return out


def run_observed_wave(
    fn: str,
    *,
    kernel: Callable[..., Any],
    sig: tuple,
    host_input: np.ndarray,
    compute: Callable[[jax.Array], tuple],
    shard_arrays: Mapping[str, Any],
) -> np.ndarray:
    """Dispatch one sharded serving wave under the full instrumentation
    contract shared by every engine: recompile-signature note, h2d stage +
    transfer bytes, timed compute, deferred AOT cost capture, wave
    device/cost annotation, d2h stage + transfer bytes, efficiency observe,
    and per-shard attribution into the wave timeline (``wave_shards``).

    ``compute(dev_input)`` runs the kernel and returns ``(packed_dev,
    cost_args)`` — the device result and the positional args
    ``capture_cost`` should trace the kernel with.

    Unlike the UNSHARDED wave paths (which capture cost before dispatch so
    the AOT analysis thread overlaps the jit compile), cost capture here
    necessarily runs after compute: the capture args include collectives'
    outputs (e.g. the gathered query rows) that only exist inside
    ``compute``.  It is still ``defer=True`` — never inside a wave
    deadline."""
    from predictionio_tpu.obs import device as device_obs
    from predictionio_tpu.parallel.mesh import meter_shards

    eff = device_obs.default_efficiency()
    device_obs.default_recompiles().note_signature(fn, sig)
    with device_obs.wave_stage("h2d"):
        dev_input = jnp.asarray(host_input)
        device_obs.note_transfer("h2d", host_input.nbytes)
    t_dev = time.perf_counter()
    with device_obs.wave_stage("compute"):
        packed_dev, cost_args = compute(dev_input)
        # per-shard settle clock: each participating device's OWN observed
        # readiness (the straggler board's input), then the whole result
        shard_seconds = settle_shards(packed_dev, t_dev)
        packed_dev.block_until_ready()
    compute_s = time.perf_counter() - t_dev
    eff.capture_cost(fn, kernel, *cost_args, signature=sig, defer=True)
    device_obs.note_wave_device(device_obs.device_label(packed_dev))
    device_obs.note_wave_cost(fn, eff.cached_cost(fn, sig))
    with device_obs.wave_stage("d2h"):
        packed = np.asarray(packed_dev)
        device_obs.note_transfer("d2h", packed.nbytes)
    eff.observe(fn, compute_s, signature=sig)
    # per-wave per-device attribution: which shard held how many bytes for
    # this wave, and each participant's measured time (per-device settle
    # seconds when the result is sharded, the SPMD wall clock otherwise)
    attribution = meter_shards(
        fn, shard_arrays, seconds=shard_seconds or compute_s
    )
    device_obs.note_wave_shards(attribution)
    if shard_seconds:
        device_obs.note_shard_seconds(shard_seconds)
        device_obs.default_stragglers().record_wave(
            fn,
            shard_seconds,
            {dev: e.get("bytes", 0.0) for dev, e in attribution.items()},
        )
    return packed


# ---------------------------------------------------------------------------
# serving-side bundle: what an engine keeps after binding a plan


@dataclass
class BoundShards:
    """One model's sharded serving state: the bound mesh, the placed arrays,
    their real row counts, and a per-(batch, k) kernel cache."""

    plan: ShardPlan
    mesh: Mesh
    arrays: dict[str, Any]
    rows: dict[str, int]
    _kernels: dict[tuple, Any] = field(default_factory=dict)

    @property
    def n_shards(self) -> int:
        axis = next(
            (e for spec in self.plan.specs.values() for e in spec if e),
            None,
        )
        return int(self.mesh.shape[axis]) if axis else 1

    def kernel(self, key: tuple, build: Callable[[], Any]) -> Any:
        fn = self._kernels.get(key)
        if fn is None:
            fn = self._kernels[key] = build()
        return fn

    def attribution(self) -> dict[str, dict[str, float]]:
        """Per-device byte attribution of the placed arrays (the
        ``shard_attribution`` view the acceptance tests assert on)."""
        from predictionio_tpu.parallel.mesh import shard_attribution

        return shard_attribution(
            {k: v for k, v in self.arrays.items() if k in self.plan.specs}
        )


def bind_shards(
    plan: ShardPlan,
    arrays: Mapping[str, Any],
    devices: Sequence[Any] | None = None,
) -> BoundShards:
    """Re-bind a recorded plan onto the CURRENT mesh: re-solve axis sizes
    for the devices at hand (re-sharding on device-count mismatch), pad and
    place every array.  The deploy-time half of the ShardPlan lifecycle."""
    mesh = plan.mesh(devices)
    bound_plan = plan.rebind(mesh.devices.size)
    placed, rows = shard_put_tree(mesh, bound_plan, arrays)
    # plan-recorded real row counts win over inferred ones (an array may
    # arrive pre-padded from a checkpoint)
    for name, n in bound_plan.rows.items():
        if name in rows:
            rows[name] = min(rows[name], int(n))
    return BoundShards(plan=bound_plan, mesh=mesh, arrays=placed, rows=rows)
