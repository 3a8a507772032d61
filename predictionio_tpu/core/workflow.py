"""Train and evaluation workflows.

Mirrors workflow/CoreWorkflow.scala: ``run_train`` (runTrain:45) executes the
engine's train pipeline, checkpoints the models into the MODELDATA store, and
records an EngineInstance row (status INIT -> COMPLETED/FAILED);
``run_evaluation`` (runEvaluation:104 + EvaluationWorkflow.scala:36) sweeps an
engine-params list through batch evaluation, scores with the evaluator, and
records an EvaluationInstance.  There is no spark-submit process hop — the
workflow runs in-process on the TPU VM.
"""

from __future__ import annotations

import json
import logging
import traceback
import uuid
from dataclasses import dataclass, field
from datetime import datetime, timezone
from typing import Any, Callable, Sequence

from predictionio_tpu.core.base import EngineContext
from predictionio_tpu.core.engine import Engine, EngineParams
from predictionio_tpu.core.persistence import save_models
from predictionio_tpu.data.storage.base import EngineInstance, EvaluationInstance
from predictionio_tpu.data.storage.config import StorageRuntime, get_storage
from predictionio_tpu.obs.logging import (
    reset_request_context,
    set_request_context,
)
from predictionio_tpu.obs.tracing import (
    install_jax_compile_listener,
    jax_compile_stats,
    trace,
)

log = logging.getLogger("predictionio_tpu.workflow")


@dataclass
class WorkflowParams:
    """Workflow flags (workflow/WorkflowParams.scala:32)."""

    batch: str = ""
    verbose: int = 2
    skip_sanity_check: bool = False
    stop_after_read: bool = False
    stop_after_prepare: bool = False


def _now() -> datetime:
    return datetime.now(tz=timezone.utc)


def _stage_breakdown(root, compile_delta_s: float | None = None) -> dict:
    """Per-stage seconds from the run's span tree + the compile split.

    The root's children (the DASE stages) by name, as ever; then every
    deeper span by its own name, seconds accumulated over same-named spans.
    Same-named spans of several threads ran side by side (the two sides of
    ``als.stage``): their value is the longest thread's, and ``parallel``
    lists those names.  ``compile_delta_s`` is the growth of
    ``pio_jax_compile_seconds`` over this run — stage wall time minus it
    approximates pure execute time.  Every name so far is seconds; what the
    spans COUNTED (a span's ``counters`` tag, a dict of numbers: the routed
    layers' pairs) goes under the one key ``counters``, same names summed.
    """
    out = {
        name: round(secs, 4) for name, secs in root.breakdown().items()
    }
    by_thread: dict[str, dict[int, float]] = {}
    counters: dict[str, float] = {}
    spans = [g for c in root.children for g in c.children]
    while spans:
        s = spans.pop()
        spans.extend(s.children)
        threads = by_thread.setdefault(s.name, {})
        threads[s.thread_id] = threads.get(s.thread_id, 0.0) + s.duration_s
        for name, n in ((s.tags or {}).get("counters") or {}).items():
            counters[name] = counters.get(name, 0) + n
    for name, threads in by_thread.items():
        out.setdefault(name, round(max(threads.values()), 4))
    parallel = sorted(n for n, t in by_thread.items() if len(t) > 1)
    if parallel:
        out["parallel"] = parallel
    if counters:
        out["counters"] = counters
    out["total"] = round(root.duration_s, 4)
    if compile_delta_s is not None:
        out["jax_compile"] = round(compile_delta_s, 4)
    return out


def run_train(
    engine: Engine,
    engine_params: EngineParams,
    ctx: EngineContext | None = None,
    workflow_params: WorkflowParams | None = None,
    engine_id: str = "default",
    engine_version: str = "default",
    engine_variant: str = "default",
    engine_factory: str = "",
    storage: StorageRuntime | None = None,
    warm_start_from: str | None = None,
) -> EngineInstance | None:
    """Train, persist models, and record the engine instance.

    Returns the COMPLETED EngineInstance (the deploy handle), or None when
    stopped early by stop_after_read/stop_after_prepare (no instance row is
    kept).  On failure the row is left in status FAILED and the exception
    re-raised.

    ``warm_start_from`` names a previous engine instance whose persisted
    models seed this run (``ctx.warm_start``): the lifecycle controller's
    incremental-retrain handle — ALS solves start from the previous
    factors, NCF from the previous embedding tables — so reacting to drift
    costs a fraction of a cold train.  A missing/unreadable previous model
    degrades to a cold start (logged), never a failed retrain.
    """
    storage = storage or get_storage()
    ctx = ctx or EngineContext(storage=storage)
    if warm_start_from is not None and ctx.warm_start is None:
        from predictionio_tpu.core.persistence import load_models

        try:
            ctx.warm_start = load_models(storage.models(), warm_start_from)
        except Exception as e:
            log.warning(
                "warm start from instance %s failed (%s); training cold",
                warm_start_from, e,
            )
        if ctx.warm_start is None:
            log.warning(
                "no persisted models for warm-start instance %s; training "
                "cold", warm_start_from,
            )
    wp = workflow_params or WorkflowParams()
    instances = storage.engine_instances()
    instance = EngineInstance(
        id=uuid.uuid4().hex,
        status="INIT",
        start_time=_now(),
        end_time=_now(),
        engine_id=engine_id,
        engine_version=engine_version,
        engine_variant=engine_variant,
        engine_factory=engine_factory,
        batch=wp.batch,
        mesh_conf=ctx.mesh_config.to_dict(),
        **engine_params.to_json_fields(),
    )
    instances.insert(instance)
    # compile-vs-execute split: XLA compile durations land in
    # pio_jax_compile_seconds alongside the stage spans
    install_jax_compile_listener()
    compile_s0 = jax_compile_stats()["compile_s"]
    # bind the engine-instance id as the run's correlation id: every log
    # line and span this training run emits carries request_id=<instance>,
    # the same correlation contract the serving path uses per query
    ctx_tokens = set_request_context(instance.id)
    try:
        with trace("workflow.run_train") as root:
            algos, models = engine.train_full(
                ctx,
                engine_params,
                skip_sanity_check=wp.skip_sanity_check,
                stop_after_read=wp.stop_after_read,
                stop_after_prepare=wp.stop_after_prepare,
            )
            if wp.stop_after_read or wp.stop_after_prepare:
                log.info("training stopped early by workflow params")
                instances.delete(instance.id)
                return None
            persistable = engine.make_persistent_models(
                ctx, engine_params, models, algos=algos
            )
            # PersistentModel flavors save themselves; only a manifest is
            # stored (Engine.makeSerializableModels:284 +
            # PersistentModelManifest)
            from predictionio_tpu.core.persistent_model import (
                PersistentModel,
                PersistentModelManifest,
            )

            stored = []
            for a, m in zip(algos, persistable):
                if isinstance(m, PersistentModel) and m.save(
                    instance.id, getattr(a, "params", None)
                ):
                    stored.append(
                        PersistentModelManifest(type(m).class_path())
                    )
                else:
                    stored.append(m)
            # sharded save: big array leaves (NCF tables, ALS factors)
            # become individual parts instead of one monolithic pickle blob
            with trace("train.persist.save_models"):
                save_models(storage.models(), instance.id, stored)
            # record the serving ShardPlan (if any algorithm declares one)
            # as a tiny sidecar blob: GenerationStore.record embeds it in
            # the manifest WITHOUT unpickling the whole model, and deploy
            # re-binds it onto the serving mesh
            _record_shard_plan(storage, instance.id, algos, models)
        done = instance.completed()
        instances.update(done)
        breakdown = _stage_breakdown(
            root, jax_compile_stats()["compile_s"] - compile_s0
        )
        log.info(
            "training finished: engine instance %s",
            instance.id,
            extra={"engine_instance": instance.id, "engine_id": engine_id},
        )
        log.info(
            "DASE stage breakdown: %s",
            json.dumps(breakdown, sort_keys=True),
            extra={"engine_instance": instance.id, "stages": breakdown},
        )
        return done
    except Exception:
        import dataclasses as _dc

        instances.update(
            _dc.replace(instance, status="FAILED", end_time=_now())
        )
        log.error(
            "training FAILED: engine instance %s",
            instance.id,
            extra={"engine_instance": instance.id, "engine_id": engine_id},
        )
        raise
    finally:
        reset_request_context(ctx_tokens)
        from predictionio_tpu.core.cleanup import run as _run_cleanups

        _run_cleanups()


#: storage-key suffix for the serving-layout sidecar blob (kept OUTSIDE the
#: checksummed model bytes: the manifest entry is the authoritative copy)
SHARD_PLAN_SUFFIX = ":shardplan"


def _record_shard_plan(storage, instance_id: str, algos, models) -> None:
    """Persist the first algorithm-declared ShardPlan for this instance.
    Best-effort bookkeeping — a failure here must never fail the train."""
    try:
        plan = next(
            (
                p
                for a, m in zip(algos, models)
                for p in [getattr(a, "serving_shard_plan", lambda _m: None)(m)]
                if p is not None
            ),
            None,
        )
        if plan is None:
            return
        storage.models().insert(
            f"{instance_id}{SHARD_PLAN_SUFFIX}",
            json.dumps(plan.to_dict(), sort_keys=True).encode("utf-8"),
        )
    except Exception as e:  # pragma: no cover - defensive
        log.warning("could not record shard plan for %s: %s", instance_id, e)


def read_shard_plan(models_store, instance_id: str) -> dict | None:
    """The recorded serving layout of one trained instance (dict form), or
    None when the model is unsharded / predates plans."""
    raw = models_store.get(f"{instance_id}{SHARD_PLAN_SUFFIX}")
    if raw is None:
        return None
    try:
        return json.loads(raw.decode("utf-8"))
    except (ValueError, UnicodeDecodeError):
        return None


def run_fake(
    fn: Callable[[EngineContext], Any],
    ctx: EngineContext | None = None,
    storage: StorageRuntime | None = None,
    label: str = "FakeWorkflow",
) -> Any:
    """Run an arbitrary function through the workflow plumbing
    (workflow/FakeWorkflow.scala:33-108): an EvaluationInstance records the
    run (EVALCOMPLETED/FAILED), cleanups fire, the function's return value
    comes back.  The reference uses this to script failure scenarios in
    tests; it doubles as a way to run ad-hoc jobs with workflow bookkeeping.
    """
    storage = storage or get_storage()
    ctx = ctx or EngineContext(storage=storage, mode="eval")
    instances = storage.evaluation_instances()
    instance = EvaluationInstance(
        id=uuid.uuid4().hex,
        status="EVALUATING",
        start_time=_now(),
        end_time=_now(),
        evaluation_class=label,
    )
    instances.insert(instance)
    import dataclasses as _dc

    try:
        result = fn(ctx)
        instances.update(
            _dc.replace(
                instance,
                status="EVALCOMPLETED",
                end_time=_now(),
                evaluator_results=f"{label} completed",
            )
        )
        return result
    except Exception:
        instances.update(_dc.replace(instance, status="FAILED", end_time=_now()))
        raise
    finally:
        from predictionio_tpu.core.cleanup import run as _run_cleanups

        _run_cleanups()


def run_evaluation(
    engine: Engine,
    engine_params_list: Sequence[EngineParams],
    evaluator: Any,
    ctx: EngineContext | None = None,
    evaluation_class: str = "",
    engine_params_generator_class: str = "",
    batch: str = "",
    storage: StorageRuntime | None = None,
) -> "EvaluationResult":
    """Sweep engine-params, score each, pick the best (MetricEvaluator role)."""
    from predictionio_tpu.eval.evaluator import EvaluationResult, MetricEvaluator

    storage = storage or get_storage()
    ctx = ctx or EngineContext(storage=storage, mode="eval")
    instances = storage.evaluation_instances()
    instance = EvaluationInstance(
        id=uuid.uuid4().hex,
        status="EVALUATING",
        start_time=_now(),
        end_time=_now(),
        evaluation_class=evaluation_class,
        engine_params_generator_class=engine_params_generator_class,
        batch=batch,
    )
    instances.insert(instance)
    try:
        if not isinstance(evaluator, MetricEvaluator):
            evaluator = MetricEvaluator(evaluator)
        with trace("workflow.run_evaluation"):
            result = evaluator.evaluate(ctx, engine, engine_params_list)
        import dataclasses as _dc

        instances.update(
            _dc.replace(
                instance,
                status="EVALCOMPLETED",
                end_time=_now(),
                evaluator_results=result.one_liner(),
                evaluator_results_html=result.to_html(),
                evaluator_results_json=result.to_json(),
            )
        )
        return result
    except Exception:
        import dataclasses as _dc

        instances.update(_dc.replace(instance, status="FAILED", end_time=_now()))
        raise
    finally:
        from predictionio_tpu.core.cleanup import run as _run_cleanups

        _run_cleanups()
