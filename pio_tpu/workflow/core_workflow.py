"""CoreWorkflow — train/eval drivers with run bookkeeping.

Rebuild of the reference's ``workflow/CoreWorkflow.scala`` +
``workflow/CreateWorkflow.scala`` + ``workflow/EvaluationWorkflow.scala``
(UNVERIFIED paths; see SURVEY.md): set the EngineInstance status to RUNNING,
run ``Engine.train``, persist models (pickled blob ≙ reference Kryo blob, or
``PersistentModel`` custom path), mark COMPLETED — or FAILED with the error
recorded, so ``pio status``/dashboard surface crashed runs.

Upgrade over the reference: per-phase wall-time is recorded into the
instance env (the reference has no tracing at all — SURVEY.md §5).
"""

from __future__ import annotations

import contextlib
import dataclasses
import datetime as _dt
import hashlib
import json as _json
import logging
import pickle
import traceback
from typing import Any, List, Optional, Sequence

import numpy as np

from pio_tpu.controller.components import PersistentModel
from pio_tpu.obs import REGISTRY, Tracer, monotonic_s
from pio_tpu.controller.engine import Engine, EngineParams
from pio_tpu.controller.evaluation import (
    EngineParamsGenerator,
    Evaluation,
    MetricEvaluator,
    MetricEvaluatorResult,
)
from pio_tpu.controller.params import params_to_dict, params_to_json
from pio_tpu.parallel.context import ComputeContext
from pio_tpu.storage import (
    EngineInstance,
    EvaluationInstance,
    Model,
    RunStatus,
    Storage,
)
from pio_tpu.obs import devicewatch, slog, trainwatch
from pio_tpu.obs.profile import reduce_scopes
from pio_tpu.workflow import shard_store
from pio_tpu.workflow.engine_json import EngineVariant
from pio_tpu.workflow.params import WorkflowParams

log = logging.getLogger("pio_tpu.workflow")

#: training-run tracer (process-global registry): every run lands in the
#: ring (inspectable in-process) and feeds pio_tpu_train_stage_seconds
#: histograms — stage labels are the engine.train timing keys
#: (read / prepare / train:<algo>) plus "persist". Wide buckets: reads
#: are milliseconds, ALS on a real corpus is minutes.
TRAIN_TRACER = Tracer(
    "train", registry=REGISTRY,
    stages=("read", "prepare", "persist"),
    buckets=(0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0, 60.0,
             300.0, 1800.0, 7200.0),
)


#: Models-store id suffix of the checksum manifest written next to each
#: pickled blob: {"sha256": ..., "size": ...}. Blob first, manifest
#: second — a crash between the two leaves a blob without a manifest,
#: which loads unverified (the pre-manifest behavior), never a manifest
#: promising bytes that don't exist.
MANIFEST_SUFFIX = ".manifest"

_MODEL_FALLBACK = REGISTRY.counter(
    "pio_tpu_model_fallback_total",
    "Deploys that fell back to an older COMPLETED instance's model "
    "after the requested instance's blob failed verification",
)


def _utcnow() -> _dt.datetime:
    return _dt.datetime.now(_dt.timezone.utc)


def _to_host(obj: Any) -> Any:
    """Pull device arrays in a model pytree back to host numpy for pickling.

    jax.Array leaves (possibly sharded) become np.ndarray; anything jax
    doesn't recognize passes through untouched.
    """
    import jax

    def leaf(x):
        return np.asarray(jax.device_get(x)) if isinstance(x, jax.Array) else x

    return jax.tree_util.tree_map(leaf, obj)


def serialize_models(models: Sequence[Any]) -> bytes:
    """Default model persistence (≙ reference Kryo blob via KryoInjection)."""
    return pickle.dumps([_to_host(m) for m in models], protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_models(blob: bytes) -> List[Any]:
    return pickle.loads(blob)


def run_train(
    engine: Engine,
    engine_params: EngineParams,
    variant: EngineVariant,
    workflow_params: WorkflowParams = WorkflowParams(),
    ctx: Optional[ComputeContext] = None,
) -> str:
    """Train + persist; returns the engine-instance id
    (reference ``CoreWorkflow.runTrain``)."""
    if ctx is None:
        ctx = ComputeContext.create(seed=workflow_params.seed)
    instances = Storage.get_meta_data_engine_instances()
    now = _utcnow()
    instance = EngineInstance(
        id="",
        status=RunStatus.RUNNING,
        start_time=now,
        end_time=now,
        engine_id=variant.engine_id,
        engine_version=variant.engine_version,
        engine_variant=variant.path or variant.engine_id,
        engine_factory=variant.engine_factory,
        batch=workflow_params.batch,
        env={},
        jax_conf=variant.jax_conf,
        data_source_params=params_to_json(engine_params.data_source_params),
        preparator_params=params_to_json(engine_params.preparator_params),
        algorithms_params=_json.dumps(
            [
                {"name": n, "params": params_to_dict(p)}
                for n, p in engine_params.algorithm_params_list
            ],
            sort_keys=True,
        ),
        serving_params=params_to_json(engine_params.serving_params),
    )
    instance_id = instances.insert(instance)
    instance = instances.get(instance_id)
    # JSON log ring + volume counter for the train path too — `pio
    # train` is a daemonless run, so the ring is its only /logs.json
    # analog (dumped on failure, queryable in-process by tests)
    slog.install()
    log.info("training started: instance %s", instance_id)

    if workflow_params.checkpoint_every > 0:
        from pio_tpu.workflow.checkpoint import (
            default_checkpoint_dir,
            state_fingerprint,
        )

        # Default dir keys on the engine variant + params (NOT the per-run
        # instance id): a preempted run restarted with the same config
        # finds its snapshots; the data fingerprint recorded inside guards
        # against resuming across a data change.
        stable_key = state_fingerprint(
            variant.engine_id,
            variant.engine_factory,
            instance.data_source_params,
            instance.preparator_params,
            instance.algorithms_params,
        )
        ckpt_dir = workflow_params.checkpoint_dir or default_checkpoint_dir(
            stable_key
        )
        ctx = dataclasses.replace(
            ctx,
            checkpoint_base=ckpt_dir,
            checkpoint_every=workflow_params.checkpoint_every,
        )

    # telemetry plane (ISSUE 16): the recorder collects step-stream
    # progress from the training loops, renders /train.json for the
    # status sidecar, and lands in the run ledger on exit
    recorder = trainwatch.StepRecorder(instance_id, variant.engine_id)
    params_hash = hashlib.sha256(
        "\n".join([
            instance.data_source_params or "",
            instance.preparator_params or "",
            instance.algorithms_params or "",
            instance.serving_params or "",
        ]).encode()
    ).hexdigest()[:16]

    def _append_run_record(status: str, train_s: float,
                           timings: dict, *,
                           shard_manifest: Optional[str] = None,
                           error: Optional[str] = None) -> None:
        # ledger append is best-effort by design: a full disk or torn
        # runs dir must never fail (or un-fail) the run itself
        try:
            rec = trainwatch.run_record(
                run_id=instance_id,
                engine_id=variant.engine_id,
                status=status,
                train_seconds=train_s,
                phases={
                    k.replace(":", "."): float(v)
                    for k, v in recorder.phases.items()
                } or {
                    k.replace(":", "."): float(v)
                    for k, v in timings.items()
                },
                params_hash=params_hash,
                step_summary=recorder.summary(),
                num_devices=ctx.num_devices,
                platform=ctx.platform,
                device_kind=ctx.device_kind,
                shard_manifest=shard_manifest,
                error=error,
                device_scopes=device_scopes,
                xla=devicewatch.xla_totals(),
            )
            path = trainwatch.append_run(rec)
            log.info("run record appended to %s", path)
        except Exception as exc:
            log.warning("run-ledger append failed: %s", exc)

    t0 = monotonic_s()
    timings: dict = {}
    device_scopes: Optional[dict] = None  # a --profile-dir run's, reduced
    try:
        # the device watch samples memory + attributes trainer compiles
        # for the run's duration; the status sidecar serves its payload
        # as /device.json while steps stream
        with trainwatch.recording(recorder), devicewatch.watching(
            devicewatch.DeviceWatch()
        ), TRAIN_TRACER.trace(
            "train", instanceId=instance_id, engineId=variant.engine_id
        ) as tr:
            with contextlib.ExitStack() as stack:
                if workflow_params.profile_dir:
                    # jax.profiler trace of the whole train — the rebuild's
                    # Spark UI equivalent; view with tensorboard/xprof
                    import jax as _jax

                    stack.enter_context(
                        _jax.profiler.trace(workflow_params.profile_dir)
                    )
                models = engine.train(
                    ctx,
                    engine_params,
                    skip_sanity_check=workflow_params.skip_sanity_check,
                    stop_after_read=workflow_params.stop_after_read,
                    stop_after_prepare=workflow_params.stop_after_prepare,
                    timings=timings,
                )
            train_s = monotonic_s() - t0
            if workflow_params.profile_dir:
                # the trace has closed: reduce it to seconds per named
                # device scope for the run record (the raw trace stays
                # where the operator asked for it)
                try:
                    device_scopes = reduce_scopes(workflow_params.profile_dir)
                except Exception as exc:  # a CPU trace has no device plane
                    log.info("no device scopes from the profile: %s", exc)
            # the phases already ran inside LIVE tr.span()s (engine.train
            # opens one per phase since ISSUE 16), so the stage
            # histograms (pio_tpu_train_stage_seconds) and the trace ring
            # saw them as they happened and every in-phase log line
            # carries (trace_id, span) — /logs.json?trace_id= reassembles
            # one run's full story. Here we only log the summary.
            for phase, dur in timings.items():
                log.info(
                    "train phase %s done in %.3fs (instance %s)",
                    phase, float(dur), instance_id,
                )
            if (workflow_params.stop_after_read
                    or workflow_params.stop_after_prepare):
                instances.update(instance.with_status(RunStatus.ABORTED))
                log.info(
                    "run %s aborted early by stop-after flag", instance_id
                )
                return instance_id

            # Persist: PersistentModel handles itself; everything else goes
            # into the Models store as one pickled blob.
            recorder.set_phase("persist")
            t_persist = monotonic_s()
            shard_manifest_id = None
            with tr.span("persist"):
                persisted_externally = []
                for (name, algo_params), model in zip(
                    engine_params.algorithm_params_list, models
                ):
                    if isinstance(model, PersistentModel):
                        persisted_externally.append(
                            model.save(instance_id, algo_params, ctx)
                        )
                    else:
                        persisted_externally.append(False)
                blob_models = [
                    None if ext else m
                    for ext, m in zip(persisted_externally, models)
                ]
                models_store = Storage.get_model_data_models()
                if shard_store.sharded_persist_enabled():
                    # ShardableModel arrays go out as per-shard records +
                    # a shard manifest (written BEFORE the blob: a torn
                    # persist leaves a blob-less shard set, never a blob
                    # naming missing shards); the blob keeps placeholders
                    mesh_shape = (
                        [int(s) for s in ctx.mesh.devices.shape]
                        if ctx.mesh is not None
                        else [1]
                    )
                    blob_models = shard_store.save_sharded(
                        models_store,
                        instance_id,
                        blob_models,
                        n_shards=ctx.num_devices,
                        mesh_shape=mesh_shape,
                    )
                    shard_manifest_id = (
                        instance_id + shard_store.SHARD_MANIFEST_SUFFIX
                    )
                blob = serialize_models(blob_models)
                models_store.insert(Model(id=instance_id, models=blob))
                manifest = _json.dumps(
                    {
                        "sha256": hashlib.sha256(blob).hexdigest(),
                        "size": len(blob),
                    },
                    sort_keys=True,
                ).encode()
                models_store.insert(
                    Model(id=instance_id + MANIFEST_SUFFIX, models=manifest)
                )
            recorder.set_phase_seconds(
                "persist", monotonic_s() - t_persist
            )
            recorder.set_phase("done")

            done = dataclasses.replace(
                instance.with_status(RunStatus.COMPLETED),
                env={
                    "train_seconds": f"{train_s:.3f}",
                    "num_devices": str(ctx.num_devices),
                    "platform": ctx.platform,
                    "device_kind": ctx.device_kind,
                    # per-phase wall seconds (read / prepare / train:<algo>)
                    **{f"phase_{k}": str(v) for k, v in timings.items()},
                },
            )
            instances.update(done)
            _append_run_record(
                "COMPLETED", train_s, timings,
                shard_manifest=shard_manifest_id,
            )
            log.info(
                "training finished: instance %s (%.2fs, %d model(s))",
                instance_id, train_s, len(models),
            )
            return instance_id
    except Exception:
        err = traceback.format_exc()
        failed = dataclasses.replace(
            instance.with_status(RunStatus.FAILED), env={"error": err[-4000:]}
        )
        instances.update(failed)
        # failed runs land in the ledger too — a crash IS trend data
        _append_run_record(
            "FAILED", monotonic_s() - t0, timings, error=err,
        )
        log.error("training FAILED: instance %s\n%s", instance_id, err)
        raise


def _verified_blob_models(
    models_store, instance_id: str, ctx: Optional[ComputeContext] = None
) -> List[Any]:
    """Fetch + checksum-verify + deserialize one instance's model blob.

    Raises RuntimeError on a missing record, a checksum mismatch against
    the instance's manifest, or a blob that fails to unpickle. A missing
    manifest (pre-manifest instance, or crash between blob and manifest
    writes) loads unverified. Shard-stripped models (sharded persist)
    reassemble from checksum-verified shard records; a missing/torn
    shard set raises like a torn blob, so the same last-known-good
    fallback applies.
    """
    record = models_store.get(instance_id)
    if record is None:
        raise RuntimeError(f"no models stored for instance {instance_id!r}")
    manifest = models_store.get(instance_id + MANIFEST_SUFFIX)
    if manifest is not None:
        try:
            want = _json.loads(manifest.models.decode("utf-8"))["sha256"]
        except Exception as e:
            raise RuntimeError(
                f"unreadable model manifest for instance {instance_id!r}: {e}"
            ) from e
        got = hashlib.sha256(record.models).hexdigest()
        if got != want:
            raise RuntimeError(
                f"model blob for instance {instance_id!r} failed checksum "
                f"verification (manifest {want}, blob {got})"
            )
    try:
        models = deserialize_models(record.models)
    except Exception as e:
        raise RuntimeError(
            f"model blob for instance {instance_id!r} failed to "
            f"deserialize: {e}"
        ) from e
    return shard_store.restore_sharded(
        models_store,
        instance_id,
        models,
        n_devices=ctx.num_devices if ctx is not None else None,
    )


def load_models_for_instance(
    instance_id: str,
    engine: Engine,
    engine_params: EngineParams,
    ctx: ComputeContext,
    variant: Optional[EngineVariant] = None,
) -> List[Any]:
    """Models-store blob + PersistentModel loads
    (reference ``Engine.prepareDeploy``).

    With ``variant`` given, a blob that fails verification (torn write,
    bit rot, half-persisted crash) does not fail the deploy: the loader
    falls back to the newest older COMPLETED instance of the same variant
    whose blob verifies — last known good — and serves that instead.
    """
    models_store = Storage.get_model_data_models()
    try:
        blob_models = _verified_blob_models(models_store, instance_id, ctx)
    except RuntimeError as primary_err:
        if variant is None:
            raise
        log.error(
            "model load for instance %s failed (%s); searching for last "
            "known good", instance_id, primary_err,
        )
        blob_models = None
        candidates = Storage.get_meta_data_engine_instances().get_completed(
            variant.engine_id,
            variant.engine_version,
            variant.path or variant.engine_id,
        )
        for cand in candidates:
            if cand.id == instance_id:
                continue
            try:
                blob_models = _verified_blob_models(
                    models_store, cand.id, ctx
                )
            except RuntimeError as e:
                log.warning("fallback candidate %s also bad: %s", cand.id, e)
                continue
            _MODEL_FALLBACK.inc()
            log.warning(
                "serving last known good instance %s in place of %s",
                cand.id, instance_id,
            )
            # PersistentModel loads below must come from the SAME instance
            # as the blob, or externally-persisted algorithms would mix
            # generations
            instance_id = cand.id
            break
        if blob_models is None:
            raise primary_err
    out = []
    for (name, algo_params), blob_model in zip(
        engine_params.algorithm_params_list, blob_models
    ):
        if blob_model is not None:
            out.append(blob_model)
            continue
        algo_cls = engine.algorithm_class_map[name]
        model_cls = getattr(algo_cls, "model_class", None)
        if model_cls is None or not issubclass(model_cls, PersistentModel):
            raise RuntimeError(
                f"algorithm {name!r}: model was persisted externally but "
                f"{algo_cls.__name__} declares no PersistentModel model_class"
            )
        out.append(model_cls.load(instance_id, algo_params, ctx))
    return out


def run_evaluation(
    evaluation: Evaluation,
    generator: EngineParamsGenerator,
    workflow_params: WorkflowParams = WorkflowParams(),
    ctx: Optional[ComputeContext] = None,
    evaluation_class: str = "",
    generator_class: str = "",
) -> MetricEvaluatorResult:
    """Sweep params, record the winner (reference
    ``EvaluationWorkflow.runEvaluation``). Returns the result; the
    EvaluationInstance row carries its JSON for the dashboard."""
    if ctx is None:
        ctx = ComputeContext.create(seed=workflow_params.seed)
    instances = Storage.get_meta_data_evaluation_instances()
    now = _utcnow()
    instance = EvaluationInstance(
        id="",
        status=RunStatus.RUNNING,
        start_time=now,
        end_time=now,
        evaluation_class=evaluation_class or type(evaluation).__name__,
        engine_params_generator_class=generator_class or type(generator).__name__,
        batch=workflow_params.batch,
    )
    instance_id = instances.insert(instance)
    instance = instances.get(instance_id)
    try:
        evaluator = MetricEvaluator(evaluation.metric, evaluation.other_metrics)
        result = evaluator.evaluate(
            ctx, evaluation.engine, generator.engine_params_list
        )
        done = dataclasses.replace(
            instance.with_status(RunStatus.COMPLETED),
            evaluator_results=f"{result.metric_header}: {result.best_score}",
            evaluator_results_json=result.to_json(),
        )
        instances.update(done)
        return result
    except Exception:
        err = traceback.format_exc()
        failed = dataclasses.replace(
            instance.with_status(RunStatus.FAILED), evaluator_results=err[-4000:]
        )
        instances.update(failed)
        raise
