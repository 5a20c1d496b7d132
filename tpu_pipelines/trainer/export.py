"""Model export/load: self-contained serving payloads.

The Model artifact payload (what Pusher ships, what InfraValidator/
BulkInferrer/serving load) is fully self-contained:

    <uri>/checkpoint/        orbax params checkpoint
    <uri>/module_copy.py     user module (defines build_model)
    <uri>/transform_graph/   copy of the resolved TransformGraph (optional)
    <uri>/model_spec.json    hyperparameters, feature names, format version

Loading reconstructs ``predict(raw_batch)`` = transform host stage (numpy
string ops) → one jitted on-chip function (numeric transform fused with the
model forward pass) — preprocessing and model co-located on TPU, the
``jit_compile=True`` serving/bulk-inference story from BASELINE, with zero
training/serving skew because the TransformGraph is the same artifact the
Trainer's input data was materialized through.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from tpu_pipelines.models.decode_contract import DecodeContract
from tpu_pipelines.trainer import quantize as qz
from tpu_pipelines.transform.graph import TransformGraph
from tpu_pipelines.utils.module_loader import load_fn, load_module

SPEC_FILE = "model_spec.json"
MODULE_COPY = "module_copy.py"
CHECKPOINT_DIR = "checkpoint"
TRANSFORM_DIR = "transform_graph"
FORMAT_VERSION = "tpu-pipelines-model/v1"


def export_model(
    *,
    serving_model_dir: str,
    params: Any,
    module_file: str,
    hyperparameters: Optional[Dict[str, Any]] = None,
    transform_graph_uri: str = "",
    extra_spec: Optional[Dict[str, Any]] = None,
    serving_dtype: Optional[str] = None,
    training_statistics_uri: str = "",
    training_schema_uri: str = "",
) -> str:
    """Write a self-contained model payload; returns the dir.

    Multi-host safe: the orbax save is a collective every process joins
    (each writes the param shards it owns into the shared dir); all other
    writes are plain files and happen on process 0 only.
    """
    os.makedirs(serving_model_dir, exist_ok=True)
    import orbax.checkpoint as ocp

    primary = jax.process_index() == 0
    ckpt_path = os.path.abspath(os.path.join(serving_model_dir, CHECKPOINT_DIR))
    if primary and os.path.exists(ckpt_path):
        shutil.rmtree(ckpt_path)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("export_model:pre_save")
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(ckpt_path, params)

    if primary:
        shutil.copyfile(
            module_file, os.path.join(serving_model_dir, MODULE_COPY)
        )
        if transform_graph_uri:
            dst = os.path.join(serving_model_dir, TRANSFORM_DIR)
            if os.path.exists(dst):
                shutil.rmtree(dst)
            shutil.copytree(transform_graph_uri, dst)
        spec = {
            "format": FORMAT_VERSION,
            "hyperparameters": hyperparameters or {},
            "has_transform": bool(transform_graph_uri),
            # Serving-payload metadata (ISSUE 14): the dtype the loader
            # should serve at (bf16 payloads cast ONCE at load; aqt_int8
            # payloads dequantize inside the jitted step) and the
            # resident parameter bytes — what the fleet's
            # serving_version_memory_bytes gauge reports per version.
            "dtype": serving_dtype or qz.infer_dtype(params),
            "params_bytes": qz.params_nbytes(params),  # tpp: disable=TPP214 (payload key)
            **(extra_spec or {}),
        }
        # Training-data lineage (ISSUE 20): the statistics/schema URIs the
        # deployed fleet scores live traffic against — recorded on the
        # payload itself so serving never walks the metadata store.  Only
        # written when provided, so pre-existing payload specs stay
        # byte-identical.
        if training_statistics_uri:
            spec["training_statistics_uri"] = training_statistics_uri
        if training_schema_uri:
            spec["training_schema_uri"] = training_schema_uri
        with open(os.path.join(serving_model_dir, SPEC_FILE), "w") as f:
            json.dump(spec, f, indent=2, sort_keys=True, default=str)
    return serving_model_dir


class AotDispatch:
    """Shape-keyed table of ahead-of-time compiled serving executables.

    ``serving/aot.py`` populates it at swap/canary time (one compiled —
    or cache-deserialized — executable per padded bucket shape); the
    loaded model's predict paths consult it before falling back to the
    lazily-traced jit.  Empty table = zero-cost passthrough (one truthy
    check per request), so payloads outside the fleet never pay for it.

    A post-warm lookup MISS that falls back to jit is a broken warmup
    contract — the request pays an XLA trace mid-traffic.  The first
    miss per (endpoint, signature) increments ``compiles_after_warm``
    (repeats hit the jit cache, so only the first is a compile) and
    fires ``on_compile_after_warm`` — the fleet wires that to
    ``serving_aot_compiles_after_warm_total`` (budget: zero), the
    predict twin of the decode engine's counter.
    """

    def __init__(self, home=None):
        # (endpoint, signature, device): an executable runs on the device
        # it was compiled for, so a fleet with one replica per chip holds
        # one entry per chip.  The payload's home device — where an
        # unpinned caller computes — is keyed None, here and nowhere else.
        self.home = home
        self.entries: Dict[Tuple[str, tuple, Any], Any] = {}
        self.fallbacks = 0
        self.compiles_after_warm = 0
        self.on_compile_after_warm: Optional[Callable[[], None]] = None
        self._fallback_sigs: set = set()
        self._lock = threading.Lock()

    @staticmethod
    def signature(batch: Dict[str, Any]) -> tuple:
        return tuple(sorted(
            (k, tuple(np.shape(v)), str(np.asarray(v).dtype))
            for k, v in batch.items()
        ))

    def _key(self, endpoint: str, sig: tuple, device) -> tuple:
        return (endpoint, sig, None if device == self.home else device)

    def lookup(self, endpoint: str, batch: Dict[str, Any], device=None):
        return self.entries.get(
            self._key(endpoint, self.signature(batch), device)
        )

    def install(
        self, endpoint: str, sig: tuple, executable: Any, device=None
    ) -> None:
        with self._lock:
            self.entries[self._key(endpoint, sig, device)] = executable

    def record_fallback(
        self, endpoint: str, batch: Dict[str, Any], device=None
    ) -> None:
        sig = self._key(endpoint, self.signature(batch), device)
        fresh = False
        with self._lock:
            self.fallbacks += 1
            if sig not in self._fallback_sigs:
                self._fallback_sigs.add(sig)
                self.compiles_after_warm += 1
                fresh = True
            cb = self.on_compile_after_warm
        if fresh and cb is not None:
            cb()


@dataclasses.dataclass
class LoadedModel:
    params: Any
    model: Any                       # flax Module from build_model
    spec: Dict[str, Any]
    transform: Optional[TransformGraph]
    predict: Callable[[Dict[str, np.ndarray]], Any]
    predict_transformed: Callable[[Dict[str, np.ndarray]], Any]
    # Autoregressive generation (seq2seq models): present when the exported
    # module defines ``make_generate_step(model, hyperparameters)`` (preferred;
    # returns ``fn(params, transformed_batch)``) or the legacy
    # ``make_generate_fn(model, params, hyperparameters)``.  ``generate``
    # takes raw batches (host transform applied first); None otherwise.
    generate: Optional[Callable[[Dict[str, np.ndarray]], Any]] = None
    # The model's ``DecodeContract`` (models/decode_contract.py): present
    # when the exported module defines ``make_decode_fns(model,
    # hyperparameters)`` (e.g. ``models/t5.py make_continuous_decode_fns``);
    # what the generative fleet model type builds its per-replica engines
    # from.  None = whole-request generate only.
    decode_fns: Optional[DecodeContract] = None
    # The two halves of `predict`, exposed for exporters (serving/
    # saved_model.py): host string stage (numpy, identity when no transform)
    # and the device computation (numeric transform fused with the forward
    # pass).  ``device_predict`` binds the loaded params, so tracing it
    # (jax2tf) embeds the weights — correct for SavedModel export.
    host_preprocess: Callable[[Dict[str, np.ndarray]], Dict[str, np.ndarray]] = None
    device_predict: Callable[[Dict[str, Any]], Any] = None
    # The raw jitted step underlying predict/predict_transformed, taking
    # ``(params, batch)``.  Params are ARGUMENTS of the compiled program —
    # never closed over — so the compiled predict program is weight-free
    # (a closure would bake every weight into the HLO as a literal constant:
    # one copy per compiled entry point, and oversized compile payloads on
    # remote-compile platforms).  Tested by test_export_no_weight_constants.
    forward_step: Callable[[Any, Dict[str, Any]], Any] = None
    device_step: Callable[[Any, Dict[str, Any]], Any] = None
    # Serving-payload metadata recorded at export (spec["dtype"] /
    # spec["params_bytes"]): the dtype this payload serves at
    # ("float32" | "bfloat16" | "aqt_int8") and its resident parameter
    # bytes (quantized payloads count int8 + scale storage).  The fleet
    # publishes both per resident version.
    dtype: str = "float32"
    params_bytes: int = 0
    # Training-data lineage stamped on the payload spec at export or
    # Pusher time (ISSUE 20): the ExampleStatistics payload the model
    # trained against ("" = unstamped) and its schema.  The fleet's
    # TrafficSampler resolves its drift baseline from these — no
    # metadata-store walk at serving time.
    training_statistics_uri: str = ""
    training_schema_uri: str = ""
    # Payload directory this model was loaded from ("" for hand-built
    # instances) — the AOT executable cache keys on its content hash.
    uri: str = ""
    # Ahead-of-time executable table (serving/aot.py warms it at the
    # fleet's canary gate; empty = lazy jit, the pre-ISSUE-14 behavior).
    aot: Optional[AotDispatch] = None
    # ``params_on(device)``: the params tree resident on ``device``, copied
    # there once on first ask (None = the home device the payload was
    # restored onto).  predict/predict_transformed compute on the device
    # the calling thread pinned with ``jax.default_device`` — a fleet
    # replica per chip — instead of dragging every replica's work back to
    # the home device the committed params would otherwise pin it to.
    params_on: Callable[[Any], Any] = None


def model_input_columns(
    loaded: "LoadedModel", raw: bool
) -> Optional[List[str]]:
    """Columns the loaded model's predict path actually consumes, for
    column-projected Parquet reads (Evaluator/BulkInferrer pass these as
    ``columns=`` instead of decoding every column).

    ``raw=True`` is the predict/generate surface (embedded transform applied
    to raw examples): the transform graph's input features.  ``raw=False``
    is predict_transformed: the transform's output features.  Returns None —
    read everything — when the payload carries no transform graph (the
    model's feature selection is then invisible from the spec) so projection
    can never starve an unknown model.
    """
    if loaded.transform is None:
        return None
    cols = (
        loaded.transform.input_feature_names() if raw
        else loaded.transform.output_feature_names()
    )
    # Models may read declared feature lists beyond the transform surface
    # (e.g. a hyperparameter-selected passthrough column).
    extra = (loaded.spec.get("hyperparameters") or {}).get("features")
    if isinstance(extra, (list, tuple)):
        cols = sorted(set(cols) | {str(c) for c in extra})
    return cols


def _checkpoint_abstract(uri: str, sharding=None) -> Any:
    """Shape/dtype(/sharding) tree of an exported payload's checkpoint, read
    from checkpoint metadata — no arrays materialized.  None when the
    metadata layout is unreadable (orbax version drift); the ONE place that
    parsing lives, so restore and warm-start validation cannot diverge."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(os.path.join(uri, CHECKPOINT_DIR))
    try:
        with ocp.StandardCheckpointer() as ckptr:
            meta = ckptr.metadata(path).item_metadata.tree
        return jax.tree.map(
            lambda m: jax.ShapeDtypeStruct(
                tuple(m.shape), m.dtype, sharding=sharding
            ),
            meta,
        )
    except Exception:
        return None


def restore_exported_params(uri: str) -> Any:
    """Restore the params checkpoint of an exported payload, device-resident.

    The checkpoint is restored against an abstract target reconstructed from
    the checkpoint's own metadata (shape/dtype tree), avoiding orbax's
    untyped-restore path and its UNSAFE warnings, then ``device_put`` once so
    every subsequent jitted call ships no host arrays.
    """
    import orbax.checkpoint as ocp

    path = os.path.abspath(os.path.join(uri, CHECKPOINT_DIR))
    target = _checkpoint_abstract(
        uri, sharding=jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
    )
    with ocp.StandardCheckpointer() as ckptr:
        if target is not None:
            return ckptr.restore(path, target)
        return jax.device_put(ckptr.restore(path))


def exported_params_abstract(uri: str) -> Any:
    """Shape/dtype tree of an exported payload's checkpoint — see
    ``_checkpoint_abstract`` (no arrays materialized; None when the
    metadata layout is unreadable)."""
    return _checkpoint_abstract(uri)


def warm_start_init(fn_args, init_params_fn):
    """TFX warm-start semantics for ``run_fn`` modules.

    When the Trainer received a ``base_model`` input (e.g. wired from
    ``Resolver(strategy="latest_created")``), ``fn_args.custom_config``
    carries ``base_model_uri``; the returned init fn then restores the
    exported payload's params instead of random-initializing.  Without a
    base model it returns ``init_params_fn`` unchanged, so modules can wrap
    unconditionally::

        init_params_fn = warm_start_init(fn_args, init_params_fn)

    Both init contracts are honored: a plain params tree, and the
    ``has_model_state`` two-tuple ``(params, model_state)`` — exported
    payloads carry params only, so model_state stays freshly initialized.

    The restored params must match the module's own init exactly
    (structure, shapes, dtypes) — warm-starting across architecture changes
    is a config error surfaced with the offending paths, not a silent
    partial load.  Validation runs on ``jax.eval_shape`` of the init and
    the checkpoint's metadata, so no throwaway random init is materialized.
    """
    uri = (getattr(fn_args, "custom_config", None) or {}).get(
        "base_model_uri", ""
    )
    if not uri:
        return init_params_fn

    from tpu_pipelines.parallel.partition import path_str

    def _validate(fresh_params, restored):
        fresh_flat = jax.tree_util.tree_flatten_with_path(fresh_params)[0]
        rest_flat = jax.tree_util.tree_flatten_with_path(restored)[0]
        fresh_map = {path_str(path): leaf for path, leaf in fresh_flat}
        rest_map = {path_str(path): leaf for path, leaf in rest_flat}
        problems = []
        for key in sorted(set(fresh_map) | set(rest_map)):
            a, b = fresh_map.get(key), rest_map.get(key)
            if a is None or b is None:
                problems.append(f"{key}: only in "
                                f"{'base model' if a is None else 'init'}")
            elif a.shape != b.shape or a.dtype != b.dtype:
                problems.append(
                    f"{key}: init {a.shape}/{a.dtype} vs "
                    f"base model {b.shape}/{b.dtype}"
                )
        if problems:
            raise ValueError(
                f"warm-start base model at {uri!r} does not match this "
                f"module's params: " + "; ".join(problems[:8])
            )

    def init(rng, sample_batch):
        shapes = jax.eval_shape(init_params_fn, rng, sample_batch)
        is_tuple = isinstance(shapes, tuple) and len(shapes) == 2
        params_shapes = shapes[0] if is_tuple else shapes
        abstract = exported_params_abstract(uri)
        if abstract is not None:
            _validate(params_shapes, abstract)
        model_state = None
        if is_tuple:
            fresh_params, model_state = init_params_fn(rng, sample_batch)
            # Free the throwaway random params before restoring, so peak
            # device memory holds one params tree, not two.
            del fresh_params
        restored = restore_exported_params(uri)
        if abstract is None:  # metadata unreadable: concrete validation
            _validate(params_shapes, restored)
        return (restored, model_state) if is_tuple else restored

    return init


def load_exported_model(uri: str) -> LoadedModel:
    """Reload an exported payload into a ready predict function."""
    with open(os.path.join(uri, SPEC_FILE)) as f:
        spec = json.load(f)
    if spec.get("format") != FORMAT_VERSION:
        raise ValueError(
            f"model at {uri!r} has format {spec.get('format')!r}, "
            f"expected {FORMAT_VERSION}"
        )
    module_copy = os.path.join(uri, MODULE_COPY)
    module = load_module(module_copy)
    build_model = load_fn(module_copy, "build_model")
    model = build_model(spec.get("hyperparameters", {}))
    # Optional module hook for models whose __call__ is not dict-of-features
    # (e.g. image models taking one array): apply_fn(model, params, batch).
    apply_fn = getattr(
        module, "apply_fn",
        lambda model, params, batch: model.apply({"params": params}, batch),
    )

    params = restore_exported_params(uri)
    dtype = str(spec.get("dtype") or qz.infer_dtype(params))
    quantized = dtype == qz.DTYPE_AQT_INT8 or qz.tree_is_quantized(params)
    if dtype == qz.DTYPE_BFLOAT16:
        # bf16 fast path: ONE cast at load (a no-op when the checkpoint
        # already stores bf16), so no request ever pays a per-call cast
        # and the resident tree holds half the bytes.
        import jax.numpy as jnp

        params = qz.cast_params(params, jnp.bfloat16)
    if quantized:
        # aqt_int8 payloads stay int8-resident; the dequant runs INSIDE
        # the jitted step (fused by XLA — gathers read int8 rows), so
        # apply_fn always sees the dense tree it was written against.
        raw_apply = apply_fn

        def apply_fn(model, p, batch, _apply=raw_apply):
            return _apply(model, qz.dequantize_params(p), batch)

    transform = None
    if spec.get("has_transform"):
        transform = TransformGraph.load(os.path.join(uri, TRANSFORM_DIR))

    @jax.jit
    def _forward(params, transformed: Dict[str, Any]):
        return apply_fn(model, params, transformed)

    # AOT executable table: serving/aot.py fills it per padded bucket at
    # the fleet's swap gate; until then every lookup short-circuits on
    # the empty-dict check and the jit path below is exactly pre-AOT.
    home = jax.local_devices()[0]   # where restore_exported_params put them
    aot = AotDispatch(home=home)
    copies: Dict[Any, Any] = {}
    copies_lock = threading.Lock()

    def params_on(device=None):
        if device is None or device == home:
            return params
        with copies_lock:
            tree = copies.get(device)
            if tree is None:
                tree = copies[device] = jax.device_put(params, device)
        return tree

    def _dispatch(endpoint: str, jit_fn, batch):
        # Committed params decide where a jitted call runs, so the params
        # follow the device the calling thread pinned (a fleet replica's
        # ``jax.default_device``); unpinned callers stay on the home device.
        device = jax.config.jax_default_device
        if not isinstance(device, jax.Device):
            device = None
        p = params_on(device)
        if aot.entries:
            exe = aot.lookup(endpoint, batch, device)
            if exe is not None:
                return exe(p, batch)
            aot.record_fallback(endpoint, batch, device)
        return jit_fn(p, batch)

    if transform is not None:
        host_fn, device_fn, _ = transform.split_host_device()

        @jax.jit
        def _transform_and_forward(params, iface: Dict[str, Any]):
            # Numeric transform + model forward in ONE compiled computation.
            return apply_fn(model, params, device_fn(iface))

        def predict(raw_batch: Dict[str, np.ndarray]):
            return _dispatch("raw", _transform_and_forward, host_fn(raw_batch))

        host_preprocess = host_fn
        device_step = _transform_and_forward
    else:
        def predict(raw_batch: Dict[str, np.ndarray]):
            return _dispatch("raw", _forward, raw_batch)

        host_preprocess = lambda b: b  # noqa: E731
        device_step = _forward

    def predict_transformed(batch: Dict[str, np.ndarray]):
        return _dispatch("transformed", _forward, batch)

    generate = None
    step_builder = getattr(module, "make_generate_step", None)
    gen_builder = getattr(module, "make_generate_fn", None)
    if quantized:
        # Generate/decode hooks receive the params tree verbatim and were
        # written against dense params; a quantized payload serves the
        # predict surfaces only.  A generative fleet's canary refuses it
        # (no decode contract) instead of crashing mid-decode.
        step_builder = gen_builder = None
    if step_builder is not None:
        # Preferred hook: fn(params, transformed_batch) — params stay a jit
        # argument all the way down.
        generate_step = step_builder(model, spec.get("hyperparameters", {}))
        device_generate = lambda b: generate_step(params, b)  # noqa: E731
    elif gen_builder is not None:
        # Legacy hook closes over params inside the user module; still
        # supported, but large models should migrate to make_generate_step.
        device_generate = gen_builder(
            model, params, spec.get("hyperparameters", {})
        )
    else:
        device_generate = None
    if device_generate is not None:
        if transform is not None:
            _transform_dev = jax.jit(device_fn)

            def generate(raw_batch: Dict[str, np.ndarray]):
                return device_generate(_transform_dev(host_fn(raw_batch)))
        else:
            generate = device_generate

    decode_builder = (
        None if quantized else getattr(module, "make_decode_fns", None)
    )
    decode_fns = None
    if decode_builder is not None:
        # Continuous-batching contract for the generative fleet model
        # type; params stay engine arguments (never closed over), same
        # discipline as make_generate_step.
        decode_fns = decode_builder(model, spec.get("hyperparameters", {}))

    return LoadedModel(
        params=params,
        model=model,
        spec=spec,
        transform=transform,
        predict=predict,
        predict_transformed=predict_transformed,
        host_preprocess=host_preprocess,
        device_predict=lambda batch: device_step(params, batch),
        forward_step=_forward,
        device_step=device_step,
        generate=generate,
        decode_fns=decode_fns,
        dtype=dtype,
        # Resident bytes of the tree actually held in memory (after the
        # bf16 load cast / with int8 + scales), not the on-disk figure.
        params_bytes=qz.params_nbytes(params),
        training_statistics_uri=str(spec.get("training_statistics_uri") or ""),
        training_schema_uri=str(spec.get("training_schema_uri") or ""),
        uri=os.path.abspath(uri),
        aot=aot,
        params_on=params_on,
    )
