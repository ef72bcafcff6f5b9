"""REST server + routing — the RequestServer analog.

Reference: water/api/RequestServer.java:38 (route table, versioned
paths), water/api/ModelBuilderHandler.java (schema fill → trainModel),
water/api/RapidsHandler.java, ParseHandler/ParseSetupHandler,
FramesHandler, ModelsHandler, JobsHandler; Jetty at :54321.

TPU re-design: one stdlib ThreadingHTTPServer; routes are (method,
pattern) pairs dispatching to plain functions; training runs as
background Jobs (h2o3_tpu.jobs) the client polls via GET /3/Jobs/{key}
exactly like h2o-py's H2OJob.poll. Parameter coercion replaces the
reflection-driven Schema.fillFromParms: form values arrive as strings
and are json/number/bool-coerced against the estimator defaults."""
from __future__ import annotations

import json
import os
import re
import sys
import tempfile
import threading
import time
import urllib.parse
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from h2o3_tpu import dkv
from h2o3_tpu.api import schemas
from h2o3_tpu.jobs import Job, get_job

_ROUTES: List[Tuple[str, re.Pattern, Callable]] = []


def route(method: str, pattern: str):
    rx = re.compile("^" + re.sub(r"\{(\w+)\}", r"(?P<\1>[^/]+)", pattern) + "$")

    def deco(fn):
        _ROUTES.append((method, rx, fn))
        return fn
    return deco


class ApiError(Exception):
    def __init__(self, status: int, msg: str, headers=None):
        super().__init__(msg)
        self.status = status
        self.headers = dict(headers or {})   # e.g. Retry-After on 503


# ---------------- algo registry ---------------------------------------

def _builders() -> Dict[str, Any]:
    from h2o3_tpu import estimators as est
    return {"gbm": est.H2OGradientBoostingEstimator,
            "drf": est.H2ORandomForestEstimator,
            "glm": est.H2OGeneralizedLinearEstimator,
            "deeplearning": est.H2ODeepLearningEstimator,
            "kmeans": est.H2OKMeansEstimator,
            "pca": est.H2OPrincipalComponentAnalysisEstimator,
            "xgboost": est.H2OXGBoostEstimator,
            "isolationforest": est.H2OIsolationForestEstimator,
            "extendedisolationforest":
                est.H2OExtendedIsolationForestEstimator,
            "isotonicregression": est.H2OIsotonicRegressionEstimator,
            "svd": est.H2OSingularValueDecompositionEstimator,
            "aggregator": est.H2OAggregatorEstimator,
            "naivebayes": est.H2ONaiveBayesEstimator,
            "gam": est.H2OGeneralizedAdditiveEstimator,
            "glrm": est.H2OGeneralizedLowRankEstimator,
            "anovaglm": est.H2OANOVAGLMEstimator,
            "coxph": est.H2OCoxProportionalHazardsEstimator,
            "psvm": est.H2OSupportVectorMachineEstimator,
            "upliftdrf": est.H2OUpliftRandomForestEstimator,
            "word2vec": est.H2OWord2vecEstimator,
            "targetencoder": est.H2OTargetEncoderEstimator,
            "infogram": est.H2OInfogram,
            "grep": est.H2OGrepEstimator,
            "generic": est.H2OGenericEstimator,
            "modelselection": est.H2OModelSelectionEstimator,
            "rulefit": est.H2ORuleFitEstimator,
            "stackedensemble": est.H2OStackedEnsembleEstimator}


def _strlist(v) -> list:
    """Parse h2o-py's stringify_list output — '[AGE,PSA]' with UNQUOTED
    items (h2o-py/h2o/utils/shared_utils.py:213) — or JSON, or an
    actual list."""
    if isinstance(v, list):
        return v
    if v is None:
        return []
    s = str(v).strip()
    if s.startswith("["):
        try:
            return json.loads(s)
        except json.JSONDecodeError:
            inner = s[1:-1].strip()
            return ([t.strip().strip('"').strip("'")
                     for t in inner.split(",")] if inner else [])
    return [s]


def _coerce(v: str) -> Any:
    """Schema.fillFromParms analog: h2o-py sends everything as strings."""
    if not isinstance(v, str):
        return v
    s = v.strip()
    if s.lower() in ("true", "false"):
        return s.lower() == "true"
    if s.lower() in ("null", "none", ""):
        return None
    if s.startswith("[") or s.startswith("{"):
        try:
            return json.loads(s)
        except json.JSONDecodeError:
            pass
    try:
        return int(s)
    except ValueError:
        pass
    try:
        return float(s)
    except ValueError:
        pass
    return s


def _coerce_typed(name: str, v: Any, defaults: dict) -> Any:
    """Schema-typed parse (water/api/Schema.java fillFromParms semantics):
    the declared field type — here the builder's default-value type from
    the same registry `/3/ModelBuilders/{algo}` metadata and the bindings
    codegen consume — drives parsing, so a string-typed parameter is
    NEVER int/bool-mangled by guessing. Falls back to the untyped
    ``_coerce`` only for parameters the builder doesn't declare."""
    if not isinstance(v, str):
        return v
    d = defaults.get(name)
    if name not in defaults or d is None:
        return _coerce(v)
    s = v.strip()
    if isinstance(d, str):
        # declared string: pass through verbatim (an enum value like
        # "none" or a column named "123" must survive)
        return s
    if s.lower() in ("null", "none", ""):
        return None
    if isinstance(d, bool):
        return s.lower() == "true" if s.lower() in ("true", "false") \
            else _coerce(s)
    if isinstance(d, int):
        try:
            f = float(s)
            return int(f) if f == int(f) else f
        except ValueError:
            return _coerce(s)
    if isinstance(d, float):
        try:
            return float(s)
        except ValueError:
            return _coerce(s)
    if isinstance(d, (list, tuple)):
        got = _coerce(s)
        return list(got) if isinstance(got, (list, tuple)) else \
            _bracket_list(s)
    return _coerce(s)


# ---------------- handlers --------------------------------------------

@route("GET", "/")
@route("GET", "/flow/index.html")
def _flow_ui(params, body):
    """The built-in web UI (h2o-web Flow analog — api/flow.py): one
    self-contained page over the same REST surface the clients use."""
    from h2o3_tpu.api.flow import FLOW_HTML
    return {"__raw": FLOW_HTML.encode(),
            "__content_type": "text/html; charset=utf-8"}


@route("GET", "/3/Cloud")
@route("HEAD", "/3/Cloud")
def _cloud(params, body):
    return schemas.cloud_v3()


@route("GET", "/3/About")
def _about(params, body):
    return {"entries": [{"name": "Build project version",
                         "value": "3.46.0.tpu"}]}


@route("POST", "/4/sessions")
def _new_session(params, body):
    sid = "_sid_" + uuid.uuid4().hex[:10]
    dkv.put(sid, "session", {"frames": []})
    return {"session_key": sid, "name": sid}


@route("DELETE", "/4/sessions/{sid}")
def _end_session(params, body, sid):
    dkv.remove(sid)
    return {"session_key": sid}


@route("POST", "/3/ImportFiles")
def _import_files(params, body):
    path = params.get("path")
    if not path or not os.path.exists(path):
        raise ApiError(404, f"path not found: {path}")
    key = "nfs://" + path.lstrip("/")
    dkv.put(key, "rawfile", path)
    return {"__meta": {"schema_version": 3, "schema_name": "ImportFilesV3"},
            "path": path, "files": [path], "destination_frames": [key],
            "fails": [], "dels": []}


def _bracket_list(v) -> List[str]:
    """h2o-py stringifies list params as '[a,b,c]' WITHOUT quotes
    (connection.py helpers) — json.loads can't touch them."""
    if isinstance(v, list):
        return [str(x) for x in v]
    s = str(v or "").strip()
    if s.startswith("[") and s.endswith("]"):
        s = s[1:-1]
    return [p.strip().strip('"') for p in s.split(",") if p.strip()]


@route("POST", "/3/ImportFilesMulti")
def _import_files_multi(params, body):
    paths = _bracket_list(params.get("paths"))
    dests, fails = [], []
    for path in paths:
        if not os.path.exists(path):
            fails.append(path)
            continue
        key = "nfs://" + path.lstrip("/")
        dkv.put(key, "rawfile", path)
        dests.append(key)
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ImportFilesMultiV3"},
            "paths": paths, "files": [p for p in paths if os.path.exists(p)],
            "destination_frames": dests, "fails": fails, "dels": []}


@route("POST", "/3/PostFile")
def _post_file(params, body):
    """h2o.upload_file: multipart body → temp file → raw key."""
    fname = params.get("filename", "upload.csv")
    data = body if isinstance(body, (bytes, bytearray)) else b""
    # strip a multipart envelope if present
    if data.startswith(b"--"):
        try:
            head, rest = data.split(b"\r\n\r\n", 1)
            boundary = data.split(b"\r\n", 1)[0]
            data = rest.rsplit(b"\r\n" + boundary, 1)[0]
        except ValueError:
            pass
    tmp = os.path.join(tempfile.gettempdir(),
                       f"h2o_upload_{uuid.uuid4().hex[:8]}_"
                       f"{os.path.basename(fname)}")
    with open(tmp, "wb") as f:
        f.write(data)
    key = "nfs://" + tmp.lstrip("/")
    dkv.put(key, "rawfile", tmp)
    return {"destination_frame": key, "total_bytes": len(data)}


def _raw_paths(source_frames) -> List[str]:
    if isinstance(source_frames, str):
        source_frames = [source_frames]
    paths = []
    for sf in source_frames:
        name = sf["name"] if isinstance(sf, dict) else sf
        ent = dkv.get_opt(name)
        if ent and ent[0] == "rawfile":
            paths.append(ent[1])
        elif os.path.exists(str(name)):
            paths.append(str(name))
        else:
            raise ApiError(404, f"source frame not found: {name}")
    return paths


@route("POST", "/3/ParseSetup")
def _parse_setup(params, body):
    from h2o3_tpu.ingest.parse import parse_setup
    src = _coerce(params.get("source_frames", "[]"))
    paths = _raw_paths(src)
    sep = params.get("separator")
    if sep and str(sep).isdigit():
        sep = chr(int(sep))
    setup = parse_setup(paths[0], separator=sep)
    dest = os.path.basename(paths[0]).replace(".csv", "") + ".hex"
    return {
        "__meta": {"schema_version": 3, "schema_name": "ParseSetupV3"},
        "source_frames": [schemas.keyref(p if isinstance(p, str) else p["name"])
                          for p in (src if isinstance(src, list) else [src])],
        "parse_type": "CSV",
        "separator": ord(setup.separator),
        "single_quotes": False,
        "check_header": 1 if setup.header else -1,
        "number_columns": len(setup.column_names),
        "column_names": list(setup.column_names),
        "column_types": [t.capitalize() for t in setup.column_types],
        "na_strings": None,
        "destination_frame": dest,
        "chunk_size": 4194304,
        "total_filtered_column_count": len(setup.column_names),
    }


@route("POST", "/3/Parse")
def _parse(params, body):
    from h2o3_tpu.ingest.parse import parse, parse_setup
    src = _coerce(params.get("source_frames", "[]"))
    paths = _raw_paths(src)
    dest = params.get("destination_frame") or (
        os.path.basename(paths[0]) + ".hex")
    col_names = _coerce(params.get("column_names")) or None
    col_types = _coerce(params.get("column_types")) or None
    if col_types:
        col_types = [str(t).lower() for t in col_types]
    sep = params.get("separator")
    if sep and str(sep).isdigit():
        sep = chr(int(sep))
    chk = params.get("check_header")
    header = None if chk in (None, "0") else (str(chk) == "1")

    job = Job(f"Parse {paths[0]}", key=None)
    # write-lock the destination against double-parses
    # (water/Lockable.java:25 "Parser should write-lock the output Frame")
    dkv.write_lock(dest, job.key)

    def body_fn(j):
        try:
            setup = parse_setup(paths, separator=sep, header=header,
                                column_names=col_names,
                                column_types=col_types)
            fr = parse(paths, setup, key=dest)
            dkv.put(dest, "frame", fr)
            return fr
        finally:
            dkv.unlock_all(j.key)

    job.run(body_fn, background=True)
    return {"__meta": {"schema_version": 3, "schema_name": "ParseV3"},
            "job": schemas.job_v3(job, dest, "Key<Frame>"),
            "destination_frame": schemas.keyref(dest, "Key<Frame>")}


@route("GET", "/3/Jobs/{key}")
def _get_job(params, body, key):
    job = get_job(key)
    if job is None:
        raise ApiError(404, f"job not found: {key}")
    dest = getattr(job, "dest_key", None)
    return {"__meta": {"schema_version": 3, "schema_name": "JobsV3"},
            "jobs": [schemas.job_v3(job, dest)]}


@route("POST", "/3/Jobs/{key}/cancel")
def _cancel_job(params, body, key):
    job = get_job(key)
    if job is None:
        raise ApiError(404, f"job not found: {key}")
    job.cancel()
    return {"jobs": [schemas.job_v3(job, getattr(job, "dest_key", None))]}


@route("GET", "/3/Frames/{key}")
def _get_frame(params, body, key):
    fr = dkv.get(key, "frame")
    rc = int(params.get("row_count", 10) or 10)
    cc = int(params.get("column_count", -1) or -1)
    ro = int(params.get("row_offset", 0) or 0)
    co = int(params.get("column_offset", 0) or 0)
    return schemas.frames_v3([schemas.frame_v3(fr, key, rc, cc, ro, co)])


@route("GET", "/3/Frames/{key}/summary")
def _frame_summary(params, body, key):
    fr = dkv.get(key, "frame")
    return schemas.frames_v3([schemas.frame_v3(fr, key, 0)])


@route("GET", "/3/Frames")
def _list_frames(params, body):
    return schemas.frames_v3(
        [schemas.frame_v3(dkv.get(k, "frame"), k, 0)
         for k in dkv.keys("frame")])


@route("DELETE", "/3/Frames/{key}")
def _del_frame(params, body, key):
    dkv.check_unlocked(key)    # refuse deleting a job's in-use frame
    dkv.remove(key)
    return {}


@route("DELETE", "/3/DKV/{key}")
def _del_key(params, body, key):
    dkv.check_unlocked(key)
    dkv.remove(key)
    return {}


@route("DELETE", "/3/DKV")
def _del_keys(params, body):
    retained = set(_coerce(params.get("retained_keys", "[]")) or [])
    for k in list(dkv.keys()):
        if k not in retained:
            try:
                dkv.check_unlocked(k)
            except dkv.KeyLockedError:
                continue       # bulk clear skips in-use keys
            dkv.remove(k)
    return {}


@route("GET", "/3/Models")
def _list_models(params, body):
    return schemas.models_v3(
        [schemas.model_v3(dkv.get(k, "model"), k)
         for k in dkv.keys("model")])


@route("GET", "/3/Models/{key}")
def _get_model(params, body, key):
    m = dkv.get(key, "model")
    return schemas.models_v3([schemas.model_v3(m, key)])


@route("DELETE", "/3/Models/{key}")
def _del_model(params, body, key):
    dkv.check_unlocked(key)
    dkv.remove(key)
    return {}


@route("POST", "/3/ModelBuilders/{algo}")
def _train(params, body, algo):
    builders = _builders()
    if algo not in builders:
        raise ApiError(404, f"unknown algorithm '{algo}'; have "
                            f"{sorted(builders)}")
    # key-like and name-like params stay raw strings — _coerce would turn
    # model_id="123" into an int DKV key and response_column="none" to None
    raw_keep = {k: params[k] for k in ("model_id", "training_frame",
                                       "validation_frame",
                                       "response_column", "fold_column",
                                       "weights_column", "offset_column",
                                       "regex", "path")
                if k in params}
    defaults = builders[algo]().params
    parms = {k: _coerce_typed(k, v, defaults) for k, v in params.items()}
    parms.update(raw_keep)
    train_key = parms.pop("training_frame", None)
    if isinstance(train_key, dict):
        train_key = train_key.get("name")
    if not train_key:
        # Generic imports an artifact — the only builder with no frame
        if algo != "generic":
            raise ApiError(400, "training_frame is required")
        frame = None
    else:
        frame = dkv.get(str(train_key), "frame")
    valid = None
    vk = parms.pop("validation_frame", None)
    if vk:
        valid = dkv.get(str(vk if not isinstance(vk, dict) else vk["name"]),
                        "frame")
    y = parms.pop("response_column", None)
    ignored = parms.pop("ignored_columns", None)
    model_id = parms.pop("model_id", None) or dkv.unique_key(f"{algo}_model")
    parms = {k: v for k, v in parms.items() if v is not None}
    if ignored:
        parms["ignored_columns"] = ignored
    est = builders[algo](**parms)

    # cooperative locking (water/Lockable.java:25): inputs read-locked,
    # output model write-locked for the build's duration — a concurrent
    # DELETE of the training frame now fails instead of racing the job.
    # The owner is a synthetic key (the training job doesn't exist yet);
    # partial acquisition must release what it took.
    lock_owner = f"$train_{model_id}"
    try:
        if train_key:
            dkv.read_lock(str(train_key), lock_owner)
        if vk:
            dkv.read_lock(str(vk if not isinstance(vk, dict)
                              else vk["name"]), lock_owner)
        dkv.write_lock(model_id, lock_owner)
    except dkv.KeyLockedError:
        dkv.unlock_all(lock_owner)
        raise
    # the client polls the TRAINING job itself (no wrapper Job): the
    # scheduler's QUEUED state, queue_wait_s and preempt_count surface
    # on the key this response returns (ISSUE 15 — a wrapper job showed
    # RUNNING with msec growing through the whole queue wait). Builders
    # that override train() and swallow background= complete
    # synchronously; est.job exists either way.
    try:
        est.train(y=y, training_frame=frame, validation_frame=valid,
                  background=True)
    except BaseException:
        dkv.unlock_all(lock_owner)
        raise
    job = est.job
    job.dest_key = model_id

    def _register():
        try:
            model = job.join()    # raises RuntimeError on FAILED
            if model is None:
                return            # cancelled before any result
            model.key = model_id
            # frame-first metric lookups + FeatureInteraction default
            # frame resolve through this backref
            model.training_frame_key = str(train_key) if train_key \
                else None
            # fold models get DKV keys so the advertised
            # cross_validation_models keyrefs resolve (ModelSchemaV3)
            for i, fm in enumerate(
                    model.output.get("cross_validation_models") or []):
                fm.key = f"{model_id}_cv_{i + 1}"
                dkv.put(fm.key, "model", fm)
            dkv.put(model_id, "model", model)
        except RuntimeError:
            pass   # FAILED: the job carries the structured failure info
        finally:
            dkv.unlock_all(lock_owner)

    threading.Thread(target=_register, daemon=True,
                     name=f"train-register-{model_id}").start()
    return {
        "__meta": {"schema_version": 3,
                   "schema_name": "%sV3" % algo.upper()},
        "job": schemas.job_v3(job, model_id),
        "algo": algo,
        "messages": [],
        "error_count": 0,
        "parameters": [{"name": k, "actual_value": v}
                       for k, v in est.params.items()
                       if isinstance(v, (int, float, str, bool, list,
                                         type(None)))],
        "__http_status": 200,
    }


def _kind_of(m) -> str:
    return ("Binomial" if m.nclasses == 2 else
            "Multinomial" if m.nclasses > 2 else "Regression")


def _start_predict_job(model, frame, dest=None, options=None):
    """Scoring job honoring hex/Model.java scoring options: plain
    predictions, predict_contributions (TreeSHAP), leaf_node_assignment,
    predict_staged_proba (water/api/ModelMetricsHandler.java predict)."""
    m = dkv.get(model, "model")
    fr = dkv.get(frame, "frame")
    dest = dest or dkv.unique_key("prediction")
    job = Job(f"prediction {model} on {frame}")
    job.dest_key = dest
    job.dest_type = "Key<Frame>"
    # h2o-py serializes booleans via str() — route every option through
    # _coerce so "False" doesn't arrive truthy
    opts = {k: _coerce(v) for k, v in (options or {}).items()}

    def body_fn(j):
        if opts.get("predict_contributions"):
            of = str(opts.get("predict_contributions_output_format")
                     or "Original").lower()
            pred = m.predict_contributions(
                fr, output_format=of,
                top_n=int(opts.get("top_n") or 0),
                bottom_n=int(opts.get("bottom_n") or 0),
                compare_abs=bool(opts.get("compare_abs")))
        elif opts.get("leaf_node_assignment"):
            pred = m.predict_leaf_node_assignment(
                fr, type=str(opts.get("leaf_node_assignment_type")
                             or "Path"))
        elif opts.get("predict_staged_proba"):
            pred = m.staged_predict_proba(fr)
        else:
            pred = m.predict(fr)
        dkv.put(dest, "frame", pred)
        return pred

    job.run(body_fn, background=True)
    return m, fr, dest, job


@route("POST", "/4/Predictions/models/{model}/frames/{frame}")
def _predict_async(params, body, model, frame):
    """Async bulk scoring: the reference returns a BARE JobV3
    (water/api/RegisterV3Api.java:363 → ModelMetricsHandler.predictAsync
    :467); h2o-py wraps it in H2OJob, polls, then fetches the dest frame.
    Returning a ModelMetricsListSchemaV3 here instead breaks the client:
    H2OResponse dispatches any schema starting with 'ModelMetrics' to a
    metrics object and H2OJob.__init__ chokes on it."""
    m, fr, dest, job = _start_predict_job(
        model, frame, params.get("predictions_frame"), options=params)
    return schemas.job_v3(job, dest, "Key<Frame>")


@route("POST", "/3/Predictions/models/{model}/frames/{frame}")
def _predict(params, body, model, frame):
    """Sync scoring + metrics (hex/Model.java:1919 score → BigScore)."""
    m, fr, dest, job = _start_predict_job(
        model, frame, params.get("predictions_frame"), options=params)
    job.join()
    perf = None
    try:
        mm = m.model_performance(fr)
        perf = schemas._metrics_v3(mm, _kind_of(m),
                                   domain=list(m.response_domain or []) or None,
                                   frame_key=frame, model_key=model)
    except Exception:
        perf = None
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelMetricsListSchemaV3"},
            "model_metrics": [perf] if perf else [],
            "job": schemas.job_v3(job, dest, "Key<Frame>"),
            "predictions_frame": schemas.keyref(dest, "Key<Frame>")}


# ---------------- serving subsystem (h2o3_tpu.serve) -------------------
# No reference analog: h2o-3's only online path is frame-batch predict.
# deploy warms per-bucket compiled predict executables; rows score
# through the micro-batching queue (ISSUE 3).


def _lane_of(params, default: str = "interactive") -> str:
    """The request's deadline class (ISSUE 20): explicit ``X-H2O3-Lane``
    header (injected as ``_lane`` by the dispatcher) > ``lane``
    body/query param > the endpoint's path default. Unknown lane names
    are a 400 — a typo must not silently ride the highest class."""
    from h2o3_tpu.serve import lanes as lanes_mod
    lane = params.get("_lane") or params.get("lane")
    try:
        return lanes_mod.normalize(str(lane)) if lane else default
    except ValueError as e:
        raise ApiError(400, str(e))


def _fleet_epoch_headers() -> Optional[Dict[str, str]]:
    """``X-H2O3-Fleet-Epoch`` on scoring responses: the membership
    epoch this replica last heard — the affinity client's staleness
    signal (a mismatch with its pinned ring triggers a refresh).
    None outside a fleet: solo deployments add no header."""
    from h2o3_tpu.serve import fleet as serve_fleet
    ep = serve_fleet.fleet_epoch()
    return {"X-H2O3-Fleet-Epoch": str(ep)} if ep is not None else None


def _ndjson(rows) -> bytes:
    """Streamed scoring body: one JSON object per line (NDJSON). The
    shape is the per-row dict of the ``rows`` format — a streamed and
    a batched response decode to bit-identical values."""
    return ("\n".join(json.dumps(r, default=_json_default)
                      for r in rows) + "\n").encode()


def _serve_config_from_params(params) -> Dict[str, Any]:
    cfg: Dict[str, Any] = {}
    for k, cast in (("max_batch", int), ("max_delay_ms", float),
                    ("queue_limit", int), ("timeout_ms", float),
                    ("circuit_failures", int), ("circuit_open_ms", float)):
        v = _coerce(params.get(k)) if params.get(k) is not None else None
        if v is not None:
            cfg[k] = cast(v)
    b = _coerce(params.get("buckets")) if params.get("buckets") else None
    if b:
        cfg["buckets"] = [int(x) for x in
                          (b if isinstance(b, list) else _bracket_list(b))]
    return cfg


@route("POST", "/3/Serve/models/{model}")
def _serve_deploy(params, body, model):
    """Deploy a model for low-latency row serving: pre-encode the
    column/domain spec, warm compiled predict executables at the batch
    buckets, start the micro-batcher. Knobs: max_batch, max_delay_ms,
    queue_limit, timeout_ms, buckets."""
    from h2o3_tpu import serve
    try:
        dep = serve.deploy(model, **_serve_config_from_params(params))
    except KeyError as e:
        raise ApiError(404, str(e))
    except ValueError as e:
        raise ApiError(400, str(e))
    return schemas.serve_deployment_v3(dep)


@route("DELETE", "/3/Serve/models/{model}")
def _serve_undeploy(params, body, model):
    from h2o3_tpu import serve
    if not serve.undeploy(model):
        raise ApiError(404, f"model '{model}' is not deployed")
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ServeDeploymentV3"},
            "model_id": schemas.keyref(model, "Key<Model>"),
            "undeployed": True}


@route("GET", "/3/Serve/models")
def _serve_list(params, body):
    from h2o3_tpu import serve
    return {"__meta": {"schema_version": 3, "schema_name": "ServeModelsV3"},
            "deployments": [schemas.serve_deployment_v3(d)
                            for d in serve.deployments()]}


@route("GET", "/3/Serve/models/{model}")
def _serve_get(params, body, model):
    from h2o3_tpu import serve
    dep = serve.deployment(model)
    if dep is None:
        raise ApiError(404, f"model '{model}' is not deployed")
    return schemas.serve_deployment_v3(dep)


@route("GET", "/3/Serve/stats")
def _serve_stats(params, body):
    from h2o3_tpu import serve
    return schemas.serve_stats_v3(serve.stats())


# ---------------- fleet front door (h2o3_tpu.fleet) --------------------
# Membership + routing: replicas join/heartbeat/leave against THIS
# process's member table (the SURVEY §L1 heartbeat-cloud shape over
# REST), and /3/Fleet/models/{m}/rows proxies a scoring request to the
# consistent-hash home replica with single failover (ISSUE 13).


def _fleet_body(params, body) -> Dict[str, Any]:
    """Fleet control-plane payloads arrive as JSON bodies (the agent's
    spelling) or form/query params (curl-friendly)."""
    out: Dict[str, Any] = {}
    if body:
        try:
            out.update(json.loads(body.decode()))
        except (json.JSONDecodeError, UnicodeDecodeError):
            pass
    for k, v in params.items():
        out.setdefault(k, _coerce(v) if isinstance(v, str) else v)
    return out


@route("GET", "/3/Fleet")
def _fleet_view(params, body):
    """Membership view: epoch, members with per-member phi suspicion /
    load / deployments, recent departures."""
    from h2o3_tpu import fleet
    return {"__meta": {"schema_version": 3, "schema_name": "FleetV3"},
            **fleet.router().table.view()}


@route("POST", "/3/Fleet/join")
def _fleet_join(params, body):
    """Admit (or re-admit) a replica. Response carries the incarnation
    token fencing its heartbeats, the current epoch, and the registry
    snapshot the replica pre-warms from before marking routable."""
    from h2o3_tpu import fleet, serve
    b = _fleet_body(params, body)
    member_id = b.get("member_id")
    base_url = b.get("base_url")
    if not member_id or not base_url:
        raise ApiError(400, "join requires member_id and base_url")
    hb_ms = b.get("heartbeat_ms")
    m = fleet.router().table.join(
        str(member_id), str(base_url),
        heartbeat_s=(float(hb_ms) / 1000.0 if hb_ms else None),
        deployments=tuple(b.get("deployments") or ()),
        routable=bool(b.get("routable", False)))
    # elastic membership (ISSUE 18): a replica joining mid-grid absorbs
    # queued children — throttled, off-thread, never fails the join
    from h2o3_tpu.fleet import sched as fleet_sched
    fleet_sched.maybe_rebalance("join")
    return {"__meta": {"schema_version": 3, "schema_name": "FleetJoinV3"},
            "member_id": m.member_id, "incarnation": m.incarnation,
            "epoch": fleet.router().table.epoch,
            "heartbeat_ms": m.heartbeat_s * 1000.0,
            "registry": serve.registry_snapshot()}


@route("POST", "/3/Fleet/heartbeat")
def _fleet_heartbeat(params, body):
    """One member beat. 404 = unknown member (join first), 409 = stale
    incarnation (a dead epoch cannot resurrect a member — rejoin).
    The response piggybacks every OTHER member's circuit states — the
    push-gossip channel that replaced the telemetry-scrape pull."""
    from h2o3_tpu import fleet
    b = _fleet_body(params, body)
    member_id = str(b.get("member_id") or "")
    table = fleet.router().table
    try:
        table.heartbeat(
            member_id, int(b.get("incarnation") or 0),
            load=float(b.get("load") or 0.0),
            deployments=tuple(b["deployments"])
            if b.get("deployments") is not None else None,
            circuit=b.get("circuit"),
            routable=b.get("routable"),
            sched=b.get("sched") if isinstance(b.get("sched"), dict)
            else None,
            wall=float(b["wall"]) if b.get("wall") is not None else None)
    except fleet.UnknownMemberError as e:
        raise ApiError(404, f"{e} — POST /3/Fleet/join")
    except fleet.StaleEpochError as e:
        raise ApiError(409, str(e))
    gossip = []
    for m in table.members():
        if m.member_id == member_id:
            continue
        for st in m.circuit:
            gossip.append({**st, "source": m.member_id})
    # the fleet-scheduler placement view rides every beat response —
    # each replica learns every peer's headroom at heartbeat latency
    from h2o3_tpu.fleet import sched as fleet_sched
    return {"__meta": {"schema_version": 3,
                       "schema_name": "FleetHeartbeatV3"},
            "ok": True, "epoch": table.epoch, "gossip": gossip,
            "fleet_sched": fleet_sched.fleet_view_from_table(table)}


@route("POST", "/3/Fleet/leave")
def _fleet_leave(params, body):
    from h2o3_tpu import fleet
    b = _fleet_body(params, body)
    left = fleet.router().table.leave(str(b.get("member_id") or ""))
    return {"__meta": {"schema_version": 3, "schema_name": "FleetLeaveV3"},
            "left": bool(left), "epoch": fleet.router().table.epoch}


@route("GET", "/3/Fleet/registry")
def _fleet_registry(params, body):
    """The warm cold-start snapshot: every deployment's model key +
    deploy config (also piggybacked on the join response)."""
    from h2o3_tpu import serve
    return {"__meta": {"schema_version": 3,
                       "schema_name": "FleetRegistryV3"},
            **serve.registry_snapshot()}


@route("POST", "/3/Fleet/models/{model}/rows")
def _fleet_predict(params, body, model):
    """Routed scoring: consistent-hash home-replica dispatch with
    least-loaded fallback and single failover; 503 + Retry-After when
    the live set cannot absorb the request. ``key`` pins the routing
    key (default: the model — all of one model's traffic shares a
    home until it falls back). ``format`` (rows | columnar | stream)
    and ``lane`` (interactive | bulk | background) ride the SAME
    failover path — before ISSUE 20 only the row shape failed over."""
    from h2o3_tpu import fleet
    b = _fleet_body(params, body)
    rows = b.get("rows")
    if not isinstance(rows, list) or not all(
            isinstance(r, dict) for r in rows):
        raise ApiError(400, 'expected {"rows": [{column: value, ...}]}')
    tmo = b.get("timeout_ms")
    fmt = str(b.get("format") or "rows").lower()
    if fmt not in ("rows", "columnar", "stream"):
        raise ApiError(400, f"unknown format '{fmt}' — use 'rows', "
                       f"'columnar' or 'stream'")
    lane = _lane_of(b)
    try:
        out = fleet.router().predict_rows(
            model, rows,
            key=str(b["key"]) if b.get("key") is not None else None,
            timeout_ms=float(tmo) if tmo is not None else None,
            fmt=fmt, lane=lane)
    except fleet.FleetUnavailableError as e:
        import math
        raise ApiError(503, str(e), headers={
            "Retry-After": str(max(int(math.ceil(e.retry_after_s)), 1))})
    except fleet.RouterError as e:
        raise ApiError(getattr(e, "http_status", 500), str(e))
    epoch_headers = {"X-H2O3-Fleet-Epoch": str(fleet.router().table.epoch)}
    if "__raw" in out:
        # streamed scoring passes through opaque — routed and direct
        # NDJSON stay byte-identical
        raw = out["__raw"]
        return {"__raw": raw.encode() if isinstance(raw, str) else raw,
                "__content_type": out.get("__content_type",
                                          "application/x-ndjson"),
                "__headers": epoch_headers}
    out.setdefault("__meta", {"schema_version": 3,
                              "schema_name": "FleetPredictionsV3"})
    out["__headers"] = epoch_headers
    return out


@route("GET", "/3/Fleet/ring")
def _fleet_ring(params, body):
    """The consistent-hash ring view (ISSUE 20): live routable members
    + virtual-point count + epoch. Clients hash keys with the SAME
    blake2b scheme and dispatch straight to the home replica — the
    zero-hop path — refreshing when a scoring response's
    ``X-H2O3-Fleet-Epoch`` disagrees with the epoch pinned here."""
    from h2o3_tpu import fleet
    return {"__meta": {"schema_version": 3, "schema_name": "FleetRingV3"},
            **fleet.router().ring_snapshot()}


@route("GET", "/3/Fleet/snapshot")
def _fleet_snapshot(params, body):
    """Warm-boot source for a (re)starting peer router (ISSUE 20): the
    full member-table snapshot (incarnations included) plus the
    deployment registry — everything a bounced router needs to answer
    its first routed request without waiting for replica beats."""
    from h2o3_tpu import fleet, serve
    return {"__meta": {"schema_version": 3,
                       "schema_name": "FleetSnapshotV3"},
            "epoch": fleet.router().table.epoch,
            "snapshot": fleet.router().table.snapshot(),
            "registry": serve.registry_snapshot()}


@route("POST", "/3/Fleet/gossip")
def _fleet_gossip(params, body):
    """Router-tier anti-entropy (ISSUE 20): absorb a peer router's
    table snapshot (epoch-fenced, incarnation-fenced — membership.py
    rules verbatim) and answer with ours, so one exchange converges
    both sides. The sender's url is adopted as a peer (elastic tier
    membership)."""
    from h2o3_tpu import fleet
    b = _fleet_body(params, body)
    snap = b.get("snapshot")
    if not isinstance(snap, dict):
        raise ApiError(400, 'expected {"snapshot": {...}, "source": url}')
    r = fleet.router()
    absorbed = r.table.absorb(snap, source=str(b.get("source") or "?"))
    if r.tier is not None and b.get("source"):
        r.tier.note_peer(str(b["source"]))
    return {"__meta": {"schema_version": 3,
                       "schema_name": "FleetGossipV3"},
            "absorbed": absorbed, "epoch": r.table.epoch,
            "snapshot": r.table.snapshot()}


@route("POST", "/3/FleetSched/submit")
def _fleet_sched_submit(params, body):
    """Fleet scheduler hand-off target (ISSUE 18): accept a training
    submission placed here by another replica — fresh placement, a
    preempt-migrated checkpoint resume, or an evict-requeue — and run
    it through THIS process's scheduler under the original priority
    class, share group and trace id."""
    from h2o3_tpu import sched
    from h2o3_tpu.fleet import sched as fleet_sched
    if not sched.enabled():
        raise ApiError(503, "this replica's training scheduler is "
                            "disabled (H2O3_SCHED=0)")
    b = _fleet_body(params, body)
    try:
        out = fleet_sched.handle_remote_submit(b)
    except sched.SchedulerSaturatedError as e:
        raise ApiError(503, str(e))
    except ValueError as e:
        raise ApiError(400, str(e))
    out["__meta"] = {"schema_version": 3,
                     "schema_name": "FleetSchedSubmitV3"}
    return out


# ---------------- fault injection admin (h2o3_tpu.faults) --------------
# Chaos tooling surface: inspect/set/clear the deterministic fault spec
# (same grammar as the H2O3_FAULTS env var). No reference analog.


@route("GET", "/3/Faults")
def _faults_get(params, body):
    from h2o3_tpu import faults
    return {"__meta": {"schema_version": 3, "schema_name": "FaultsV3"},
            "spec": faults.spec(), "rules": faults.describe(),
            "fired_total": faults.fired_total()}


@route("POST", "/3/Faults")
def _faults_set(params, body):
    from h2o3_tpu import faults
    spec = params.get("spec")
    if spec is None and body:
        try:
            spec = json.loads(body.decode()).get("spec")
        except (json.JSONDecodeError, UnicodeDecodeError):
            spec = body.decode(errors="replace").strip() or None
    if not spec:
        # a typo'd body must not silently DISARM a live chaos run —
        # clearing is DELETE's job, setting requires a spec
        raise ApiError(400, "POST /3/Faults requires spec=<grammar> "
                            "(use DELETE /3/Faults to clear)")
    try:
        faults.configure(spec)
    except ValueError as e:
        raise ApiError(400, f"bad fault spec: {e}")
    return {"__meta": {"schema_version": 3, "schema_name": "FaultsV3"},
            "spec": faults.spec(), "rules": faults.describe(),
            "fired_total": faults.fired_total()}


@route("DELETE", "/3/Faults")
def _faults_clear(params, body):
    from h2o3_tpu import faults
    faults.configure(None)
    return {"__meta": {"schema_version": 3, "schema_name": "FaultsV3"},
            "spec": None, "rules": [], "fired_total": 0}


# ---------------- training scheduler (h2o3_tpu.sched, ISSUE 15) ---------


@route("GET", "/3/Scheduler")
def _scheduler_get(params, body):
    """Training-scheduler state: queue contents per priority class with
    wait reasons, running entries with their admission estimates, the
    reserved-bytes ledger vs the memman budget, and the sched counters.
    ``?scope=cluster`` merges every replica's snapshot through the
    telemetry peer plane (dead peers flagged, never fatal)."""
    from h2o3_tpu import sched
    if str(params.get("scope") or "").lower() == "cluster":
        from h2o3_tpu.fleet import sched as fleet_sched
        snap = fleet_sched.cluster_scheduler_snapshot()
        snap["__meta"] = {"schema_version": 3,
                          "schema_name": "SchedulerClusterV3"}
        snap["enabled"] = sched.enabled()
        return snap
    snap = sched.scheduler().snapshot()
    snap["__meta"] = {"schema_version": 3, "schema_name": "SchedulerV3"}
    snap["enabled"] = sched.enabled()
    return snap


@route("POST", "/3/Scheduler")
def _scheduler_control(params, body):
    """Control: ``pause=true|false`` stops/starts dispatch (running
    entries finish; the queue holds), ``job=<key>&priority=<class>``
    moves a QUEUED entry to another priority class."""
    from h2o3_tpu import sched
    s = sched.scheduler()
    # validate EVERYTHING before applying ANYTHING: a request that is
    # half-bad must not half-execute (e.g. pause applied, then the
    # reprioritize half 400s — the client sees an error yet dispatch
    # is now paused)
    pause = params.get("pause")
    pause_action = None
    if pause is not None:
        val = str(pause).lower()
        if val in ("1", "true", "yes"):
            pause_action = True
        elif val in ("0", "false", "no"):
            pause_action = False
        else:
            # a typo'd value must not silently RESUME a paused queue
            raise ApiError(400, f"pause={pause!r} is not a boolean "
                                f"(true/false)")
    job_key = params.get("job")
    priority = params.get("priority")
    if (job_key or priority) and not (job_key and priority):
        raise ApiError(400, "reprioritizing needs BOTH job=<key> and "
                            "priority=<class>")
    if priority:
        priority = str(priority).lower()
        if priority not in sched.PRIORITY_LEVELS:
            raise ApiError(400, f"unknown priority '{priority}' (one of "
                                f"{sorted(sched.PRIORITY_LEVELS)})")
    if pause_action is None and not job_key:
        raise ApiError(400, "POST /3/Scheduler needs pause=true|false "
                            "and/or job=<key>&priority=<class>")
    actions = []
    # apply the fallible half FIRST: reprioritize can 404 (the job may
    # have dispatched since the client looked), and a combined request
    # that errors must not have half-executed by flipping pause state
    if job_key:
        if not s.reprioritize(str(job_key), priority):
            raise ApiError(404, f"no QUEUED scheduler entry for job "
                                f"'{job_key}'")
        actions.append(f"reprioritized {job_key} -> {priority}")
    if pause_action is True:
        s.pause()
        actions.append("paused")
    elif pause_action is False:
        s.resume()
        actions.append("resumed")
    snap = s.snapshot()
    snap["__meta"] = {"schema_version": 3, "schema_name": "SchedulerV3"}
    snap["actions"] = actions
    return snap


# ---------------- restart recovery (h2o3_tpu.recovery) ------------------


@route("GET", "/3/Recovery")
def _recovery_get(params, body):
    """Restart-recovery state: the durable dir, pending manifests (with
    their newest resumable checkpoint), and the last boot scan's report
    — what an operator checks after a pod restart to see which trains
    came back."""
    from h2o3_tpu import recovery
    manifests = []
    if recovery.enabled():
        # read-only scan: a monitoring poll must not quarantine corrupt
        # manifests aside before the next BOOT's scan reports them
        entries, corrupt = recovery.scan(quarantine=False)
        manifests = entries
    else:
        corrupt = []
    return {"__meta": {"schema_version": 3, "schema_name": "RecoveryV3"},
            "enabled": recovery.enabled(),
            "dir": recovery.recovery_dir(),
            "manifests": manifests,
            "corrupt": corrupt,
            "last_boot": recovery.last_report()}


@route("POST", "/3/Predictions/models/{model}/rows")
def _predict_rows(params, body, model):
    """Row-level scoring through the micro-batcher: JSON rows in
    ({"rows": [{col: value, ...}, ...]} or a bare list), predictions +
    per-class probabilities out. ``?format=columnar`` returns COLUMN
    arrays ({"columns": {"predict": [...], "p<label>": [...]}}) from
    the batch's one vectorized decode — bit-identical values to the
    per-row dict shape at a fraction of the decode cost for large
    batches. Admission control maps to HTTP: queue-full /
    deadline-expired → 503 (retryable), not-deployed → 404 with deploy
    guidance."""
    from h2o3_tpu import serve
    rows = params.get("rows")
    if rows is None and body:
        try:
            rows = json.loads(body.decode())
        except (json.JSONDecodeError, UnicodeDecodeError) as e:
            raise ApiError(400, f"request body is not JSON rows: {e}")
    if isinstance(rows, str):
        rows = _coerce(rows)
    if isinstance(rows, dict):
        rows = rows.get("rows")
    if not isinstance(rows, list) or not all(
            isinstance(r, dict) for r in rows):
        raise ApiError(400, 'expected {"rows": [{column: value, ...}]}')
    tmo = _coerce(params.get("timeout_ms")) \
        if params.get("timeout_ms") is not None else None
    fmt = (params.get("format") or "rows").lower()
    if fmt not in ("rows", "columnar", "stream"):
        raise ApiError(400, f"unknown format '{fmt}' — use 'rows', "
                       f"'columnar' or 'stream'")
    lane = _lane_of(params)
    epoch_headers = _fleet_epoch_headers()
    try:
        # explicit timeout_ms=0 means fail-fast, NOT the default
        if fmt == "columnar":
            cols = serve.predict_columnar(
                model, rows,
                timeout_ms=float(tmo) if tmo is not None else None,
                lane=lane)
            out = {"__meta": {"schema_version": 3,
                              "schema_name": "ServePredictionsColumnarV3"},
                   "model_id": schemas.keyref(model, "Key<Model>"),
                   "nrow": len(rows),
                   "columns": cols}
            if epoch_headers:
                out["__headers"] = epoch_headers
            return out
        preds = serve.predict_rows(
            model, rows, timeout_ms=float(tmo) if tmo is not None else None,
            lane=lane)
    except KeyError as e:
        raise ApiError(404, str(e))
    except serve.ServeError as e:
        headers = {}
        ra = getattr(e, "retry_after_s", None)
        if ra is not None:
            # circuit-open fast 503s tell clients WHEN to come back
            import math
            headers["Retry-After"] = str(max(int(math.ceil(ra)), 1))
        raise ApiError(getattr(e, "http_status", 500), str(e),
                       headers=headers)
    if fmt == "stream":
        # streamed scoring (NDJSON): same values, one row-dict per
        # line — and the same admission/failover semantics as 'rows'
        # because it IS the rows path up to serialization
        out = {"__raw": _ndjson(preds),
               "__content_type": "application/x-ndjson"}
        if epoch_headers:
            out["__headers"] = epoch_headers
        return out
    out = {"__meta": {"schema_version": 3,
                      "schema_name": "ServePredictionsV3"},
           "model_id": schemas.keyref(model, "Key<Model>"),
           "predictions": preds}
    if epoch_headers:
        out["__headers"] = epoch_headers
    return out


@route("POST", "/3/ModelMetrics/models/{model}/frames/{frame}")
def _model_metrics_score(params, body, model, frame):
    """ModelMetricsHandler.score (water/api/ModelMetricsHandler.java:288):
    score the frame with the model, return fresh metrics (h2o-py
    model_performance)."""
    m = dkv.get(model, "model")
    fr = dkv.get(frame, "frame")
    mm = m.model_performance(fr)
    perf = schemas._metrics_v3(mm, _kind_of(m),
                               domain=list(m.response_domain or []) or None,
                               frame_key=frame, model_key=model)
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelMetricsListSchemaV3"},
            "model_metrics": [perf] if perf else []}


@route("GET", "/3/ModelMetrics/models/{model}")
def _model_metrics_list(params, body, model):
    m = dkv.get(model, "model")
    out = []
    for mm in (m.training_metrics, m.validation_metrics,
               m.cross_validation_metrics):
        if mm is not None:
            out.append(schemas._metrics_v3(
                mm, _kind_of(m),
                domain=list(m.response_domain or []) or None,
                model_key=model))
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelMetricsListSchemaV3"},
            "model_metrics": out}


@route("GET", "/99/Models.bin/{model}")
def _save_model_bin(params, body, model):
    """h2o.save_model → GET /99/Models.bin/{id}?dir=...&force=...
    (water/api/ModelsHandler importModel/exportModel pair)."""
    from h2o3_tpu.persist import save_model
    m = dkv.get(model, "model")
    path = params.get("dir") or model
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    if os.path.exists(path) and str(params.get("force", "")
                                    ).lower() != "true":
        raise ApiError(409, f"{path} exists; use force=True")
    out = save_model(m, path=os.path.dirname(path) or ".",
                     force=True, filename=os.path.basename(path))
    return {"__meta": {"schema_version": 3, "schema_name": "ModelExportV3"},
            "dir": out}


@route("POST", "/99/Models.bin/{model}")
@route("POST", "/99/Models.bin/")
def _load_model_bin(params, body, model=""):
    from h2o3_tpu.persist import load_model
    path = params.get("dir")
    if not path or not os.path.exists(path):
        raise ApiError(404, f"model artifact not found: {path}")
    m = load_model(path)
    key = m.key or dkv.unique_key("model")
    dkv.put(key, "model", m)
    return {"__meta": {"schema_version": 3, "schema_name": "ModelsV3"},
            "models": [{"model_id": schemas.keyref(key, "Key<Model>")}]}


@route("POST", "/3/LogAndEcho")
def _log_echo(params, body):
    return {"message": params.get("message", "")}


@route("GET", "/3/DownloadDataset")
@route("GET", "/3/DownloadDataset.bin")
def _download_dataset(params, body):
    """Frame → CSV stream (water/api/DownloadDataHandler); h2o-py
    as_data_frame/get_frame_data parse this client-side."""
    from h2o3_tpu.persist import export_file
    key = params.get("frame_id")
    if isinstance(key, dict):
        key = key.get("name")
    fr = dkv.get(str(key), "frame")
    tmp = os.path.join(tempfile.gettempdir(),
                       f"h2o_dl_{uuid.uuid4().hex[:8]}.csv")
    try:
        export_file(fr, tmp, force=True)
        with open(tmp, "rb") as f:
            data = f.read()
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return {"__raw": data, "__content_type": "text/csv"}


@route("GET", "/3/Metadata/endpoints")
def _endpoints(params, body):
    return {"routes": [{"http_method": m, "url_pattern": rx.pattern}
                       for m, rx, _ in _ROUTES]}


_ERROR_FIELDS = ["timestamp", "error_url", "msg", "dev_msg", "http_status",
                 "values", "exception_type", "exception_msg", "stacktrace"]


@route("GET", "/3/Metadata/schemas/{name}")
def _schema_meta(params, body, name):
    """Schema metadata (water/api/MetadataHandler fetchSchemaMetadata) —
    h2o-py defines H2OCluster/H2OErrorV3 properties from the field list
    at connect time (h2o-py/h2o/schemas/schema.py:29)."""
    if name == "CloudV3":
        keys = [k for k in schemas.cloud_v3() if k != "__meta"]
    elif name == "H2OErrorV3":
        keys = list(_ERROR_FIELDS)
    elif name == "H2OModelBuilderErrorV3":
        keys = _ERROR_FIELDS + ["parameters", "messages", "error_count"]
    else:
        keys = []
    fields = [{"name": k, "help": k, "type": "string", "is_schema": False,
               "schema_name": None} for k in keys]
    return {"__meta": {"schema_version": 3, "schema_name": "MetadataV3"},
            "schemas": [{"name": name, "fields": fields}], "routes": []}


@route("POST", "/99/Grid/{algo}")
def _grid_build(params, body, algo):
    """Grid search over REST (water/api/GridSearchHandler; h2o-py
    grid_search.py:414 wraps the returned job and then fetches
    /99/Grids/{id})."""
    from h2o3_tpu.models.grid import H2OGridSearch
    builders = _builders()
    if algo not in builders:
        raise ApiError(404, f"unknown algorithm '{algo}'")
    raw_keep = {k: params[k] for k in ("grid_id", "model_id",
                                       "training_frame", "validation_frame",
                                       "response_column", "fold_column",
                                       "weights_column", "offset_column")
                if k in params}
    defaults = builders[algo]().params
    parms = {k: _coerce_typed(k, v, defaults) for k, v in params.items()}
    parms.update(raw_keep)
    hyper = parms.pop("hyper_parameters", None) or {}
    if isinstance(hyper, str):
        hyper = json.loads(hyper)
    criteria = parms.pop("search_criteria", None) or {}
    if isinstance(criteria, str):
        criteria = json.loads(criteria)
    gid = parms.pop("grid_id", None) or dkv.unique_key(f"{algo}_grid")
    par = int(parms.pop("parallelism", 1) or 1)
    train_key = parms.pop("training_frame", None)
    frame = dkv.get(str(train_key), "frame")
    valid = None
    vk = parms.pop("validation_frame", None)
    if vk:
        valid = dkv.get(str(vk), "frame")
    y = parms.pop("response_column", None)
    parms = {k: v for k, v in parms.items() if v is not None}
    parms.pop("_rest_version", None)
    est = builders[algo](**parms)
    grid = H2OGridSearch(est, hyper, search_criteria=criteria or None,
                         parallelism=par)

    job = Job(f"{algo} grid search")
    job.dest_key = gid
    # same Lockable contract as /3/ModelBuilders: inputs read-locked,
    # output grid write-locked for the search's duration
    try:
        dkv.read_lock(str(train_key), job.key)
        if vk:
            dkv.read_lock(str(vk), job.key)
        dkv.write_lock(gid, job.key)
    except dkv.KeyLockedError:
        dkv.unlock_all(job.key)
        job.cancel()
        raise

    def body_fn(j):
        try:
            grid.train(y=y, training_frame=frame, validation_frame=valid)
            for i, m in enumerate(grid.models):
                mid = f"{gid}_model_{i}"
                m.key = mid
                dkv.put(mid, "model", m)
            dkv.put(gid, "grid", grid)
            return grid
        finally:
            dkv.unlock_all(j.key)

    job.run(body_fn, background=True)
    return {"__meta": {"schema_version": 99, "schema_name": "GridSearchV99"},
            "job": schemas.job_v3(job, gid, "Key<Grid>"),
            "grid_id": schemas.keyref(gid, "Key<Grid>")}


@route("GET", "/99/Grids/{gid}")
def _grid_get(params, body, gid):
    grid = dkv.get(gid, "grid")
    return {"__meta": {"schema_version": 99, "schema_name": "GridSchemaV99"},
            "grid_id": schemas.keyref(gid, "Key<Grid>"),
            "model_ids": [schemas.keyref(m.key, "Key<Model>")
                          for m in grid.models],
            "hyper_names": list(grid.hyper_params.keys()),
            "failed_params": [], "failure_details": [],
            "failure_stack_traces": [], "failed_raw_params": [],
            "warning_details": [],
            "export_checkpoints_dir": None,
            "summary_table": None, "scoring_history": None}


@route("GET", "/99/Grids")
def _grids_list(params, body):
    return {"grids": [{"grid_id": schemas.keyref(k, "Key<Grid>")}
                      for k in dkv.keys("grid")]}


@route("GET", "/99/Models/{key}")
def _get_model_99(params, body, key):
    return _get_model(params, body, key)


def _automl_tables(aml):
    lb = aml.leaderboard
    metric = lb.metric if lb.rows else "auc"
    table = schemas.twodim(
        "Leaderboard", ["model_id", metric],
        [[r["model_id"] for r in lb.rows],
         [r[metric] for r in lb.rows]], ["string", "double"])
    n_ev = len(aml.event_log)
    # EventLogEntry schema: timestamp/level/stage/message/name/value —
    # h2o-py _fetch() slices el[el['name'] != '', ['name', 'value']]
    ev = schemas.twodim(
        "Event Log",
        ["timestamp", "level", "stage", "message", "name", "value"],
        [[str(e["timestamp"]) for e in aml.event_log],
         ["Info"] * n_ev,
         [e["stage"] for e in aml.event_log],
         [e["message"] for e in aml.event_log],
         [""] * n_ev, [""] * n_ev],
        ["string"] * 6)
    return table, ev


@route("POST", "/99/AutoMLBuilder")
def _automl_build(params, body):
    """AutoML over REST (water/api + ai/h2o/automl; h2o-py
    _estimator.py:668 posts {build_control, input_spec, build_models} and
    polls the returned job)."""
    from h2o3_tpu.automl import H2OAutoML
    spec = params if isinstance(params, dict) else {}
    bc = spec.get("build_control") or {}
    ins = spec.get("input_spec") or {}
    bm = spec.get("build_models") or {}
    sc = bc.get("stopping_criteria") or {}

    def keyname(v):
        return v.get("name") if isinstance(v, dict) else v

    project = bc.get("project_name") or dkv.unique_key("automl")
    train_key = keyname(ins.get("training_frame"))
    frame = dkv.get(str(train_key), "frame")
    valid = None
    if ins.get("validation_frame"):
        valid = dkv.get(str(keyname(ins["validation_frame"])), "frame")
    lb_frame = None
    if ins.get("leaderboard_frame"):
        lb_frame = dkv.get(str(keyname(ins["leaderboard_frame"])), "frame")
    y = ins.get("response_column")
    if isinstance(y, dict):
        y = y.get("column_name")
    ignored = ins.get("ignored_columns") or None
    x = None
    if ignored:
        x = [n for n in frame.names if n not in ignored and n != y]
    def _num(v, default):
        # explicit 0 is a real value (seed=0 pins the RNG) — only
        # missing/empty falls back
        return default if v in (None, "") else v

    aml = H2OAutoML(
        max_models=sc.get("max_models"),
        max_runtime_secs=sc.get("max_runtime_secs"),
        max_runtime_secs_per_model=sc.get("max_runtime_secs_per_model"),
        nfolds=bc.get("nfolds", 3),
        seed=_num(sc.get("seed"), -1),
        sort_metric=ins.get("sort_metric"),
        include_algos=bm.get("include_algos"),
        exclude_algos=bm.get("exclude_algos"),
        project_name=project,
        exploitation_ratio=_num(bm.get("exploitation_ratio"), -1.0))
    dkv.put(project, "automl", aml)

    job = Job(f"AutoML {project}")
    job.dest_key = project
    try:
        dkv.read_lock(str(train_key), job.key)
        if ins.get("validation_frame"):
            dkv.read_lock(str(keyname(ins["validation_frame"])), job.key)
        if ins.get("leaderboard_frame"):
            dkv.read_lock(str(keyname(ins["leaderboard_frame"])), job.key)
    except dkv.KeyLockedError:
        dkv.unlock_all(job.key)
        job.cancel()
        raise

    def body_fn(j):
        try:
            aml.train(x=x, y=y, training_frame=frame,
                      validation_frame=valid, leaderboard_frame=lb_frame)
            return aml
        finally:
            dkv.unlock_all(j.key)

    job.run(body_fn, background=True)
    return {"__meta": {"schema_version": 99, "schema_name": "AutoMLBuilderV99"},
            "job": schemas.job_v3(job, project, "Key<AutoML>"),
            "build_control": {"project_name": project}}


@route("GET", "/99/AutoML/{project}")
def _automl_get(params, body, project):
    aml = dkv.get(project, "automl")
    table, ev = _automl_tables(aml)
    return {"__meta": {"schema_version": 99, "schema_name": "AutoMLV99"},
            "project_name": project,
            "leaderboard": {"models": [schemas.keyref(m.key, "Key<Model>")
                                       for m in aml.models]},
            "leaderboard_table": table,
            "event_log_table": ev}


@route("GET", "/99/Leaderboards/{project}")
def _leaderboard_get(params, body, project):
    aml = dkv.get(project, "automl")
    table, _ev = _automl_tables(aml)
    return {"__meta": {"schema_version": 99,
                       "schema_name": "LeaderboardV99"},
            "project_name": project, "table": table}


@route("GET", "/3/ModelBuilders")
def _model_builders(params, body):
    """Algo registry (water/api/ModelBuildersHandler list)."""
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelBuildersV3"},
            "model_builders": {a: {"algo": a, "visibility": "Stable",
                                   "algo_full_name": a.upper()}
                               for a in sorted(_builders())}}


@route("GET", "/3/ModelBuilders/{algo}")
def _model_builder_meta(params, body, algo):
    builders = _builders()
    if algo not in builders:
        raise ApiError(404, f"unknown algorithm '{algo}'")
    est = builders[algo]()
    parameters = [{"name": k,
                   "default_value": list(v) if isinstance(v, tuple) else v,
                   "actual_value": list(v) if isinstance(v, tuple) else v,
                   "label": k, "type": type(v).__name__, "level": "critical",
                   "values": []}
                  for k, v in est.params.items()
                  if isinstance(v, (int, float, str, bool, list, tuple,
                                    type(None)))]
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelBuildersV3"},
            "model_builders": {algo: {"algo": algo,
                                      "parameters": parameters}}}


@route("GET", "/3/Jobs")
def _jobs_list(params, body):
    from h2o3_tpu.jobs import list_jobs
    return {"__meta": {"schema_version": 3, "schema_name": "JobsV3"},
            "jobs": [schemas.job_v3(j, getattr(j, "dest_key", None))
                     for j in list_jobs()]}


@route("GET", "/3/Typeahead/files")
def _typeahead(params, body):
    """Path completion (water/api/TypeaheadHandler)."""
    src = params.get("src") or "/"
    limit = int(params.get("limit", 100) or 100)
    base = src if os.path.isdir(src) else os.path.dirname(src) or "/"
    prefix = "" if os.path.isdir(src) else os.path.basename(src)
    try:
        entries = sorted(os.listdir(base))
    except OSError:
        entries = []
    matches = [os.path.join(base, e) for e in entries
               if e.startswith(prefix)][:limit]
    return {"__meta": {"schema_version": 3, "schema_name": "TypeaheadV3"},
            "src": src, "limit": limit, "matches": matches}


@route("GET", "/3/Capabilities")
@route("GET", "/3/Capabilities/Core")
def _capabilities(params, body):
    return {"__meta": {"schema_version": 3,
                       "schema_name": "CapabilitiesV3"},
            "capabilities": [{"name": a, "category": "Algos"}
                             for a in sorted(_builders())]}


@route("POST", "/3/SplitFrame")
def _split_frame(params, body):
    """water/api/SplitFrameHandler: ratios → destination frames."""
    from h2o3_tpu.frame.frame import Frame
    key = _coerce(params.get("dataset"))
    if isinstance(key, dict):
        key = key.get("name")
    fr = dkv.get(str(key), "frame")
    ratios = _coerce(params.get("ratios", "[0.75]")) or [0.75]
    dests = _bracket_list(params.get("destination_frames", "")) or None
    seed_p = params.get("seed")
    seed = int(seed_p) if seed_p not in (None, "") else -1
    parts = fr.split_frame(ratios=[float(r) for r in ratios], seed=seed)
    keys = []
    for i, p in enumerate(parts):
        k = (dests[i] if dests and i < len(dests)
             else dkv.unique_key("split"))
        dkv.put(k, "frame", p)
        keys.append(k)
    job = Job("SplitFrame")
    job.dest_key = keys[0] if keys else None
    job.run(lambda j: None, background=False)
    return {"__meta": {"schema_version": 3, "schema_name": "SplitFrameV3"},
            "key": schemas.keyref(job.key, "Key<Job>"),
            "job": schemas.job_v3(job, job.dest_key, "Key<Frame>"),
            "destination_frames": [schemas.keyref(k, "Key<Frame>")
                                   for k in keys]}


@route("POST", "/3/GarbageCollect")
def _gc(params, body):
    import gc
    gc.collect()
    return {}


@route("GET", "/3/JStack")
def _jstack(params, body):
    """Thread dumps (water/util/JStackCollectorTask → /3/JStack)."""
    import traceback
    frames = sys._current_frames()
    traces = []
    for tid, frm in frames.items():
        traces.append({"thread_id": tid,
                       "stack": "".join(traceback.format_stack(frm))})
    return {"__meta": {"schema_version": 3, "schema_name": "JStackV3"},
            "traces": [{"node": "127.0.0.1:54321",
                        "thread_traces": traces}]}


@route("POST", "/3/Shutdown")
def _shutdown(params, body):
    """Accepted but ignored: single-controller process lifetime belongs
    to the host (the reference kills the JVM here)."""
    return {}


@route("POST", "/99/Rapids")
def _rapids(params, body):
    from h2o3_tpu.rapids import exec_rapids
    ast = params.get("ast", "")
    # numpy>=2 compatibility for the UNMODIFIED client: h2o-py pins
    # numpy<2 and str()-serializes column names; under numpy 2 a
    # np.str_ reprs as np.str_('name') and leaks into the ast
    ast = re.sub(r"np\.str_\('([^']*)'\)", r'"\1"', ast)
    session = params.get("session_id")
    try:
        return exec_rapids(ast, session)
    except Exception as e:
        # surface WHICH expression failed — rapids errors without the
        # ast are undebuggable from the client side (ValueError: not
        # every exception type reconstructs from one string)
        raise ValueError(
            f"{type(e).__name__}: {e} [ast: {str(ast)[:400]}]") from e


# ---------------- HTTP plumbing ----------------------------------------

class _Handler(BaseHTTPRequestHandler):
    server_version = "h2o3-tpu/3.46"

    def log_message(self, fmt, *args):  # quiet by default
        if os.environ.get("H2O3_API_LOG"):
            super().log_message(fmt, *args)

    def _dispatch(self, method):
        from h2o3_tpu.telemetry import trace as teletrace
        # trace propagation (ISSUE 8): accept a W3C traceparent header
        # (or mint a fresh id), bind it to this handler thread for the
        # whole request — every span/job the handler touches inherits
        # it — and echo it back on the response
        self._trace_id = teletrace.parse_traceparent(
            self.headers.get(teletrace.TRACEPARENT_HEADER)) \
            or teletrace.new_trace_id()
        with teletrace.trace_context(self._trace_id):
            self._dispatch_traced(method)

    def _dispatch_traced(self, method):
        parsed = urllib.parse.urlparse(self.path)
        path = parsed.path
        params = {k: v[0] for k, v in
                  urllib.parse.parse_qs(parsed.query).items()}
        body = b""
        try:
            clen = int(self.headers.get("Content-Length") or 0)
            if clen:
                body = self.rfile.read(clen)
            ctype = self.headers.get("Content-Type", "")
            if body and "application/x-www-form-urlencoded" in ctype:
                params.update({k: v[0] for k, v in
                               urllib.parse.parse_qs(body.decode()).items()})
            elif body and "application/json" in ctype:
                try:
                    params.update(json.loads(body.decode()))
                except json.JSONDecodeError:
                    pass
        except Exception as e:  # malformed body → JSON error, not a reset
            self._reply(400, {"__meta": {"schema_name": "H2OErrorV3"},
                              "http_status": 400, "msg": str(e),
                              "exception_type": type(e).__name__,
                              "values": {}, "stacktrace": []})
            return
        # deadline-class lane (ISSUE 20): an explicit X-H2O3-Lane header
        # outranks body/query params — the router's dispatch spelling
        lane_hdr = self.headers.get("X-H2O3-Lane")
        if lane_hdr:
            params["_lane"] = lane_hdr
        for m, rx, fn in _ROUTES:
            if m != method:
                continue
            match = rx.match(path)
            if match:
                try:
                    groups = {k: urllib.parse.unquote(v)
                              for k, v in match.groupdict().items()}
                    out = fn(params, body, **groups)
                    extra = out.pop("__headers", None) if isinstance(
                        out, dict) else None
                    if isinstance(out, dict) and "__raw" in out:
                        self._reply_raw(200, out["__raw"],
                                        out.get("__content_type",
                                                "application/octet-stream"),
                                        headers=extra)
                        return
                    status = out.pop("__http_status", 200) if isinstance(
                        out, dict) else 200
                    self._reply(status, out, headers=extra)
                except ApiError as e:
                    self._reply(e.status, {
                        "__meta": {"schema_name": "H2OErrorV3"},
                        "http_status": e.status, "msg": str(e),
                        "dev_msg": str(e), "exception_msg": str(e),
                        "exception_type": "ApiError", "values": {},
                        "stacktrace": []}, headers=e.headers)
                except dkv.KeyLockedError as e:
                    self._reply(409, {
                        "__meta": {"schema_name": "H2OErrorV3"},
                        "http_status": 409, "msg": str(e),
                        "dev_msg": str(e), "exception_msg": str(e),
                        "exception_type": "KeyLockedError", "values": {},
                        "stacktrace": []})
                except Exception as e:  # noqa: BLE001 — wire boundary
                    import traceback
                    self._reply(500, {
                        "__meta": {"schema_name": "H2OErrorV3"},
                        "http_status": 500, "msg": str(e),
                        "dev_msg": str(e), "exception_msg": str(e),
                        "exception_type": type(e).__name__, "values": {},
                        "stacktrace": traceback.format_exc().split("\n")})
                return
        self._reply(404, {"__meta": {"schema_name": "H2OErrorV3"},
                          "http_status": 404,
                          "msg": f"no route for {method} {path}",
                          "exception_type": "NotFound", "values": {},
                          "stacktrace": []})

    def _trace_headers(self):
        tid = getattr(self, "_trace_id", None)
        if tid:
            from h2o3_tpu.telemetry import trace as teletrace
            self.send_header(teletrace.TRACEPARENT_HEADER,
                             teletrace.format_traceparent(tid))
            self.send_header("X-H2O3-Trace-Id", tid)

    def _reply_raw(self, status, data: bytes, ctype: str, headers=None):
        self.send_response(status)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        self._trace_headers()
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        if self.command != "HEAD":
            self.wfile.write(data)

    def _reply(self, status, obj, headers=None):
        data = json.dumps(obj, default=_json_default).encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self._trace_headers()
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        if self.command != "HEAD":
            self.wfile.write(data)

    def do_GET(self):
        self._dispatch("GET")

    def do_POST(self):
        self._dispatch("POST")

    def do_DELETE(self):
        self._dispatch("DELETE")

    def do_HEAD(self):
        self._dispatch("HEAD")


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        v = float(o)
        return v if np.isfinite(v) else None
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


# ------------- analytics / tooling routes (reference parity set) -------


@route("POST", "/3/CreateFrame")
def _create_frame_route(params, body):
    """water/api/CreateFrameHandler → hex/createframe; h2o.create_frame."""
    from h2o3_tpu.analytics import create_frame
    p = {k: _coerce(v) for k, v in params.items()}
    p.pop("dest", None)
    dest = params.get("dest") or dkv.unique_key("create_frame")
    kw = {k: p[k] for k in ("rows", "cols", "categorical_fraction",
                            "integer_fraction", "binary_fraction",
                            "missing_fraction", "factors", "real_range",
                            "integer_range", "seed", "has_response")
          if p.get(k) is not None}
    job = Job("CreateFrame")
    job.dest_key = dest
    job.dest_type = "Key<Frame>"

    def body_fn(j):
        fr = create_frame(**kw)
        fr.key = dest
        dkv.put(dest, "frame", fr)
        return fr

    job.run(body_fn, background=True)
    return schemas.job_v3(job, dest, "Key<Frame>")


@route("POST", "/3/Interaction")
def _interaction_route(params, body):
    """hex/Interaction via water/api/InteractionHandler; h2o.interaction."""
    from h2o3_tpu.analytics import interaction_frame
    p = {k: _coerce(v) for k, v in params.items()}
    fr = dkv.get(str(params.get("source_frame")), "frame")
    factors = _strlist(params.get("factor_columns")
                       or params.get("factors"))
    dest = params.get("dest") or dkv.unique_key("interaction")
    job = Job("Interaction")
    job.dest_key = dest
    job.dest_type = "Key<Frame>"

    def body_fn(j):
        out = interaction_frame(
            fr, factors, pairwise=bool(p.get("pairwise")),
            max_factors=int(p.get("max_factors") or 100),
            min_occurrence=int(p.get("min_occurrence") or 1))
        out.key = dest
        dkv.put(dest, "frame", out)
        return out

    job.run(body_fn, background=True)
    return schemas.job_v3(job, dest, "Key<Frame>")


@route("POST", "/3/FriedmansPopescusH")
def _friedman_popescu_h(params, body):
    """Friedman-Popescu H statistic (hex/tree/FriedmanPopescusH.java,
    water/api/schemas3/FriedmanPopescusHV3.java; h2o-py model.h())."""
    m = dkv.get(str(params.get("model_id")), "model")
    fr = dkv.get(str(params.get("frame")), "frame")
    variables = _strlist(params.get("variables"))
    if not variables:
        raise ApiError(400, "variables is required")
    return {"__meta": {"schema_version": 3,
                       "schema_name": "FriedmanPopescusHV3"},
            "model_id": {"name": params.get("model_id")},
            "frame": {"name": params.get("frame")},
            "variables": variables,
            "h": m.h(fr, variables)}


@route("POST", "/3/PartialDependence/")
@route("POST", "/3/PartialDependence")
def _pdp_build(params, body):
    """hex/PartialDependence via water/api; h2o-py model.partial_plot."""
    from h2o3_tpu.analytics import partial_dependence
    p = {k: _coerce(v) for k, v in params.items()}
    m = dkv.get(str(params.get("model_id")), "model")
    fr = dkv.get(str(params.get("frame_id")), "frame")
    cols = _strlist(params.get("cols"))
    if not cols:
        cols = [c for c in m.feature_names][:3]
    dest = params.get("destination_key") or dkv.unique_key("pdp")
    job = Job("PartialDependencePlot")
    job.dest_key = dest
    job.dest_type = "Key<PartialDependence>"

    def body_fn(j):
        res = partial_dependence(m, fr, cols,
                                 nbins=int(p.get("nbins") or 20))
        dkv.put(dest, "pdp", {"cols": cols, "data": res})
        return res

    job.run(body_fn, background=True)
    return schemas.job_v3(job, dest, "Key<PartialDependence>")


@route("GET", "/3/PartialDependence/{key}")
def _pdp_get(params, body, key):
    obj = dkv.get(key, "pdp")
    tables = []
    for col in obj["cols"]:
        d = obj["data"][col]
        n_avg = max(int(d.get("n_rows", 1)), 1)   # rows averaged per point
        tables.append(schemas.twodim(
            f"PartialDependence for '{col}'",
            [col, "mean_response", "stddev_response", "std_error_mean_response"],
            [d["grid"], d["mean_response"], d["stddev_response"],
             [s / n_avg ** 0.5 for s in d["stddev_response"]]],
            ["string", "double", "double", "double"]))
    return {"__meta": {"schema_version": 3,
                       "schema_name": "PartialDependenceV3"},
            "destination_key": key,
            "partial_dependence_data": tables}


@route("POST", "/99/Tabulate")
@route("GET", "/99/Tabulate")
def _tabulate_route(params, body):
    """hex/Tabulate (Flow's tabulate cell); h2o.tabulate."""
    from h2o3_tpu.analytics import tabulate
    p = {k: _coerce(v) for k, v in params.items()}
    fr = dkv.get(str(params.get("dataset")), "frame")
    res = tabulate(fr, str(params.get("predictor")),
                   str(params.get("response")),
                   nbins_x=int(p.get("nbins_predictor") or 20),
                   nbins_y=int(p.get("nbins_response") or 20))
    ylab = [str(v) for v in res["y_labels"]]
    count_tbl = schemas.twodim(
        "Tabulate counts", ["predictor"] + ylab,
        [[str(v) for v in res["x_labels"]]]
        + [list(r) for r in np.asarray(res["counts"]).T.tolist()],
        ["string"] + ["double"] * len(ylab))
    means = res.get("mean_y_per_x")
    if means is None:       # categorical response: no per-bin mean
        means = [float("nan")] * len(res["x_labels"])
    resp_tbl = schemas.twodim(
        "Tabulate response", ["predictor", "mean_response"],
        [[str(v) for v in res["x_labels"]], means],
        ["string", "double"])
    return {"__meta": {"schema_version": 99, "schema_name": "TabulateV99"},
            "count_table": count_tbl, "response_table": resp_tbl}


@route("GET", "/3/Tree")
def _tree_route(params, body):
    """Tree inspection (hex/tree/TreeHandler → TreeV3; h2o-py H2OTree)."""
    p = {k: _coerce(v) for k, v in params.items()}
    m = dkv.get(str(params.get("model")), "model")
    if not hasattr(m, "_feat"):
        raise ApiError(400, f"model '{m.key}' is not tree-based")
    tree_no = int(p.get("tree_number") or 0)
    K = getattr(m, "_K", 1)
    cls = p.get("tree_class")
    cls_idx = 0
    if K > 1 and cls is not None:
        dom = list(m.response_domain or [])
        if str(cls) in dom:
            cls_idx = dom.index(str(cls))
        else:
            try:
                cls_idx = int(cls)
            except (TypeError, ValueError):
                raise ApiError(400, f"unknown tree_class '{cls}' "
                                    f"(domain: {dom})")
            if not 0 <= cls_idx < K:
                raise ApiError(400, f"tree_class index {cls_idx} out of "
                                    f"range for {K} classes")
    t = tree_no * K + cls_idx
    if t >= m._feat.shape[0] or tree_no < 0:
        raise ApiError(404, f"tree {tree_no} out of range")
    feat = np.asarray(m._feat[t])
    thr = np.asarray(m._thr[t])
    nal = np.asarray(m._na_left[t])
    spl = np.asarray(m._is_split[t])
    val = np.asarray(m._value[t])
    # a split on a set of an enum's levels (GBM): the levels on the edge
    # into each child, as TreeV3's ``levels`` has them
    sets = getattr(m, "_cat_set", None)
    is_set = (np.asarray(m._is_set[t]) if sets is not None
              else np.zeros(len(feat), bool))
    levels_of = {}
    # BFS over reachable nodes of the complete array → compressed arrays
    idx_of = {}
    order = []
    stack = [0]
    while stack:
        n = stack.pop(0)
        idx_of[n] = len(order)
        order.append(n)
        if spl[n]:
            stack += [2 * n + 1, 2 * n + 2]
    left, right, feats, thrs, nas, preds, descs = [], [], [], [], [], [], []
    for n in order:
        if spl[n]:
            left.append(idx_of[2 * n + 1])
            right.append(idx_of[2 * n + 2])
            fname = m.feature_names[int(feat[n])]
            feats.append(fname)
            thrs.append("NaN" if is_set[n] else float(thr[n]))
            nas.append("LEFT" if nal[n] else "RIGHT")
            if is_set[n]:
                from h2o3_tpu.models.tree import set_levels
                dom = m.cat_domains.get(fname) or ()
                goes = set_levels(np.asarray(sets[t, n]), len(dom))
                levels_of[2 * n + 1] = np.flatnonzero(goes).tolist()
                levels_of[2 * n + 2] = np.flatnonzero(~goes).tolist()
                descs.append(f"{fname} in {int(goes.sum())} of {len(dom)} "
                             f"levels goes left"
                             f" (NA {'left' if nal[n] else 'right'})")
            else:
                descs.append(f"{fname} < {thr[n]:.6g} goes left"
                             f" (NA {'left' if nal[n] else 'right'})")
        else:
            left.append(-1)
            right.append(-1)
            feats.append(None)
            thrs.append("NaN")
            nas.append(None)
            descs.append("leaf")
        preds.append(float(val[n]))
    return {"__meta": {"schema_version": 3, "schema_name": "TreeV3"},
            "model": schemas.keyref(m.key, "Key<Model>"),
            "tree_number": tree_no,
            "tree_class": cls if K > 1 else None,
            "left_children": left, "right_children": right,
            "root_node_id": 0, "descriptions": descs,
            "thresholds": thrs, "features": feats,
            "levels": [levels_of.get(n) for n in order], "nas": nas,
            "predictions": preds,
            "tree_decision_path": None, "decision_paths": None}


@route("GET", "/3/TargetEncoderTransform")
def _te_transform_route(params, body):
    """TargetEncoder transform over REST (ai/h2o/targetencoding
    TargetEncoderHandler; h2o-py H2OTargetEncoderEstimator.transform)."""
    p = {k: _coerce(v) for k, v in params.items()}
    m = dkv.get(str(params.get("model")), "model")
    fr = dkv.get(str(params.get("frame")), "frame")
    out = m.transform(fr,
                      as_training=bool(p.get("as_training")),
                      noise=float(p["noise"]) if p.get("noise") not in
                      (None, -1) else None)
    dest = dkv.unique_key("te_transform")
    out.key = dest
    dkv.put(dest, "frame", out)
    return {"__meta": {"schema_version": 3,
                       "schema_name": "TargetEncoderTransformV3"},
            "name": dest, "key": schemas.keyref(dest, "Key<Frame>")}


@route("GET", "/3/Word2VecSynonyms")
def _w2v_synonyms(params, body):
    m = dkv.get(str(params.get("model")), "model")
    word = str(params.get("word"))
    count = int(_coerce(params.get("count", 20)) or 20)
    syn = m.find_synonyms(word, count)
    return {"__meta": {"schema_version": 3,
                       "schema_name": "Word2VecSynonymsV3"},
            "synonyms": list(syn.keys()), "scores": list(syn.values())}


@route("GET", "/3/Word2VecTransform")
def _w2v_transform(params, body):
    m = dkv.get(str(params.get("model")), "model")
    wf = dkv.get(str(params.get("words_frame")), "frame")
    agg = str(params.get("aggregate_method") or "NONE").lower()
    out = m.transform(wf, aggregate_method=agg)
    dest = dkv.unique_key("w2v_transform")
    out.key = dest
    dkv.put(dest, "frame", out)
    return {"__meta": {"schema_version": 3,
                       "schema_name": "Word2VecTransformV3"},
            "vectors_frame": schemas.keyref(dest, "Key<Frame>")}


@route("POST", "/3/Grid.bin/import")
def _grid_import(params, body):
    """h2o.load_grid → reload a saved grid + its models (water/api/
    GridImportExportHandler)."""
    from h2o3_tpu.models.grid import load_grid_artifact
    path = str(params.get("grid_path"))
    gid, grid, models = load_grid_artifact(path)
    for m in models:
        dkv.put(m.key, "model", m)
    dkv.put(gid, "grid", grid)
    return {"__meta": {"schema_version": 3, "schema_name": "GridKeyV3"},
            "name": gid}


@route("POST", "/3/Grid.bin/{gid}/export")
def _grid_export(params, body, gid):
    """h2o.save_grid → persist a grid + models to a directory."""
    from h2o3_tpu.models.grid import save_grid_artifact
    grid = dkv.get(gid, "grid")
    d = params.get("grid_directory")
    if not d:
        raise ApiError(400, "grid_directory is required")
    save_grid_artifact(grid, gid, str(d))
    return {"__meta": {"schema_version": 3, "schema_name": "GridKeyV3"},
            "name": gid}


@route("POST", "/3/Frames/{fid}/save")
def _frame_save(params, body, fid):
    """Binary frame export (water/api/FramesHandler.saveFrame;
    h2o-py frame.save)."""
    from h2o3_tpu.persist import save_frame
    fr = dkv.get(fid, "frame")
    d = params.get("dir")
    if not d:
        raise ApiError(400, "dir is required")
    d = str(d)
    force = _coerce(params.get("force", "true"))
    job = Job(f"Save frame {fid}")
    job.dest_key = fid
    job.dest_type = "Key<Frame>"

    def body_fn(j):
        return save_frame(fr, d, force=bool(force), key=fid)

    job.run(body_fn, background=True)
    return schemas.job_v3(job, fid, "Key<Frame>")


@route("POST", "/3/Frames/load")
def _frame_load(params, body):
    """Binary frame import (FramesHandler.loadFrame; h2o.load_frame)."""
    from h2o3_tpu.persist import load_frame
    fid = str(params.get("frame_id"))
    d = params.get("dir")
    if not d:
        raise ApiError(400, "dir is required")
    d = str(d)
    job = Job(f"Load frame {fid}")
    job.dest_key = fid
    job.dest_type = "Key<Frame>"

    def body_fn(j):
        fr = load_frame(d, key=fid)
        dkv.put(fid, "frame", fr)
        return fr

    job.run(body_fn, background=True)
    return schemas.job_v3(job, fid, "Key<Frame>")


class _FrontDoorServer(ThreadingHTTPServer):
    # the stdlib default accept backlog (5) overflows under concurrent
    # scoring clients + fleet beats + router gossip on one socket,
    # surfacing as spurious connection-refused at the front door
    request_queue_size = 128


class H2OApiServer:
    """Embedded API server (the h2o.jar web server analog)."""

    def __init__(self, port: int = 54321, host: str = "127.0.0.1"):
        # any process that serves REST serves /metrics — make sure the
        # XLA compile/cache listeners are live before the first scrape
        from h2o3_tpu import telemetry
        telemetry.install()
        self.httpd = _FrontDoorServer((host, port), _Handler)
        self.port = self.httpd.server_address[1]
        self.host = host
        self._thread: Optional[threading.Thread] = None

    def start(self):
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def start_server(port: int = 54321, host: str = "127.0.0.1") -> H2OApiServer:
    return H2OApiServer(port=port, host=host).start()


@route("GET", "/3/Logs/download")
@route("GET", "/3/Logs")
def _logs(params, body):
    from h2o3_tpu.log import buffered_lines
    return {"__meta": {"schema_version": 3, "schema_name": "LogsV3"},
            "log": "\n".join(buffered_lines(int(params.get("n", 1000)
                                                or 1000)))}


@route("GET", "/3/Timeline")
def _timeline(params, body):
    """water/TimeLine.java ring-buffer snapshot (/3/Timeline).

    Default: the H2O event shape Flow expects — TimelineV3 has no
    nodeidx path parameter (water/api/TimelineHandler serves the whole
    cloud's merged ring); each event carries the EventV3 fields
    (date/nanos/who/io_flavor/event/bytes). The ring is now fed by
    every pipeline's finished ROOT telemetry spans (ingest.parse,
    train.*, serve.request/batch), not just model builds.

    ``?format=trace``: Chrome-trace/Perfetto JSON of the finished-span
    ring — the accelerator-aware timeline the JVM tools never had.

    ``?scope=cluster`` (ISSUE 19): the fleet-wide CAUSAL timeline from
    the flight-recorder rings instead of the local span ring — this
    process's ring, every live peer's ring (telemetry peer plane), and
    any DEAD member's mmap ring still readable under the shared
    blackbox dir. Events sort by (membership epoch, skew-corrected
    wall clock); members whose heartbeat skew exceeds the flag
    threshold are marked. ``format=trace`` renders the same merge as
    Chrome-trace instants, one process row per member, dead members
    labeled."""
    from h2o3_tpu import telemetry
    fmt = (params.get("format") or "").lower()
    if (params.get("scope") or "").lower() == "cluster":
        from h2o3_tpu.telemetry import blackbox
        n = int(params.get("n", 256) or 256)
        if fmt in ("trace", "perfetto", "chrome"):
            return {"__raw": blackbox.cluster_trace_bytes(n),
                    "__content_type": "application/json"}
        return {"__meta": {"schema_version": 3,
                           "schema_name": "TimelineClusterV3"},
                **blackbox.cluster_timeline(n)}
    if fmt in ("trace", "perfetto", "chrome"):
        limit = int(params.get("n", 0) or 0) or None
        return {"__raw": telemetry.chrome_trace_bytes(limit),
                "__content_type": "application/json"}
    from h2o3_tpu.log import timeline_events
    evs = timeline_events(int(params.get("n", 2048) or 2048))
    out = []
    for e in evs:
        ts = float(e.get("ts", 0.0))
        out.append({
            "date": time.strftime("%Y-%m-%d %H:%M:%S",
                                  time.localtime(ts)),
            "nanos": int(ts * 1e9),
            "who": "tpu-controller/0",
            "io_flavor": None,
            "event": e.get("kind", ""),
            "bytes": e.get("detail", ""),
            # legacy keys kept for the built-in Flow page
            "ts": ts, "kind": e.get("kind", ""),
            "detail": e.get("detail", ""),
        })
    return {"__meta": {"schema_version": 3, "schema_name": "TimelineV3"},
            "now": int(time.time() * 1000), "self": "tpu-controller/0",
            "events": out}


@route("GET", "/3/Blackbox")
def _blackbox(params, body):
    """This process's flight-recorder tail (ISSUE 19) — the wire format
    peers pull for ``/3/Timeline?scope=cluster``. Decoded events, not
    raw ring bytes: the reader never needs the writer's struct layout
    version."""
    from h2o3_tpu.telemetry import blackbox
    n = int(params.get("n", 256) or 256)
    return {"__meta": {"schema_version": 3, "schema_name": "BlackboxV3"},
            "member_id": blackbox._default_member_id(),
            "enabled": blackbox.ring_path() is not None,
            "events_recorded": blackbox.events_recorded(),
            "events": blackbox.local_events(n)}


def _cluster_prometheus_raw():
    """Merged cluster scrape rendered as exposition text — the one
    spelling behind ``/metrics?scope=cluster`` and
    ``/3/Telemetry/cluster?format=prometheus``."""
    from h2o3_tpu import telemetry
    samples, _meta = telemetry.cluster_samples()
    return {"__raw": telemetry.prometheus_text(samples=samples).encode(),
            "__content_type": "text/plain; version=0.0.4; charset=utf-8"}


@route("GET", "/metrics")
def _metrics(params, body):
    """Prometheus exposition of the process-wide telemetry registry
    (text format 0.0.4) — counters/gauges/histograms from every
    pipeline plus the XLA compile/cache/transfer collectors.

    ``?scope=cluster`` merges peer-process snapshots (peer list from
    H2O3_TELEMETRY_PEERS; counters sum, histograms bucket-merge, gauges
    get a ``process=`` label) through the SAME formatter. The default
    scope never touches the aggregation path — single-process output is
    bit-identical to PR 4/7."""
    from h2o3_tpu import telemetry
    telemetry.install()
    if (params.get("scope") or "").lower() == "cluster":
        return _cluster_prometheus_raw()
    return {"__raw": telemetry.prometheus_text().encode(),
            "__content_type": "text/plain; version=0.0.4; charset=utf-8"}


@route("GET", "/3/Telemetry")
def _telemetry_snapshot(params, body):
    """H2O-style JSON snapshot of the same registry /metrics exports:
    flat metric map, per-span stage aggregates, device memory, compile
    and transfer counters."""
    from h2o3_tpu import telemetry
    telemetry.install()
    return {"__meta": {"schema_version": 3, "schema_name": "TelemetryV3"},
            **telemetry.telemetry_snapshot()}


@route("GET", "/3/Telemetry/snapshot")
def _telemetry_process_snapshot(params, body):
    """THIS process's registry + finished-span ring as one mergeable
    snapshot — the wire format peers pull for the cluster aggregation
    (telemetry/snapshot.py). ``n`` bounds the serialized span count."""
    from h2o3_tpu import telemetry
    telemetry.install()
    n = int(params.get("n", 2048) or 2048)
    return {"__meta": {"schema_version": 3,
                       "schema_name": "TelemetrySnapshotV3"},
            **telemetry.local_snapshot(max_spans=n)}


@route("GET", "/3/Telemetry/cluster")
def _telemetry_cluster(params, body):
    """Cluster-merged telemetry: this process + every peer in
    H2O3_TELEMETRY_PEERS (counters summed, histograms bucket-merged,
    gauges labeled ``process=``). ``?format=prometheus`` renders the
    merged samples as exposition text instead of the JSON map. Dead
    peers are reported in ``peers_failed``, never fatal."""
    from h2o3_tpu import telemetry
    telemetry.install()
    if (params.get("format") or "").lower() == "prometheus":
        return _cluster_prometheus_raw()
    return {"__meta": {"schema_version": 3,
                       "schema_name": "TelemetryClusterV3"},
            **telemetry.cluster_snapshot()}


@route("GET", "/3/Telemetry/perf")
def _telemetry_perf(params, body):
    """Performance accounting view (ISSUE 11): detected per-chip peaks
    (``peak_source`` provenance, ``informational`` flag on CPU/unknown
    hardware) plus a roofline point per phase — achieved flops/bytes
    per second, arithmetic intensity, MFU and compute- vs memory-bound
    regime — derived from the cumulative ``h2o3_achieved_*`` counters
    the cost-capture seams feed."""
    from h2o3_tpu import telemetry
    telemetry.install()
    return {"__meta": {"schema_version": 3,
                       "schema_name": "TelemetryPerfV3"},
            **telemetry.costmodel.summary()}


@route("GET", "/3/Profiler")
def _profiler(params, body):
    """water/api/ProfilerHandler: aggregated stack samples per node
    (ProfilerV3 -> ProfilerNodeV3 {node_name, timestamp, entries:
    [{stacktrace, count}]}). One controller process here, so one node."""
    import time as _time

    from h2o3_tpu.log import stack_samples
    depth = int(params.get("depth", 10) or 10)
    if depth < 1:
        raise ApiError(400, "depth must be >= 1")
    entries = stack_samples(depth=depth)
    return {"__meta": {"schema_version": 3, "schema_name": "ProfilerV3"},
            "depth": depth,
            "nodes": [{"node_name": "tpu-controller/0",
                       "timestamp": int(_time.time() * 1000),
                       "entries": entries}]}


@route("POST", "/3/Profiler/trace")
def _profiler_trace(params, body):
    """TPU-native device tracing (no reference analog — the JVM profiler
    cannot see the accelerator): start/stop a jax.profiler trace whose
    artifacts load in TensorBoard/Perfetto. action=start|stop."""
    import jax as _jax
    action = (params.get("action") or "").lower()
    if action == "start":
        log_dir = params.get("log_dir") or os.path.join(
            tempfile.gettempdir(), "h2o3_jax_trace")
        try:
            _jax.profiler.start_trace(log_dir)
        except RuntimeError as e:      # double-start: already tracing
            raise ApiError(400, f"trace already active: {e}")
        return {"__meta": {"schema_name": "ProfilerTraceV3"},
                "status": "started", "log_dir": log_dir}
    if action == "stop":
        try:
            _jax.profiler.stop_trace()
        except RuntimeError as e:
            raise ApiError(400, f"no active trace: {e}")
        return {"__meta": {"schema_name": "ProfilerTraceV3"},
                "status": "stopped"}
    raise ApiError(400, "action must be 'start' or 'stop'")


# ---------------- round-5 REST breadth batch 2 -------------------------
# The remaining RegisterV3Api.java registrations with real machinery
# behind them in this codebase; hive/decryption/steam are honest gates.

@route("GET", "/3/Ping")
def _ping(params, body):
    """water/api/PingHandler: liveness + a cloud snapshot."""
    import psutil
    vm = psutil.virtual_memory()
    return {"__meta": {"schema_version": 3, "schema_name": "PingV3"},
            "cloud_uptime_millis": schemas.uptime_ms(),
            "cloud_healthy": True,
            "nodes": [{"mem": int(vm.available),
                       "num_cpus": os.cpu_count() or 1}]}


@route("GET", "/3/InitID")
def _init_id(params, body):
    """water/api/InitIDHandler: issue a session key (h2o-py uses the
    /4/sessions flavor; R's h2o.init path hits this one)."""
    import uuid as _uuid
    sid = "_sid_" + _uuid.uuid4().hex[:10]
    dkv.put(sid, "session", {"frames": []})
    return {"__meta": {"schema_version": 3, "schema_name": "InitIDV3"},
            "session_key": sid}


@route("DELETE", "/3/InitID")
def _end_init_id(params, body):
    return {"__meta": {"schema_version": 3, "schema_name": "InitIDV3"}}


@route("GET", "/3/CloudLock")
def _cloud_lock(params, body):
    """water/api/CloudLockHandler. The single-controller cloud never
    re-forms after boot, so it is always locked-stable."""
    return {"__meta": {"schema_version": 3, "schema_name": "CloudLockV3"},
            "locked": True, "reason": "single-controller: cloud is "
            "fixed at boot (no Paxos re-formation to lock against)"}


@route("POST", "/3/UnlockKeys")
def _unlock_keys(params, body):
    """water/api/UnlockKeysHandler: force-release every cooperative
    lock (admin escape hatch)."""
    dkv.unlock_everything()
    return {"__meta": {"schema_version": 3, "schema_name": "UnlockKeysV3"}}


_SESSION_PROPS: Dict[str, str] = {}


@route("GET", "/3/SessionProperties")
def _session_props_get(params, body):
    k = params.get("key")
    return {"__meta": {"schema_version": 3,
                       "schema_name": "SessionPropertyV3"},
            "key": k, "value": _SESSION_PROPS.get(k)}


@route("POST", "/3/SessionProperties")
def _session_props_set(params, body):
    k = params.get("key")
    if not k:
        raise ApiError(400, "key is required")
    _SESSION_PROPS[k] = params.get("value")
    return {"__meta": {"schema_version": 3,
                       "schema_name": "SessionPropertyV3"},
            "key": k, "value": _SESSION_PROPS.get(k)}


@route("GET", "/3/Capabilities/API")
def _capabilities_api(params, body):
    return {"__meta": {"schema_version": 3,
                       "schema_name": "CapabilitiesV3"},
            "capabilities": [
                {"name": f"{m} {rx.pattern}", "category": "API"}
                for m, rx, _ in _ROUTES]}


@route("GET", "/3/Metadata/schemas")
def _metadata_schemas_list(params, body):
    """water/api/MetadataHandler.listSchemas."""
    from h2o3_tpu.api import schemas as _sch
    return {"__meta": {"schema_version": 3, "schema_name": "MetadataV3"},
            "schemas": [{"name": n, "version": 3}
                        for n in _sch.known_schema_names()]}


@route("GET", "/3/Metadata/endpoints/{num}")
def _metadata_endpoint_one(params, body, num):
    i = int(num)
    if not (0 <= i < len(_ROUTES)):
        raise ApiError(404, f"endpoint index {i} out of range")
    m, rx, fn = _ROUTES[i]
    return {"__meta": {"schema_version": 3, "schema_name": "MetadataV3"},
            "routes": [{"http_method": m, "url_pattern": rx.pattern,
                        "summary": (fn.__doc__ or "").strip()[:200]}]}


@route("GET", "/3/Frames/{key}/light")
def _frame_light(params, body, key):
    """FramesHandler.fetchLight: schema without data pages."""
    fr = dkv.get(key, "frame")
    return {"__meta": {"schema_version": 3, "schema_name": "FramesV3"},
            "frames": [schemas.frame_v3(fr, key, row_count=0)]}


@route("GET", "/3/Frames/{key}/columns")
def _frame_columns(params, body, key):
    fr = dkv.get(key, "frame")
    return {"__meta": {"schema_version": 3, "schema_name": "FramesV3"},
            "frames": [{"frame_id": {"name": key},
                        "columns": list(fr.names)}]}


def _one_column_v3(fr, key, col, row_count=10, row_offset=0):
    if col not in fr.names:
        raise ApiError(404, f"column '{col}' not in frame '{key}'")
    return schemas.frame_v3(fr, key, row_count=row_count,
                            row_offset=row_offset,
                            column_offset=fr.names.index(col),
                            column_count=1)


@route("GET", "/3/Frames/{key}/columns/{col}")
def _frame_column(params, body, key, col):
    fr = dkv.get(key, "frame")
    return {"__meta": {"schema_version": 3, "schema_name": "FramesV3"},
            "frames": [_one_column_v3(
                fr, key, col,
                row_count=int(params.get("row_count", 10) or 10),
                row_offset=int(params.get("row_offset", 0) or 0))]}


@route("GET", "/3/Frames/{key}/columns/{col}/summary")
def _frame_column_summary(params, body, key, col):
    fr = dkv.get(key, "frame")
    return {"__meta": {"schema_version": 3, "schema_name": "FramesV3"},
            "frames": [_one_column_v3(fr, key, col)]}


@route("GET", "/3/Frames/{key}/columns/{col}/domain")
def _frame_column_domain(params, body, key, col):
    fr = dkv.get(key, "frame")
    if col not in fr.names:
        raise ApiError(404, f"column '{col}' not in frame '{key}'")
    v = fr.vec(col)
    dom = list(v.domain) if v.domain else None
    return {"__meta": {"schema_version": 3,
                       "schema_name": "FrameV3.ColV3"},
            "domain": [dom] if dom else [None],
            "map_keys": {"string": dom or []}}


@route("POST", "/3/Frames/{key}/export")
@route("POST", "/3/Frames/{key}/export/{path}/overwrite/{force}")
def _frame_export(params, body, key, path=None, force=None):
    """FramesHandler.export: write the frame as CSV at `path` (job)."""
    from h2o3_tpu.persist import export_file
    fr = dkv.get(key, "frame")
    out_path = path or params.get("path")
    if not out_path:
        raise ApiError(400, "path is required")
    frc = (str(force if force is not None
               else params.get("force", "false")).lower() == "true")
    job = Job(f"Export frame {key}")
    job.dest_key = out_path

    def body_fn(j):
        export_file(fr, out_path, force=frc)
    job.run(body_fn, background=True)
    return schemas.job_v3(job, out_path)


@route("GET", "/3/ModelMetrics")
def _model_metrics_all(params, body):
    """ModelMetricsHandler.list with no filter: every model's stored
    metrics."""
    out = []
    for key in dkv.keys("model"):
        m = dkv.get(key, "model")
        for mm in (m.training_metrics, m.validation_metrics,
                   m.cross_validation_metrics):
            if mm is not None:
                v3 = schemas._metrics_v3(
                    mm, _kind_of(m),
                    domain=list(m.response_domain or []) or None,
                    model_key=key)
                if v3:
                    out.append(v3)
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelMetricsListSchemaV3"},
            "model_metrics": out}


@route("POST", "/3/ModelMetrics/predictions_frame/{pred}/actuals_frame/{act}")
def _make_metrics(params, body, pred, act):
    """ModelMetricsHandler.make (h2o.make_metrics): metrics straight
    from a predictions frame + actuals frame, no model needed."""
    import numpy as _np

    from h2o3_tpu.models.model_base import compute_metrics
    pf = dkv.get(pred, "frame")
    af = dkv.get(act, "frame")
    domain = _coerce(params.get("domain", "null"))
    dist = (params.get("distribution") or "").lower() or None
    av = af.vec(0)
    if av.domain or domain:
        dom = list(domain or av.domain)
        nclasses = len(dom)
        if av.domain:
            yh = _np.asarray(av.to_numpy())[: af.nrow]
        else:
            lut = {d: i for i, d in enumerate(dom)}
            yh = _np.asarray(
                [lut.get(s, -1) for s in av.to_strings()[: af.nrow]])
    else:
        dom = None
        nclasses = 1
        yh = _np.asarray(av.to_numpy())[: af.nrow]
    # predictions frame: regression = 1 numeric col; classification =
    # [label, p0, p1, ...] or bare probability columns
    pcols = [pf.vec(n) for n in pf.names]
    if nclasses > 1:
        probs = [_np.asarray(v.to_numpy())[: pf.nrow]
                 for v in pcols if v.domain is None]
        if len(probs) < nclasses:
            raise ApiError(400, f"predictions frame needs {nclasses} "
                                f"probability columns")
        scores = _np.stack(probs[-nclasses:], axis=1)
    else:
        scores = _np.asarray(pcols[0].to_numpy())[: pf.nrow]
    w = _np.ones(len(yh), _np.float32)
    y_in = _np.asarray(yh, _np.float64)
    if nclasses > 1:
        # -1 marks a label outside the domain (lut miss) — excluded;
        # regression actuals pass through untouched (negatives are data)
        w[y_in == -1] = 0.0
        y_in = _np.maximum(y_in, 0)
    mm = compute_metrics(scores, y_in, w, nclasses,
                         response_domain=tuple(dom) if dom else None)
    kind = ("regression" if nclasses == 1 else
            "binomial" if nclasses == 2 else "multinomial")
    if dist in ("bernoulli",) and nclasses == 2:
        kind = "binomial"
    v3 = schemas._metrics_v3(mm, kind, domain=dom,
                             frame_key=act) or {}
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelMetricsListSchemaV3"},
            "model_metrics": v3}


@route("GET", "/3/Models.java/{model}")
def _pojo_download(params, body, model):
    """ModelsHandler.fetchJavaCode: the POJO source as java text."""
    from h2o3_tpu.genmodel import pojo_source, pojo_source_glm
    m = dkv.get(model, "model")
    try:
        src = (pojo_source_glm(m) if m.algo in ("glm",)
               else pojo_source(m))
    except (NotImplementedError, AttributeError) as e:
        raise ApiError(400, f"no POJO for algo '{m.algo}': {e}")
    return {"__raw": src.encode(), "__content_type": "text/java"}


@route("GET", "/3/Models.java/{model}/preview")
def _pojo_preview(params, body, model):
    out = _pojo_download(params, body, model)
    return {"__raw": out["__raw"][:4096], "__content_type": "text/java"}


@route("GET", "/3/Models/{model}/mojo")
@route("GET", "/99/Models.mojo/{model}")
def _mojo_download(params, body, model):
    """ModelsHandler.fetchMojo: the MOJO zip bytes (h2o-py
    model.download_mojo streams this)."""
    m = dkv.get(model, "model")
    with tempfile.TemporaryDirectory() as td:
        try:
            path = m.download_mojo(td)
        except (NotImplementedError, AttributeError) as e:
            raise ApiError(400, f"no MOJO for algo '{m.algo}': {e}")
        data = open(path, "rb").read()
    return {"__raw": data, "__content_type": "application/zip"}


@route("POST", "/3/ParseSVMLight")
def _parse_svmlight(params, body):
    """ParseHandler.parseSVMLight: svmlight files → frame (job)."""
    from h2o3_tpu.ingest.formats import parse_svmlight
    srcs = _raw_paths(_coerce(params.get("source_frames", "[]")))
    if not srcs:
        raise ApiError(400, "source_frames is required")
    dest = params.get("destination_frame") or dkv.unique_key("svmlight")
    job = Job("ParseSVMLight")
    job.dest_key = dest

    def body_fn(j):
        fr = parse_svmlight(srcs[0])
        dkv.put(dest, "frame", fr)
    job.run(body_fn, background=True)
    return schemas.job_v3(job, dest)


@route("GET", "/3/Find")
def _find(params, body):
    """water/api/FindHandler: first row >= `row` where `column`
    matches `match` (value or NA)."""
    import math as _math

    import numpy as _np
    key = _coerce(params.get("key"))
    if isinstance(key, dict):
        key = key.get("name")
    fr = dkv.get(str(key), "frame")
    col = params.get("column")
    if col not in fr.names:
        raise ApiError(404, f"column '{col}' not in frame")
    start = int(params.get("row", 0) or 0)
    match = params.get("match")
    v = fr.vec(col)
    if v.domain is not None or v.type == "str":
        vals = [None if s is None else str(s)
                for s in v.to_strings()[: fr.nrow]]
        hit = next((i for i in range(start, fr.nrow)
                    if (vals[i] is None if match in (None, "")
                        else vals[i] == match)), -1)
    else:
        a = _np.asarray(v.to_numpy())[: fr.nrow]
        if v.type == "time":
            # int64 millis with a sentinel NA (Vec.TIME_NA), not NaN
            from h2o3_tpu.frame.vec import Vec as _V
            na = a == _V.TIME_NA
            if match in (None, ""):
                idx = _np.nonzero(na[start:])[0]
            else:
                idx = _np.nonzero((a[start:] == int(float(match)))
                                  & ~na[start:])[0]
        elif match in (None, ""):
            idx = _np.nonzero(_np.isnan(a[start:]))[0]
        else:
            tgt = float(match)
            idx = _np.nonzero(a[start:] == tgt)[0] if not _math.isnan(tgt) \
                else _np.nonzero(_np.isnan(a[start:]))[0]
        hit = int(idx[0]) + start if len(idx) else -1
    if hit < 0:
        raise ApiError(404, f"no match for '{match}' in '{col}' from "
                            f"row {start}")
    return {"__meta": {"schema_version": 3, "schema_name": "FindV3"},
            "prev": -1, "next": hit}


@route("POST", "/3/MissingInserter")
def _missing_inserter(params, body):
    """water/api/MissingInserterHandler: corrupt a fraction of a frame
    to NAs in place (client test utility h2o.insert_missing_values)."""
    import numpy as _np

    from h2o3_tpu.frame.vec import Vec
    key = _coerce(params.get("dataset"))
    if isinstance(key, dict):
        key = key.get("name")
    fr = dkv.get(str(key), "frame")
    frac = float(params.get("fraction", 0.1) or 0.1)
    seed = int(params.get("seed", -1) or -1)
    rng = _np.random.default_rng(None if seed == -1 else seed)
    job = Job("MissingInserter")
    job.dest_key = str(key)

    def body_fn(j):
        from h2o3_tpu.frame.vec import T_ENUM, T_TIME
        for name in fr.names:
            v = fr.vec(name)
            if v.domain is not None:
                codes = _np.asarray(v.to_numpy(), _np.int32)[: fr.nrow]
                codes[rng.random(fr.nrow) < frac] = -1
                fr[name] = Vec.from_numpy(codes, vtype=T_ENUM,
                                          domain=v.domain)
            elif v.type == "str":
                continue              # reference skips string cols too
            elif v.type == T_TIME:
                ms = _np.asarray(v.to_numpy(), _np.int64)[: fr.nrow]
                ms[rng.random(fr.nrow) < frac] = Vec.TIME_NA
                fr[name] = Vec.from_numpy(ms, vtype=T_TIME)
            else:
                a = _np.asarray(v.to_numpy(), _np.float64)[: fr.nrow]
                a[rng.random(fr.nrow) < frac] = _np.nan
                fr[name] = Vec.from_numpy(a)
        dkv.put(str(key), "frame", fr)
    job.run(body_fn, background=True)
    return schemas.job_v3(job, str(key))


@route("GET", "/99/Rapids/help")
def _rapids_help(params, body):
    import re as _re

    import h2o3_tpu.rapids as _r
    prims = sorted(set(_re.findall(r'if op == "([^"]+)"',
                                   open(_r.__file__).read())))
    return {"__meta": {"schema_version": 99,
                       "schema_name": "RapidsHelpV3"},
            "syntax": [{"name": p} for p in prims]}


@route("GET", "/3/KillMinus3")
def _kill_minus3(params, body):
    """water/api/KillMinus3Handler (kill -3 = JVM stack dump): log the
    aggregated thread stacks, return OK."""
    from h2o3_tpu.log import info, stack_samples
    for e in stack_samples(depth=12, samples=1, interval=0.0):
        info("stack x%d:\n%s", e["count"], e["stacktrace"])
    return {"__meta": {"schema_version": 3,
                       "schema_name": "KillMinus3V3"}}


@route("GET", "/3/WaterMeterCpuTicks/{nodeidx}")
def _watermeter_cpu(params, body, nodeidx):
    """water/api/WaterMeterCpuTicksHandler: per-core cpu tick counters
    (Flow's CPU meter polls this)."""
    import psutil
    per = psutil.cpu_times(percpu=True)
    ticks = [[int(c.user * 100), int(getattr(c, "nice", 0) * 100),
              int(c.system * 100), int(c.idle * 100)] for c in per]
    return {"__meta": {"schema_version": 3,
                       "schema_name": "WaterMeterCpuTicksV3"},
            "cpu_ticks": ticks}


@route("GET", "/3/WaterMeterIo")
@route("GET", "/3/WaterMeterIo/{nodeidx}")
def _watermeter_io(params, body, nodeidx=None):
    import psutil
    io = psutil.disk_io_counters()
    return {"__meta": {"schema_version": 3,
                       "schema_name": "WaterMeterIoV3"},
            "persist_stats": [{
                "backend": "local",
                "store_bytes": int(getattr(io, "write_bytes", 0)),
                "load_bytes": int(getattr(io, "read_bytes", 0))}]}


@route("GET", "/3/NetworkTest")
def _network_test(params, body):
    """water/init/NetworkBench analog: a loopback TCP round-trip +
    bandwidth microbench (single-host cloud → one matrix cell)."""
    import socket
    import time as _t
    payload = os.urandom(1 << 20)
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    out = {}

    def _echo():
        conn, _ = srv.accept()
        with conn:
            got = 0
            while got < len(payload):
                b = conn.recv(1 << 16)
                if not b:
                    break
                got += len(b)
            conn.sendall(b"ok")
    t = threading.Thread(target=_echo, daemon=True)
    t.start()
    cli = socket.create_connection(("127.0.0.1", port))
    t0 = _t.time()
    cli.sendall(payload)
    cli.recv(2)
    dt = _t.time() - t0
    cli.close()
    srv.close()
    out["bandwidth_bytes_per_sec"] = len(payload) / max(dt, 1e-9)
    out["microseconds_collective"] = dt * 1e6
    return {"__meta": {"schema_version": 3,
                       "schema_name": "NetworkTestV3"},
            "nodes": ["tpu-controller/0"],
            "bandwidths_bytes_per_sec": [[out["bandwidth_bytes_per_sec"]]],
            "microseconds_collective": [out["microseconds_collective"]]}


@route("POST", "/3/FeatureInteraction")
def _feature_interaction_route(params, body):
    """hex/FeatureInteraction via water/api: pairwise interaction
    screen for a tree model (h2o-py model.feature_interaction)."""
    from h2o3_tpu.analytics import feature_interaction
    m = dkv.get(str(params.get("model_id")), "model")
    fkey = (params.get("frame") or params.get("frame_id")
            or getattr(m, "training_frame_key", None))
    if not fkey:
        raise ApiError(400, "frame is required (model has no recorded "
                            "training_frame_key)")
    fr = dkv.get(str(fkey), "frame")
    rows = feature_interaction(
        m, fr, max_pairs=int(params.get("max_interaction_depth", 10)
                             or 10))
    return {"__meta": {"schema_version": 3,
                       "schema_name": "FeatureInteractionV3"},
            "feature_interaction": rows}


@route("POST", "/3/SignificantRules")
def _significant_rules(params, body):
    """hex/rulefit SignificantRulesHandler: the nonzero-coefficient
    rule table of a RuleFit model."""
    m = dkv.get(str(params.get("model_id")), "model")
    if m.algo != "rulefit":
        raise ApiError(400, f"model '{m.key}' is {m.algo}, not rulefit")
    imp = m.rule_importance()
    return {"__meta": {"schema_version": 3,
                       "schema_name": "SignificantRulesV3"},
            "significant_rules_table": imp}


@route("POST", "/3/Recovery/resume")
def _recovery_resume(params, body):
    """hex/faulttolerance/Recovery: after a crash, reload every model
    artifact a recovery_dir holds back into the DKV (grid manifests +
    AutoML state files both point at artifacts saved there); training
    re-issued against the same recovery_dir then resumes from them."""
    from h2o3_tpu.persist import load_model
    rdir = params.get("recovery_dir")
    if not rdir or not os.path.isdir(rdir):
        raise ApiError(400, f"recovery_dir '{rdir}' does not exist")
    restored = []
    for mf in sorted(os.listdir(rdir)):
        if not mf.endswith(".json"):
            continue
        try:
            with open(os.path.join(rdir, mf)) as f:
                manifest = json.load(f)
        except (json.JSONDecodeError, OSError):
            continue
        arts = manifest.get("completed", {})
        if isinstance(arts, dict):
            for art in arts.values():
                try:
                    model = load_model(art)
                    dkv.put(model.key, "model", model)
                    restored.append(model.key)
                except Exception:      # noqa: BLE001 - partial restore
                    continue
    return {"__meta": {"schema_version": 3, "schema_name": "RecoveryV3"},
            "restored_models": restored}


@route("POST", "/99/DCTTransformer")
def _dct_transformer(params, body):
    """util/DCTTransformer (TabToDct): per-row 2D DCT-II of
    [height x width x depth]-shaped rows. TPU re-design: the DCT is two
    dense cosine-matrix matmuls (MXU) instead of a per-chunk FFT."""
    import jax.numpy as jnp
    import numpy as _np
    key = _coerce(params.get("dataset"))
    if isinstance(key, dict):
        key = key.get("name")
    fr = dkv.get(str(key), "frame")
    dims = _coerce(params.get("dimensions", "[0,0,1]")) or [0, 0, 1]
    h, w_, d = (int(dims[0]) or 1), (int(dims[1]) or 1), (int(dims[2])
                                                          or 1)
    if h * w_ * d != fr.ncol:
        raise ApiError(400, f"dimensions {dims} do not multiply to "
                            f"ncol={fr.ncol}")
    dest = params.get("destination_frame") or dkv.unique_key("dct")

    def dct_mat(n):
        k = _np.arange(n)[:, None]
        i = _np.arange(n)[None, :]
        M = _np.sqrt(2.0 / n) * _np.cos(_np.pi * (2 * i + 1) * k /
                                        (2.0 * n))
        M[0] *= 1.0 / _np.sqrt(2.0)
        return jnp.asarray(M, jnp.float32)

    job = Job("DCTTransformer")
    job.dest_key = dest

    def body_fn(j):
        from h2o3_tpu.frame.frame import Frame
        from h2o3_tpu.frame.vec import Vec
        X = jnp.asarray(_np.nan_to_num(_np.asarray(
            fr.as_matrix()))[: fr.nrow]).reshape(fr.nrow, h, w_, d)
        Dh, Dw = dct_mat(h), dct_mat(w_)
        # rows x [h, w, d] -> DCT over h and w axes per depth slice
        Y = jnp.einsum("ab,rbwd->rawd", Dh, X)
        Z = jnp.einsum("cw,rawd->racd", Dw, Y)
        out = _np.asarray(Z.reshape(fr.nrow, -1))
        names = [f"C{i + 1}" for i in range(out.shape[1])]
        dkv.put(dest, "frame", Frame(
            names,
            [Vec.from_numpy(out[:, i]) for i in range(out.shape[1])]))
    job.run(body_fn, background=True)
    return schemas.job_v3(job, dest)


_NPS_ROOT = os.path.join(tempfile.gettempdir(), "h2o3_nps")


def _nps_path(cat: str, name: str = None) -> str:
    """Traversal-safe NPS path: route segments arrive URL-DECODED, so
    '..%2F..' style names must be rejected on every verb, not just
    POST."""
    for part in (cat,) + ((name,) if name is not None else ()):
        if (not part or "/" in part or "\\" in part or ".." in part
                or os.path.isabs(part)):
            raise ApiError(400, f"invalid category/name '{part}'")
    return os.path.join(_NPS_ROOT, cat, *((name,) if name is not None
                                          else ()))


@route("GET", "/3/NodePersistentStorage/configured")
def _nps_configured(params, body):
    return {"__meta": {"schema_version": 3,
                       "schema_name": "NodePersistentStorageV3"},
            "configured": True}


@route("GET", "/3/NodePersistentStorage/categories/{cat}/exists")
def _nps_cat_exists(params, body, cat):
    return {"__meta": {"schema_version": 3,
                       "schema_name": "NodePersistentStorageV3"},
            "exists": os.path.isdir(_nps_path(cat))}


@route("GET",
       "/3/NodePersistentStorage/categories/{cat}/names/{name}/exists")
def _nps_exists(params, body, cat, name):
    return {"__meta": {"schema_version": 3,
                       "schema_name": "NodePersistentStorageV3"},
            "exists": os.path.isfile(_nps_path(cat, name))}


@route("GET", "/3/NodePersistentStorage/{cat}")
def _nps_list(params, body, cat):
    """water/api/NodePersistentStorageHandler (Flow stores notebooks
    here): list entries of a category."""
    d = _nps_path(cat)
    entries = []
    if os.path.isdir(d):
        for n in sorted(os.listdir(d)):
            p = os.path.join(d, n)
            entries.append({"name": n, "size": os.path.getsize(p),
                            "timestamp_millis": int(
                                os.path.getmtime(p) * 1000)})
    return {"__meta": {"schema_version": 3,
                       "schema_name": "NodePersistentStorageV3"},
            "category": cat, "entries": entries}


@route("GET", "/3/NodePersistentStorage/{cat}/{name}")
def _nps_get(params, body, cat, name):
    p = _nps_path(cat, name)
    if not os.path.isfile(p):
        raise ApiError(404, f"no NPS entry {cat}/{name}")
    return {"__raw": open(p, "rb").read(),
            "__content_type": "application/octet-stream"}


@route("POST", "/3/NodePersistentStorage/{cat}/{name}")
def _nps_put(params, body, cat, name):
    d = _nps_path(cat)
    _nps_path(cat, name)
    os.makedirs(d, exist_ok=True)
    data = body if isinstance(body, (bytes, bytearray)) else \
        (params.get("value") or "").encode()
    with open(os.path.join(d, name), "wb") as f:
        f.write(data or b"")
    return {"__meta": {"schema_version": 3,
                       "schema_name": "NodePersistentStorageV3"},
            "category": cat, "name": name}


@route("DELETE", "/3/NodePersistentStorage/{cat}/{name}")
def _nps_delete(params, body, cat, name):
    p = _nps_path(cat, name)
    if os.path.isfile(p):
        os.unlink(p)
    return {"__meta": {"schema_version": 3,
                       "schema_name": "NodePersistentStorageV3"}}


@route("POST", "/99/ImportSQLTable")
def _import_sql_table_route(params, body):
    """water/jdbc/SQLManager route (h2o.import_sql_table): DB-API
    import. sqlite:///path URLs work out of the box (stdlib driver);
    other engines need their driver package installed."""
    from h2o3_tpu.ingest.sql import import_sql_table
    url = params.get("connection_url") or ""
    table = params.get("table")
    if not table:
        raise ApiError(400, "table is required")
    if url.startswith(("sqlite:///", "jdbc:sqlite:")):
        if url.startswith("jdbc:"):
            # jdbc:sqlite:/abs/path or jdbc:sqlite:rel.db — verbatim
            dbpath = url[len("jdbc:sqlite:"):]
        else:
            # sqlite:///abs/path (3 slashes = absolute, SQLAlchemy form)
            dbpath = "/" + url[len("sqlite:///"):]
        import sqlite3

        def factory():
            return sqlite3.connect(dbpath)
    else:
        raise ApiError(501, f"no DB-API driver wired for '{url}' in "
                            f"this image (sqlite:/// is built in)")
    cols = _coerce(params.get("columns", "null"))
    dest = params.get("destination_frame") or dkv.unique_key("sql")
    job = Job("ImportSQLTable")
    job.dest_key = dest

    def body_fn(j):
        fr = import_sql_table(factory, table, columns=cols or None)
        dkv.put(dest, "frame", fr)
    job.run(body_fn, background=True)
    return schemas.job_v3(job, dest)


@route("POST", "/99/Sample")
def _sample_frame(params, body):
    """99/Sample: uniform row sample of a frame into a new key."""
    import numpy as _np
    key = _coerce(params.get("dataset"))
    if isinstance(key, dict):
        key = key.get("name")
    fr = dkv.get(str(key), "frame")
    n = int(params.get("rows", 0) or 0)
    if n <= 0 or n >= fr.nrow:
        raise ApiError(400, f"rows must be in (0, {fr.nrow})")
    seed = int(params.get("seed", -1) or -1)
    rng = _np.random.default_rng(None if seed == -1 else seed)
    sel = _np.sort(rng.choice(fr.nrow, size=n, replace=False))
    sub = fr.rows(sel)
    dest = params.get("destination_frame") or dkv.unique_key("sample")
    dkv.put(dest, "frame", sub)
    return {"__meta": {"schema_version": 99, "schema_name": "SampleV3"},
            "destination_frame": dest, "rows": n}


@route("POST", "/3/ImportHiveTable")
@route("POST", "/3/SaveToHiveTable")
def _hive_gate(params, body):
    raise ApiError(501, "Hive import/export needs a Hive metastore + "
                        "HDFS environment this image does not ship "
                        "(reference: h2o-hive); use JDBC "
                        "(/99/ImportSQLTable) or file ingest instead")


@route("POST", "/3/DecryptionSetup")
def _decryption_gate(params, body):
    raise ApiError(501, "encrypted-file ingest (water/parser/"
                        "DecryptionTool) is not wired in this build; "
                        "decrypt files before import")


@route("GET", "/3/h2o-genmodel.jar")
def _genmodel_jar(params, body):
    raise ApiError(501, "h2o-genmodel.jar is a JVM artifact this "
                        "TPU-native build does not ship; score POJO/"
                        "MOJO artifacts with h2o3_tpu.genmodel "
                        "(EasyPredict) or pass get_jar=False to "
                        "download_pojo")


@route("POST", "/99/Assembly")
def _assembly_fit(params, body):
    """water/api/AssemblyHandler.fit: replay munging steps (h2o-py
    H2OAssembly.fit) against a frame; returns assembly + result keys."""
    from h2o3_tpu.assembly import Assembly, parse_steps
    steps = parse_steps(params.get("steps") or "[]")
    fkey = str(params.get("frame"))
    try:
        fr = dkv.get(fkey, "frame")
    except KeyError:
        raise ApiError(404, f"frame '{fkey}' not found")
    akey = dkv.unique_key("assembly")
    asm = Assembly(akey, steps)
    out = asm.fit(fr)
    rkey = dkv.unique_key("assembly_result")
    dkv.put(rkey, "frame", out)
    dkv.put(akey, "assembly", asm)
    return {"__meta": {"schema_version": 99, "schema_name": "AssemblyV99"},
            "assembly": {"name": akey, "type": "Key<Assembly>"},
            "result": {"name": rkey, "type": "Key<Frame>"}}


@route("GET", "/99/Assembly.java/{aid}/{pojo_name}")
def _assembly_java(params, body, aid, pojo_name):
    """AssemblyHandler.toJava: the munging POJO source."""
    try:
        asm = dkv.get(aid, "assembly")
    except KeyError:
        raise ApiError(404, f"assembly '{aid}' not found")
    try:
        src = asm.to_java(pojo_name)
    except NotImplementedError as e:
        raise ApiError(501, str(e))
    return {"__raw": src.encode(), "__content_type": "text/java"}


@route("GET", "/3/Logs/nodes/{nodeidx}/files/{name}")
def _logs_file(params, body, nodeidx, name):
    """water/api/LogsHandler.fetch: a node's named log. One controller
    process here; every name view serves the in-memory ring buffer
    (water/util/Log analog in log.py)."""
    from h2o3_tpu.log import buffered_lines
    return {"__meta": {"schema_version": 3, "schema_name": "LogsV3"},
            "nodeidx": int(nodeidx), "name": name,
            "log": "\n".join(buffered_lines(5000))}


@route("GET", "/3/ModelBuilders/{algo}/model_id")
def _next_model_id(params, body, algo):
    """ModelBuildersHandler.calcModelId: a fresh unique model id."""
    if algo not in _builders():
        raise ApiError(404, f"unknown algorithm '{algo}'")
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelIdV3"},
            "model_id": {"name": dkv.unique_key(f"{algo}_model")}}


@route("POST", "/3/ModelBuilders/{algo}/parameters")
def _validate_parameters(params, body, algo):
    """ModelBuilderHandler.validate_parameters (Flow form validation):
    typed-coerce + construct the builder WITHOUT training; returns
    per-field messages + error_count."""
    builders = _builders()
    if algo not in builders:
        raise ApiError(404, f"unknown algorithm '{algo}'")
    defaults = builders[algo]().params
    messages = []
    parms = {}
    for k, v in params.items():
        if k in ("_rest_version", "model_id", "training_frame",
                 "validation_frame", "response_column"):
            continue
        if k not in defaults:
            messages.append({"message_type": "WARN", "field_name": k,
                             "message": f"unknown parameter '{k}' for "
                                        f"algo '{algo}'"})
            continue
        got = _coerce_typed(k, v, defaults)
        d = defaults.get(k)
        # strict check: _coerce_typed falls back to guessing instead of
        # raising, so validate the COERCED value against the declared
        # type here (bool is an int subtype — test it first)
        ok = True
        if isinstance(d, bool):
            ok = isinstance(got, bool)
        elif isinstance(d, (int, float)):
            ok = isinstance(got, (int, float)) \
                and not isinstance(got, bool) or got is None
        elif isinstance(d, (list, tuple)):
            ok = isinstance(got, (list, tuple)) or got is None
        if not ok:
            messages.append({
                "message_type": "ERRR", "field_name": k,
                "message": f"cannot parse '{v}' as "
                           f"{type(d).__name__} (default {d!r})"})
        else:
            parms[k] = got
    if not any(m["message_type"] == "ERRR" for m in messages):
        try:
            builders[algo](**parms)
        except Exception as e:  # noqa: BLE001 - surfaced as validation
            messages.append({"message_type": "ERRR",
                             "field_name": "_parms", "message": str(e)})
    errs = sum(1 for m in messages if m["message_type"] == "ERRR")
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelParametersSchemaV3"},
            "messages": messages, "error_count": errs}


@route("GET", "/3/FrameChunks/{frame_id}")
def _frame_chunks(params, body, frame_id):
    """water/api/FrameChunksHandler: the frame's physical distribution.
    Chunks map to mesh-shard row ranges in this design (SURVEY §2.5:
    rows shard over the 'data' axis; each shard is one 'chunk')."""
    from h2o3_tpu.parallel.mesh import current_mesh
    fr = dkv.get(frame_id, "frame")
    mesh = current_mesh()
    n_shards = int(mesh.shape.get("data", 1)) if mesh is not None else 1
    per = -(-fr.nrow // max(n_shards, 1))
    chunks = [{"chunk_id": i,
               "row_count": max(0, min(per, fr.nrow - i * per)),
               "node_idx": i}
              for i in range(n_shards)]
    return {"__meta": {"schema_version": 3,
                       "schema_name": "FrameChunksV3"},
            "frame_id": {"name": frame_id},
            "chunks": [c for c in chunks if c["row_count"] > 0]}


@route("GET", "/3/SteamMetrics")
def _steam_metrics(params, body):
    """water/api/SteamMetricsHandler: Enterprise Steam keepalive
    metrics — no Steam in this deployment, report idle truthfully."""
    return {"__meta": {"schema_version": 3,
                       "schema_name": "SteamMetricsV3"},
            "idle_millis": schemas.uptime_ms()}


@route("GET", "/3/Metadata/schemaclasses/{classname}")
def _metadata_schemaclass(params, body, classname):
    """MetadataHandler.fetchSchemaMetadataByClass — same payload as
    /3/Metadata/schemas/{name} (one schema namespace here)."""
    return _schema_meta(params, body, classname)


@route("GET", "/3/ModelMetrics/frames/{frame}")
def _metrics_by_frame(params, body, frame):
    """ModelMetricsHandler.list filtered by frame: stored metrics for
    every model that scored this frame (training-frame metrics here —
    the single-controller store does not index ad-hoc scores)."""
    try:
        dkv.get(frame, "frame")
    except KeyError:
        raise ApiError(404, f"frame '{frame}' not found")
    out = []
    for key in dkv.keys("model"):
        m = dkv.get(key, "model")
        if getattr(m, "training_frame_key", None) != frame:
            continue
        if m.training_metrics is not None:
            v3 = schemas._metrics_v3(
                m.training_metrics, _kind_of(m),
                domain=list(m.response_domain or []) or None,
                frame_key=frame, model_key=key)
            if v3:
                out.append(v3)
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelMetricsListSchemaV3"},
            "model_metrics": out}


@route("POST", "/3/ModelMetrics/frames/{frame}/models/{model}")
def _metrics_frame_model(params, body, frame, model):
    """Frame-first spelling of models/{model}/frames/{frame} (POST =
    score)."""
    return _model_metrics_score(params, body, model, frame)


@route("GET", "/3/ModelMetrics/frames/{frame}/models/{model}")
def _metrics_frame_model_fetch(params, body, frame, model):
    """GET = fetch STORED metrics only (ModelMetricsHandler.fetch) —
    no scoring pass, works on frames lacking the response column."""
    m = dkv.get(model, "model")
    out = []
    for mm in (m.training_metrics, m.validation_metrics,
               m.cross_validation_metrics):
        if mm is not None:
            v3 = schemas._metrics_v3(
                mm, _kind_of(m),
                domain=list(m.response_domain or []) or None,
                frame_key=frame, model_key=model)
            if v3:
                out.append(v3)
    return {"__meta": {"schema_version": 3,
                       "schema_name": "ModelMetricsListSchemaV3"},
            "model_metrics": out}


@route("GET", "/3/Models.fetch.bin/{model}")
def _fetch_model_bin(params, body, model):
    """ModelsHandler.fetchBinaryModel: stream the binary artifact
    (h2o.download_model)."""
    from h2o3_tpu.persist import save_model
    m = dkv.get(model, "model")
    with tempfile.TemporaryDirectory() as td:
        path = save_model(m, path=td, force=True, filename=model)
        data = open(path, "rb").read()
    return {"__raw": data, "__content_type": "application/octet-stream"}


@route("POST", "/99/Models.upload.bin/{model}")
@route("POST", "/99/Models.upload.bin/")
def _upload_model_bin(params, body, model=None):
    """ModelsHandler.uploadBinaryModel (h2o.upload_model): body bytes →
    artifact → live model in the DKV."""
    from h2o3_tpu.persist import load_model
    if not body:
        raise ApiError(400, "binary model body required")
    # accept the client's multipart envelope too (h2o.upload_model posts
    # a file upload): find the zip magic and strip everything before it,
    # and the trailing boundary after the payload
    if body[:2] != b"PK":
        start = body.find(b"PK\x03\x04")
        if start < 0:
            raise ApiError(400, "no zip artifact in request body")
        end = body.rfind(b"\r\n--")
        body = body[start:end if end > start else len(body)]
    with tempfile.TemporaryDirectory() as td:
        p = os.path.join(td, "upload.zip")
        with open(p, "wb") as f:
            f.write(body)
        try:
            m = load_model(p)
        except Exception as e:  # noqa: BLE001 - bad artifact → 400
            raise ApiError(400, f"not a model artifact: {e}")
    if model:
        m.key = model
    dkv.put(m.key, "model", m)
    return {"__meta": {"schema_version": 99, "schema_name": "ModelsV3"},
            "models": [{"model_id": {"name": m.key}}]}


@route("GET", "/99/Models/{key}/json")
def _model_json(params, body, key):
    """ModelsHandler.fetch with full output (the /99 'json' spelling
    Flow downloads)."""
    m = dkv.get(key, "model")
    return {"__meta": {"schema_version": 99, "schema_name": "ModelsV3"},
            "models": [schemas.model_v3(m, key)]}
