"""genmodel breadth: non-tree MOJO writers/readers, POJO codegen, and the
EasyPredict row API.

Reference wire formats (re-derived from the READERS, not copied):
- GLM MOJO 1.00 — hex/genmodel/algos/glm/GlmMojoReader.java kv set
  (use_all_factor_levels, cats, cat_modes, cat_offsets, nums, num_means,
  mean_imputation, beta, family, link) and GlmMojoModelBase.score0's beta
  layout: per-cat indicator blocks first (skipping level 0 when
  use_all_factor_levels=false), then numerics, intercept LAST; data rows
  arrive cats-first (DataInfo column reordering).
- KMeans MOJO 1.00 — algos/kmeans/KMeansMojoReader.java (standardize,
  standardize_means/mults/modes, center_num, center_i arrays).
- DeepLearning MOJO 1.10 — algos/deeplearning/DeeplearningMojoReader.java
  (nums/cats/cat_offsets/norm_mul/norm_sub/activation/
  neural_network_sizes, weight_layer{i}/bias_layer{i}).
- POJO codegen — hex/tree/TreeJCodeGen.java emits one Java class per
  model with nested if/else per tree; we emit the same *shape* of source
  (compile-checked only when a JDK exists; golden-file otherwise).
- EasyPredict row API — hex/genmodel/easy/EasyPredictModelWrapper.java
  (RowData dict → typed prediction).

Array kv values use Java's Arrays.toString format ("[a, b, c]"), the
format AbstractMojoWriter.writekv emits and ModelMojoReader parses.
"""
from __future__ import annotations

import uuid as _uuid
import zipfile
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


def _jarr(vals, quote: bool = False) -> str:
    if quote:
        # JSON-escape so names containing '"' or ',' roundtrip
        import json
        return "[" + ", ".join(json.dumps(str(v)) for v in vals) + "]"
    return "[" + ", ".join(str(v) for v in vals) + "]"


def _parse_jarr(s: str, typ=float):
    s = s.strip()
    if '"' in s:
        # quoted string array — written JSON-escaped by _jarr
        import json
        return [typ(v) for v in json.loads(s)]
    if s.startswith("["):
        s = s[1:-1]
    return [typ(v.strip()) for v in s.split(",") if v.strip()]


def _split_design(model):
    """Cats-first column reordering (DataInfo): returns (cat_idx,
    num_idx) into model.feature_names."""
    cat_idx = [i for i, c in enumerate(model.feature_is_cat) if c]
    num_idx = [i for i, c in enumerate(model.feature_is_cat) if not c]
    return cat_idx, num_idx


def _beta_glm_layout(model) -> Tuple[np.ndarray, List[int], List[float]]:
    """Map our expand_design-ordered beta (original column order, enum
    blocks inline) to the genmodel layout: cat blocks first, then nums,
    intercept last. Returns (beta, cat_offsets, num_means)."""
    cat_idx, num_idx = _split_design(model)
    names = model.feature_names
    # index our exp_names: cat level j of col n is "n.<lvl>"; numeric is n
    pos = {n: i for i, n in enumerate(model.exp_names)}
    beta_src = np.asarray(model.beta, dtype=np.float64)
    out: List[float] = []
    cat_offsets = [0]
    for ci in cat_idx:
        n = names[ci]
        dom = list(model.cat_domains.get(n, ()))
        for lvl in dom[1:]:                     # level 0 skipped
            key = f"{n}.{lvl}"
            out.append(float(beta_src[pos[key]]) if key in pos else 0.0)
        cat_offsets.append(cat_offsets[-1] + max(len(dom) - 1, 0))
    num_means = []
    for ni in num_idx:
        n = names[ni]
        out.append(float(beta_src[pos[n]]))
        num_means.append(float(model.impute_means.get(n, 0.0)))
    out.append(float(model.intercept_value))
    return np.asarray(out), cat_offsets, num_means


def _ini_header(model, algo: str, algorithm: str, category: str,
                columns: List[str], mojo_version: str,
                extra_kv: List[str]) -> Tuple[str, List[Tuple[str, List[str]]]]:
    n_features = len(columns) - (1 if model.response else 0)
    ini = ["[info]",
           "h2o_version = 3.46.0.1",
           f"mojo_version = {mojo_version}",
           "license = Apache License Version 2.0",
           f"algo = {algo}",
           f"algorithm = {algorithm}",
           f"category = {category}",
           f"uuid = {int(_uuid.uuid4()) % (1 << 63)}",
           f"supervised = {'true' if model.response else 'false'}",
           f"n_features = {n_features}",
           f"n_classes = {max(model.nclasses, 1)}",
           f"n_columns = {len(columns)}",
           "balance_classes = false",
           "default_threshold = 0.5",
           "prior_class_distrib = null",
           "model_class_distrib = null",
           "timestamp = 2026-01-01 00:00:00",
           "escape_domain_values = false",
           "_genmodel_encoding = AUTO",
           ] + extra_kv
    dom_lines = ["", "[columns]"] + columns + ["", "[domains]"]
    dom_files: List[Tuple[str, List[str]]] = []
    di = 0
    for ci, name in enumerate(columns):
        dom = None
        if name == model.response and model.response_domain:
            dom = list(model.response_domain)
        elif name in model.cat_domains:
            dom = list(model.cat_domains[name])
        if dom:
            fn = f"d{di:03d}.txt"
            dom_lines.append(f"{ci}: {len(dom)} {fn}")
            dom_files.append((fn, dom))
            di += 1
    return "\n".join(ini + dom_lines) + "\n", dom_files


def _write_zip(path: str, ini_text: str,
               dom_files: List[Tuple[str, List[str]]],
               blobs: Optional[Dict[str, bytes]] = None) -> str:
    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as zf:
        zf.writestr("model.ini", ini_text)
        for fn, dom in dom_files:
            zf.writestr(f"domains/{fn}",
                        "\n".join(str(d) for d in dom) + "\n")
        for name, data in (blobs or {}).items():
            zf.writestr(name, data)
    return path


# ---------------- GLM ---------------------------------------------------

def export_mojo_glm(model, path: str) -> str:
    if model.family == "multinomial":
        raise ValueError("multinomial GLM MOJO export not supported yet")
    cat_idx, num_idx = _split_design(model)
    names = model.feature_names
    beta, cat_offsets, num_means = _beta_glm_layout(model)
    cat_modes = [0] * len(cat_idx)
    columns = ([names[i] for i in cat_idx] + [names[i] for i in num_idx]
               + ([model.response] if model.response else []))
    link = {"gaussian": "identity", "binomial": "logit", "poisson": "log",
            "gamma": "log"}[model.family]
    extra = [
        "use_all_factor_levels = false",
        f"cats = {len(cat_idx)}",
        f"cat_modes = {_jarr(cat_modes)}",
        f"cat_offsets = {_jarr(cat_offsets)}",
        f"nums = {len(num_idx)}",
        f"num_means = {_jarr(num_means)}",
        "mean_imputation = true",
        f"beta = {_jarr(beta.tolist())}",
        f"family = {model.family}",
        f"link = {link}",
        "tweedie_link_power = 0.0",
    ]
    ini, doms = _ini_header(model, "glm", "Generalized Linear Model",
                            "Binomial" if model.nclasses == 2
                            else "Regression", columns, "1.00", extra)
    return _write_zip(path, ini, doms)


class GlmMojoScorer:
    """Standalone scorer for a GLM MOJO (GlmMojoModel.glmScore0)."""

    def __init__(self, kv: Dict[str, str], columns, domains, response):
        self.cats = int(kv["cats"])
        self.nums = int(kv["nums"])
        self.cat_offsets = _parse_jarr(kv["cat_offsets"], int)
        self.cat_modes = _parse_jarr(kv.get("cat_modes", "[]"), int)
        self.num_means = _parse_jarr(kv.get("num_means", "[]"), float)
        self.beta = np.asarray(_parse_jarr(kv["beta"], float))
        self.family = kv["family"]
        self.link = kv.get("link", "identity")
        self.columns = columns
        self.domains = domains
        self.response = response
        self.nclasses = 2 if self.family == "binomial" else 1

    def score(self, row: np.ndarray) -> np.ndarray:
        data = np.asarray(row, dtype=np.float64).copy()
        for i in range(self.cats):
            if np.isnan(data[i]):
                data[i] = self.cat_modes[i]
        for i in range(self.nums):
            if np.isnan(data[self.cats + i]):
                data[self.cats + i] = self.num_means[i]
        eta = 0.0
        for i in range(self.cats):
            code = int(data[i])
            if code != 0:               # level 0 skipped
                ival = self.cat_offsets[i] + code - 1
                if ival < self.cat_offsets[i + 1]:
                    eta += self.beta[ival]
        noff = self.cat_offsets[self.cats] if self.cats else 0
        for i in range(self.nums):
            eta += self.beta[noff + i] * data[self.cats + i]
        eta += self.beta[-1]
        mu = {"identity": lambda e: e,
              "logit": lambda e: 1.0 / (1.0 + np.exp(-e)),
              "log": np.exp}[self.link](eta)
        if self.family == "binomial":
            return np.array([float(mu > 0.5), 1.0 - mu, mu])
        return np.array([mu])


# ---------------- KMeans ------------------------------------------------

def export_mojo_kmeans(model, path: str) -> str:
    # our KMeans trains on the expanded standardized design; centers_raw
    # are in expanded-column space (exp_names)
    columns = list(model.feature_names)
    centers = np.asarray(model.centers_raw, dtype=np.float64)
    means = np.asarray(model.xm, dtype=np.float64)
    mults = 1.0 / np.maximum(np.asarray(model.xs, dtype=np.float64), 1e-12)
    extra = [
        "standardize = true",
        f"standardize_means = {_jarr(means.tolist())}",
        f"standardize_mults = {_jarr(mults.tolist())}",
        f"standardize_modes = {_jarr([0] * len(means))}",
        f"center_num = {centers.shape[0]}",
    ]
    extra += [f"center_{i} = {_jarr(c.tolist())}"
              for i, c in enumerate(centers)]
    ini, doms = _ini_header(model, "kmeans", "K-means", "Clustering",
                            columns, "1.00", extra)
    return _write_zip(path, ini, doms)


class KMeansMojoScorer:
    def __init__(self, kv: Dict[str, str], columns, domains, response):
        self.standardize = kv.get("standardize", "true") == "true"
        self.means = np.asarray(_parse_jarr(kv["standardize_means"]))
        self.mults = np.asarray(_parse_jarr(kv["standardize_mults"]))
        n = int(kv["center_num"])
        self.centers = np.stack([
            np.asarray(_parse_jarr(kv[f"center_{i}"])) for i in range(n)])
        self.nclasses = 1
        self.columns = columns

    def score(self, row: np.ndarray) -> np.ndarray:
        x = np.asarray(row, dtype=np.float64)
        x = np.where(np.isnan(x), self.means, x)
        xs = (x - self.means) * self.mults if self.standardize else x
        cs = (self.centers - self.means[None, :]) * self.mults[None, :] \
            if self.standardize else self.centers
        d = ((cs - xs[None, :]) ** 2).sum(1)
        return np.array([float(np.argmin(d))])


# ---------------- DeepLearning -----------------------------------------

def export_mojo_deeplearning(model, path: str) -> str:
    """MLP MOJO (mojo 1.10 kv set). Our net: list of (W [in, out], b)
    float32; genmodel stores row-major [out*in] weight blobs per layer."""
    if model.task == "autoencoder":
        raise ValueError("autoencoder MOJO export not supported")
    cat_idx, num_idx = _split_design(model)
    names = model.feature_names
    columns = ([names[i] for i in cat_idx] + [names[i] for i in num_idx]
               + ([model.response] if model.response else []))
    # expanded design is standardized over ALL expanded cols; genmodel
    # normalizes only numerics (norm_sub/mul over nums) — we export the
    # expanded-space stats and mark all expanded cols numeric-like via
    # cat_offsets on the ORIGINAL enum blocks
    pos = {n: i for i, n in enumerate(model.exp_names)}
    cat_offsets = [0]
    perm: List[int] = []
    for ci in cat_idx:
        n = names[ci]
        dom = list(model.cat_domains.get(n, ()))
        block = [pos[f"{n}.{lvl}"] for lvl in dom[1:] if f"{n}.{lvl}" in pos]
        perm.extend(block)
        cat_offsets.append(cat_offsets[-1] + len(block))
    num_perm = [pos[names[ni]] for ni in num_idx]
    perm_all = perm + num_perm
    xm = np.asarray(model.xm, dtype=np.float64)
    xs = np.asarray(model.xs, dtype=np.float64)
    units = [len(perm_all)] + list(model.hidden) + [
        model.nclasses if model.nclasses > 1 else 1]
    act_map = {"rectifier": "Rectifier", "tanh": "Tanh", "maxout": "Maxout"}
    extra = [
        "mini_batch_size = 1",
        f"nums = {len(num_idx)}",
        f"cats = {len(cat_idx)}",
        f"cat_offsets = {_jarr(cat_offsets)}",
        f"norm_mul = {_jarr((1.0 / np.maximum(xs[perm_all], 1e-12)).tolist())}",
        f"norm_sub = {_jarr(xm[perm_all].tolist())}",
        "norm_resp_mul = null",
        "norm_resp_sub = null",
        "use_all_factor_levels = false",
        f"activation = {act_map.get(model.activation, 'Rectifier')}",
        f"distribution = {model.dist_name}",
        "mean_imputation = true",
        f"cat_modes = {_jarr([0] * len(cat_idx))}",
        f"neural_network_sizes = {_jarr(units)}",
        f"hidden_dropout_ratios = {_jarr([0.0] * len(model.hidden))}",
    ]
    # weights: reorder input layer rows by perm_all (original exp order →
    # cats-first order); genmodel blob is row-major [out, in]
    for li, layer in enumerate(model.net):
        Wn = np.asarray(layer["W"], dtype=np.float64)
        b = np.asarray(layer["b"], dtype=np.float64).reshape(-1)
        if li == 0:
            Wn = Wn[np.asarray(perm_all)]
        extra.append(f"weight_layer{li} = {_jarr(Wn.T.reshape(-1).tolist())}")
        extra.append(f"bias_layer{li} = {_jarr(b.tolist())}")
    ini, doms = _ini_header(
        model, "deeplearning", "Deep Learning", "Binomial"
        if model.nclasses == 2 else "Multinomial" if model.nclasses > 2
        else "Regression", columns, "1.10", extra)
    return _write_zip(path, ini, doms)


class DeepLearningMojoScorer:
    def __init__(self, kv: Dict[str, str], columns, domains, response):
        self.cats = int(kv["cats"])
        self.nums = int(kv["nums"])
        self.cat_offsets = _parse_jarr(kv["cat_offsets"], int)
        self.norm_mul = np.asarray(_parse_jarr(kv["norm_mul"]))
        self.norm_sub = np.asarray(_parse_jarr(kv["norm_sub"]))
        self.units = _parse_jarr(kv["neural_network_sizes"], int)
        self.activation = kv["activation"]
        self.distribution = kv.get("distribution", "gaussian")
        self.layers = []
        for li in range(len(self.units) - 1):
            w = np.asarray(_parse_jarr(kv[f"weight_layer{li}"]))
            b = np.asarray(_parse_jarr(kv[f"bias_layer{li}"]))
            self.layers.append(
                (w.reshape(self.units[li + 1], self.units[li]), b))
        self.columns = columns
        self.domains = domains
        k = self.units[-1]
        self.nclasses = k if k > 1 else 1

    def score(self, row: np.ndarray) -> np.ndarray:
        data = np.asarray(row, dtype=np.float64)
        vec = np.zeros(self.units[0])
        for i in range(self.cats):
            code = int(data[i]) if np.isfinite(data[i]) else 0
            if code != 0:
                ival = self.cat_offsets[i] + code - 1
                if ival < self.cat_offsets[i + 1]:
                    vec[ival] = 1.0
        noff = self.cat_offsets[self.cats] if self.cats else 0
        for i in range(self.nums):
            v = data[self.cats + i]
            vec[noff + i] = 0.0 if not np.isfinite(v) else v
        vec = (vec - self.norm_sub) * self.norm_mul
        h = vec
        for li, (W, b) in enumerate(self.layers):
            h = W @ h + b
            if li < len(self.layers) - 1:
                if self.activation == "Tanh":
                    h = np.tanh(h)
                else:
                    h = np.maximum(h, 0.0)
        if self.nclasses > 1:
            e = np.exp(h - h.max())
            p = e / e.sum()
            return np.concatenate([[float(np.argmax(p))], p])
        if self.distribution == "bernoulli":
            p1 = 1.0 / (1.0 + np.exp(-h[0]))
            return np.array([float(p1 > 0.5), 1 - p1, p1])
        return np.array([h[0]])


# ---------------- POJO codegen (TreeJCodeGen analog) --------------------

def pojo_source(model, class_name: Optional[str] = None) -> str:
    """Emit Java source scoring a GBM/DRF model — the
    hex/tree/TreeJCodeGen.java role: one static method per tree with the
    nested if/else descent, a score0 summing them. Compiles against
    h2o-genmodel's GenModel when a JDK is present; golden-file checked
    otherwise."""
    from h2o3_tpu import telemetry
    from h2o3_tpu.models.tree import refuse_set_splits
    refuse_set_splits(model, "the POJO writer")
    algo = model.algo
    cls = class_name or f"{algo}_pojo_{abs(hash(model.key)) % 10 ** 8}"
    # one counted pytree fetch for the codegen arrays (export-time D2H
    # must show up in the transfer budgets like every other fetch)
    feat, thr, nal, spl, val = (np.asarray(a) for a in telemetry.device_get(
        (model._feat, model._thr, model._na_left, model._is_split,
         model._value), pipeline="export"))
    K = model.nclasses if model.nclasses > 2 else 1
    T = model.ntrees_built
    names = list(model.feature_names)

    def emit_node(t, m, indent) -> List[str]:
        pad = "  " * indent
        if not spl[t, m]:
            return [f"{pad}return {val[t, m]!r}f;"]
        f = int(feat[t, m])
        cond = f"Double.isNaN(data[{f}]) ? {str(bool(nal[t, m])).lower()}" \
               f" : data[{f}] < {thr[t, m]!r}f"
        out = [f"{pad}if ({cond}) {{"]
        out += emit_node(t, 2 * m + 1, indent + 1)
        out += [f"{pad}}} else {{"]
        out += emit_node(t, 2 * m + 2, indent + 1)
        out += [f"{pad}}}"]
        return out

    lines = [
        "// Auto-generated POJO scorer (hex/tree/TreeJCodeGen shape);",
        "// score0 contract matches hex/genmodel/GenModel.score0.",
        f"public class {cls} {{",
        f"  public static final String[] NAMES = {{"
        + ", ".join(f'"{n}"' for n in names) + "};",
        f"  public static final int NTREES = {T};",
        f"  public static final int NCLASSES = {max(model.nclasses, 1)};",
    ]
    for t in range(T * K):
        lines.append(f"  static float tree_{t}(double[] data) {{")
        lines += emit_node(t, 0, 2)
        lines.append("  }")
    if K == 1:
        f0 = float(np.asarray(model.f0).reshape(-1)[0]) \
            if model.algo == "gbm" else 0.0
        lines += [
            "  public static double[] score0(double[] data, double[] preds) {",
            f"    double f = {f0!r};",
            f"    for (int t = 0; t < {T}; t++) f += scoreTree(t, data);",
        ]
        if model.nclasses == 2:
            lines += [
                "    double p1 = 1.0 / (1.0 + Math.exp(-f));",
                "    preds[0] = p1 > 0.5 ? 1 : 0; preds[1] = 1 - p1; "
                "preds[2] = p1;",
            ]
        else:
            lines += ["    preds[0] = f;"]
        lines += ["    return preds;", "  }"]
    else:
        lines += [
            "  public static double[] score0(double[] data, double[] preds) {",
            f"    double[] margin = new double[{K}];",
            f"    for (int t = 0; t < {T}; t++)",
            f"      for (int k = 0; k < {K}; k++)",
            f"        margin[k] += scoreTree(t * {K} + k, data);",
            "    double max = Double.NEGATIVE_INFINITY, sum = 0;",
            f"    for (int k = 0; k < {K}; k++) max = Math.max(max, margin[k]);",
            f"    for (int k = 0; k < {K}; k++) {{ "
            "preds[k + 1] = Math.exp(margin[k] - max); sum += preds[k + 1]; }",
            f"    for (int k = 0; k < {K}; k++) preds[k + 1] /= sum;",
            "    preds[0] = 0;",
            "    return preds;",
            "  }",
        ]
    # dispatch table (javac rejects methods > 64KB; per-tree methods keep
    # each unit small — the same reason TreeJCodeGen splits classes)
    lines.append("  static float scoreTree(int t, double[] data) {")
    lines.append("    switch (t) {")
    for t in range(T * K):
        lines.append(f"      case {t}: return tree_{t}(data);")
    lines.append("      default: throw new IllegalArgumentException();")
    lines.append("    }")
    lines.append("  }")
    lines.append("}")
    return "\n".join(lines) + "\n"


def pojo_source_glm(model, class_name: Optional[str] = None) -> str:
    """GLM POJO (water/util/JCodeGen + GLM's POJO emit): the cats-first
    beta layout from the MOJO writer, scored with the same skip-level-0
    indicator logic as GlmMojoModel.glmScore0."""
    if model.family not in ("gaussian", "binomial", "poisson", "gamma"):
        raise ValueError(
            f"GLM POJO export supports gaussian/binomial/poisson/gamma "
            f"(got family='{model.family}')")
    cls = class_name or f"glm_pojo_{abs(hash(model.key)) % 10 ** 8}"
    beta, cat_offsets, num_means = _beta_glm_layout(model)
    cat_idx, num_idx = _split_design(model)
    names = model.feature_names
    columns = [names[i] for i in cat_idx] + [names[i] for i in num_idx]
    link = {"gaussian": "eta", "binomial": "1.0 / (1.0 + Math.exp(-eta))",
            "poisson": "Math.exp(eta)", "gamma": "Math.exp(eta)"}[
                model.family]
    lines = [
        "// Auto-generated GLM POJO (water/util/JCodeGen shape);",
        "// beta layout matches GlmMojoModelBase (cats first, intercept",
        "// last, level 0 of each factor dropped).",
        f"public class {cls} {{",
        "  public static final String[] NAMES = {"
        + ", ".join(f'"{n}"' for n in columns) + "};",
        "  public static final double[] BETA = {"
        + ", ".join(repr(float(v)) for v in beta) + "};",
        "  public static final int[] CAT_OFFSETS = {"
        + ", ".join(str(v) for v in cat_offsets) + "};",
        "  public static final double[] NUM_MEANS = {"
        + ", ".join(repr(float(v)) for v in num_means) + "};",
        f"  public static final int CATS = {len(cat_idx)};",
        f"  public static final int NUMS = {len(num_idx)};",
        "  public static double[] score0(double[] data, double[] preds) {",
        "    double eta = 0.0;",
        "    for (int i = 0; i < CATS; i++) {",
        "      int code = Double.isNaN(data[i]) ? 0 : (int) data[i];",
        "      if (code != 0) {",
        "        int ival = CAT_OFFSETS[i] + code - 1;",
        "        if (ival < CAT_OFFSETS[i + 1]) eta += BETA[ival];",
        "      }",
        "    }",
        "    int noff = CATS > 0 ? CAT_OFFSETS[CATS] : 0;",
        "    for (int i = 0; i < NUMS; i++) {",
        "      double v = data[CATS + i];",
        "      if (Double.isNaN(v)) v = NUM_MEANS[i];",
        "      eta += BETA[noff + i] * v;",
        "    }",
        "    eta += BETA[BETA.length - 1];",
        f"    double mu = {link};",
    ]
    if model.nclasses == 2:
        lines += ["    preds[0] = mu > 0.5 ? 1 : 0;",
                  "    preds[1] = 1.0 - mu; preds[2] = mu;"]
    else:
        lines += ["    preds[0] = mu;"]
    lines += ["    return preds;", "  }", "}"]
    return "\n".join(lines) + "\n"


def export_pojo(model, path: str, class_name: Optional[str] = None) -> str:
    if getattr(model, "algo", "") == "glm":
        src = pojo_source_glm(model, class_name)
    else:
        src = pojo_source(model, class_name)
    with open(path, "w") as f:
        f.write(src)
    return path


# ---------------- EasyPredict row API ----------------------------------

def build_domain_luts(columns: Sequence[str],
                      cat_domains: Dict[str, Sequence[str]]
                      ) -> Dict[str, Dict[str, int]]:
    """Per-column label→code lookup tables for the categorical columns.
    Built once per model (deploy/wrapper construction) so batch encoding
    is O(1) per label instead of the O(|domain|) list.index scan."""
    return {c: {str(lab): i for i, lab in enumerate(cat_domains[c])}
            for c in columns if cat_domains.get(c)}


def rows_to_matrix(rows: Sequence[Dict[str, Any]], columns: Sequence[str],
                   cat_domains: Dict[str, Sequence[str]], *,
                   convert_unknown_categorical_levels_to_na: bool = True,
                   convert_invalid_numbers_to_na: bool = False,
                   unknown_seen: Optional[Dict[str, int]] = None,
                   luts: Optional[Dict[str, Dict[str, int]]] = None,
                   out: Optional[np.ndarray] = None) -> np.ndarray:
    """Vectorized RowData encoding: a batch of {column: value} dicts →
    [n, F] float matrix in training column order — the
    EasyPredictModelWrapper dict→array contract applied to whole
    batches (the serve codec's hot path). Per column: enum labels map
    through the training-domain LUT, unknown levels → NA (or raise,
    per the convert_unknown flag), missing columns / None → NA.

    Int-coded enum levels honor the SAME unknown-level policy as
    string labels: a numeric code outside [0, cardinality) — or a
    non-integral one — is an unknown level, not a silent pass-through
    (the old single-row path forwarded any float verbatim, so an
    out-of-domain code could route down a tree branch that training
    never built).

    ``out`` may be a caller-provided (padded) buffer with >= n rows;
    rows past len(rows) are left untouched."""
    n = len(rows)
    F = len(columns)
    if out is None:
        out = np.full((n, F), np.nan, np.float64)
    else:
        out[:n, :] = np.nan
    if luts is None:
        luts = build_domain_luts(columns, cat_domains)
    for j, c in enumerate(columns):
        lut = luts.get(c)
        if lut is None:
            # numeric column: one-shot asarray fast path, element-wise
            # fallback only when a value refuses to parse
            vals = [r.get(c) for r in rows]
            try:
                col = np.asarray(
                    [np.nan if v is None else v for v in vals],
                    dtype=np.float64)
            except (TypeError, ValueError):
                if not convert_invalid_numbers_to_na:
                    raise
                col = np.full(n, np.nan, np.float64)
                for i, v in enumerate(vals):
                    if v is None:
                        continue
                    try:
                        col[i] = float(v)
                    except (TypeError, ValueError):
                        pass
            out[:n, j] = col
            continue
        ncat = len(lut)
        unknown = 0
        for i, r in enumerate(rows):
            v = r.get(c)
            if v is None:
                continue
            if isinstance(v, str):
                code = lut.get(v, -1)
            else:
                try:
                    fv = float(v)
                except (TypeError, ValueError):
                    code = -1
                else:
                    if np.isnan(fv):
                        continue            # numeric NA → NA level
                    code = int(fv) if (np.isfinite(fv) and fv == int(fv)
                                       and 0 <= fv < ncat) else -1
            if code < 0:
                # unseen level: NA when configured (default), else a
                # PredictUnknownCategoricalLevelException analog
                if not convert_unknown_categorical_levels_to_na:
                    raise ValueError(
                        f"unknown categorical level {v!r} for column "
                        f"'{c}' (set convert_unknown_categorical_levels"
                        f"_to_na=True to map to NA)")
                unknown += 1
                continue
            out[i, j] = code
        if unknown and unknown_seen is not None:
            unknown_seen[c] = unknown_seen.get(c, 0) + unknown
    return out


class EasyPredictModelWrapper:
    """Row-dict scoring over any of our models OR a loaded MOJO scorer —
    hex/genmodel/easy/EasyPredictModelWrapper.java's RowData contract:
    values may be numbers or category LABELS; unknown categoricals map
    to NA; missing columns are NA."""

    def __init__(self, model, convert_unknown_categorical_levels_to_na:
                 bool = True, convert_invalid_numbers_to_na: bool = False,
                 enable_contributions: bool = False,
                 enable_leaf_assignment: bool = False):
        """Config mirrors EasyPredictModelWrapper.Config
        (hex/genmodel/easy/EasyPredictModelWrapper.java): unknown-level
        handling, invalid-number handling, and contributions/leaf
        pass-through for tree models."""
        self.model = model
        self.columns = list(getattr(model, "feature_names", None)
                            or getattr(model, "columns", []))
        self.cat_domains = dict(getattr(model, "cat_domains", {}) or {})
        self.response_domain = list(
            getattr(model, "response_domain", None) or [])
        self.convert_unknown_categorical_levels_to_na = bool(
            convert_unknown_categorical_levels_to_na)
        self.convert_invalid_numbers_to_na = bool(
            convert_invalid_numbers_to_na)
        self.unknown_categorical_levels_seen: Dict[str, int] = {}
        self._luts = build_domain_luts(self.columns, self.cat_domains)
        self.enable_contributions = bool(enable_contributions)
        self.enable_leaf_assignment = bool(enable_leaf_assignment)
        if enable_contributions and not hasattr(model,
                                                "predict_contributions"):
            raise ValueError("enable_contributions: this model has no "
                             "TreeSHAP support (GBM/DRF/XGBoost only)")

    def _row_to_array(self, row: Dict[str, Any]) -> np.ndarray:
        return rows_to_matrix(
            [row], self.columns, self.cat_domains,
            convert_unknown_categorical_levels_to_na=self
            .convert_unknown_categorical_levels_to_na,
            convert_invalid_numbers_to_na=self.convert_invalid_numbers_to_na,
            unknown_seen=self.unknown_categorical_levels_seen,
            luts=self._luts)[0]

    def predict_row(self, row: Dict[str, Any]) -> Dict[str, Any]:
        arr = self._row_to_array(row)
        m = self.model
        if hasattr(m, "score") and not hasattr(m, "_predict_matrix"):
            preds = np.asarray(m.score(arr))
        else:
            import jax.numpy as jnp
            out = np.asarray(m._predict_matrix(jnp.asarray(arr[None, :])))[0]
            if m.nclasses >= 2:
                preds = np.concatenate([[float(np.argmax(out))], out])
            else:
                preds = np.asarray([float(out)]).reshape(-1)
        nclasses = getattr(m, "nclasses", 1)
        if nclasses >= 2:
            label_idx = int(preds[0])
            label = (self.response_domain[label_idx]
                     if self.response_domain else str(label_idx))
            probs = {(self.response_domain[k] if self.response_domain
                      else str(k)): float(p)
                     for k, p in enumerate(preds[1:])}
            out_d = {"label": label, "classProbabilities": probs}
        else:
            out_d = {"value": float(preds[0])}
        out_d.update(self._tree_extras(arr))
        return out_d

    def _tree_extras(self, arr: np.ndarray) -> Dict[str, Any]:
        """contributions / leafNodeAssignments pass-through (the
        Config.setEnableContributions / setEnableLeafAssignment
        behaviors of the reference wrapper)."""
        extras: Dict[str, Any] = {}
        m = self.model
        if self.enable_contributions or self.enable_leaf_assignment:
            from h2o3_tpu.models.tree import refuse_set_splits
            refuse_set_splits(m, "contributions and leaf assignments")
        if self.enable_contributions:
            from h2o3_tpu.models.treeshap import tree_shap_contributions
            phi, bias = tree_shap_contributions(
                arr[None, :], m._feat, m._thr, m._na_left, m._is_split,
                m._node_w, m._value, m.max_depth, len(self.columns),
                tree_scale=m._contrib_scale())
            extras["contributions"] = {
                **{c: float(phi[0, i]) for i, c in enumerate(self.columns)},
                "BiasTerm": float(bias + m._contrib_f0())}
        if self.enable_leaf_assignment and hasattr(m, "_feat"):
            from h2o3_tpu.models.treeshap import leaf_node_assignment
            paths = leaf_node_assignment(arr[None, :], m._feat, m._thr,
                                         m._na_left, m._is_split,
                                         m.max_depth, kind="Path")
            extras["leafNodeAssignments"] = [str(p) for p in paths[0]]
        return extras


# ---------------- CoxPH -------------------------------------------------

def export_mojo_coxph(model, path: str) -> str:
    """CoxPH MOJO (hex/genmodel/algos/coxph/CoxPHMojoWriter wire role:
    coefficients over the cats-first genmodel layout + design means; no
    JVM in this image, so parity is the reader-contract round-trip —
    recorded limitation). The GLM layout machinery is reused: CoxPH has
    no intercept, so the trailing layout slot carries 0."""
    cat_idx, num_idx = _split_design(model)
    names = model.feature_names
    if not hasattr(model, "intercept_value"):
        model.intercept_value = 0.0          # partial likelihood: none
    beta, cat_offsets, num_means = _beta_glm_layout(model)
    cols = ([names[i] for i in cat_idx] + [names[i] for i in num_idx]
            + ([model.response] if model.response else []))
    kv = [f"cats = {len(cat_idx)}",
          f"cat_offsets = {_jarr(cat_offsets)}",
          f"nums = {len(num_idx)}",
          f"num_means = {_jarr(num_means)}",
          f"beta = {_jarr(beta.tolist())}",
          "use_all_factor_levels = false"]
    ini, doms = _ini_header(model, "coxph", "CoxPH", "CoxPH", cols,
                            "1.00", kv)
    return _write_zip(path, ini, doms)


class CoxPHMojoScorer:
    """Linear predictor over the cats-first layout (the genmodel
    CoxPHMojoModel score0 contract: preds[0] = lp, centered on the
    numeric design means)."""

    def __init__(self, kv, columns, domains, response):
        self.columns = [c for c in columns if c != response]
        self.cats = int(kv["cats"])
        self.nums = int(kv["nums"])
        self.cat_offsets = _parse_jarr(kv["cat_offsets"], int)
        self.num_means = _parse_jarr(kv.get("num_means", "[]"), float)
        self.beta = np.asarray(_parse_jarr(kv["beta"]))
        self.cat_domains = domains
        self.nclasses = 1

    def score(self, row: np.ndarray) -> np.ndarray:
        data = np.asarray(row, dtype=np.float64).copy()
        lp = 0.0
        for i in range(self.cats):
            if np.isnan(data[i]):
                continue                       # NA level: no indicator
            code = int(data[i])
            if code != 0:                      # level 0 dropped
                ival = self.cat_offsets[i] + code - 1
                if ival < self.cat_offsets[i + 1]:
                    lp += self.beta[ival]
        noff = self.cat_offsets[self.cats] if self.cats else 0
        for i in range(self.nums):
            v = data[self.cats + i]
            if np.isnan(v):
                v = self.num_means[i]
            lp += self.beta[noff + i] * (v - self.num_means[i])
        return np.array([float(lp)])


# ---------------- Word2Vec ---------------------------------------------

def export_mojo_word2vec(model, path: str) -> str:
    """Word2Vec MOJO (hex/genmodel/algos/word2vec/WordEmbeddingModel
    role): vocab + [V, D] embedding block."""
    vecs = np.asarray(model.vectors, np.float32)
    kv = [f"vec_size = {vecs.shape[1]}",
          f"vocab_size = {len(model.vocab)}"]
    cols = ["word"]
    ini, doms = _ini_header(model, "word2vec", "Word2Vec", "WordEmbedding",
                            cols, "1.00", kv)
    blobs = {"vectors.bin": vecs.tobytes(),
             "vocab.txt": ("\n".join(model.vocab) + "\n").encode()}
    return _write_zip(path, ini, doms, blobs)


class Word2VecMojoScorer:
    def __init__(self, kv, columns, domains, response, blobs=None):
        self.vec_size = int(kv["vec_size"])
        vocab = (blobs or {}).get("vocab.txt", b"").decode().splitlines()
        raw = (blobs or {}).get("vectors.bin", b"")
        self.vectors = np.frombuffer(raw, np.float32).reshape(
            len(vocab), self.vec_size) if vocab else np.zeros((0, 0))
        self.index = {w: i for i, w in enumerate(vocab)}
        self.nclasses = 1
        self.columns = list(columns)
        self.cat_domains = domains

    def transform(self, word: str) -> np.ndarray:
        i = self.index.get(word)
        return (self.vectors[i] if i is not None
                else np.full(self.vec_size, np.nan))

    def score(self, row: np.ndarray) -> np.ndarray:
        raise ValueError("word2vec MOJOs embed words (use .transform), "
                         "they do not score rows")


# ---------------- GLRM --------------------------------------------------

def export_mojo_glrm(model, path: str) -> str:
    """GLRM MOJO (hex/genmodel/algos/glrm/GlrmMojoWriter role):
    archetypes + scaling; scoring solves the row's X by proximal
    iterations like GlrmMojoModel.impute_data."""
    Y = np.asarray(model.archetypes_y, np.float64)
    # expansion layout (exp_names order): per raw column, either its
    # numeric slot or its dropped-first one-hot block
    layout = []
    pos = {n: i for i, n in enumerate(model.exp_names)}
    for n in model.feature_names:
        if n in model.cat_domains:
            dom = list(model.cat_domains[n])
            idxs = [pos.get(f"{n}.{lvl}", -1) for lvl in dom[1:]]
            layout.append(("cat", idxs))
        elif n in pos:
            layout.append(("num", [pos[n]]))
    import json as _json
    kv = [f"k = {Y.shape[0]}",
          f"ncolX = {Y.shape[1]}",
          f"exp_names = {','.join(model.exp_names)}",
          f"xm = {_jarr(model._xm)}",
          f"xs = {_jarr(model._xs)}"]
    cols = list(model.feature_names)
    ini, doms = _ini_header(model, "glrm", "GLRM",
                            "DimReduction", cols, "1.10", kv)
    return _write_zip(path, ini, doms,
                      {"archetypes.bin": Y.astype(np.float64).tobytes(),
                       "layout.json": _json.dumps(layout).encode()})


class GlrmMojoScorer:
    def __init__(self, kv, columns, domains, response, blobs=None):
        import json as _json
        self.k = int(kv["k"])
        ncol = int(kv["ncolX"])
        self.Y = np.frombuffer((blobs or {})["archetypes.bin"],
                               np.float64).reshape(self.k, ncol)
        self.xm = np.asarray(_parse_jarr(kv["xm"]))
        self.xs = np.asarray(_parse_jarr(kv["xs"]))
        lay = (blobs or {}).get("layout.json")
        self.layout = _json.loads(lay.decode()) if lay else             [("num", [i]) for i in range(ncol)]
        self.columns = list(columns)
        self.cat_domains = domains
        self.nclasses = 1

    def _expand(self, row: np.ndarray) -> np.ndarray:
        """Raw column-ordered row → expand_design space (dropped-first
        one-hot per categorical, numeric passthrough)."""
        out = np.zeros(self.Y.shape[1])
        for ci, (kind, idxs) in enumerate(self.layout):
            v = row[ci] if ci < len(row) else np.nan
            if kind == "num":
                if idxs[0] >= 0:
                    out[idxs[0]] = 0.0 if np.isnan(v) else v
            else:
                if not np.isnan(v):
                    code = int(v)
                    if 1 <= code <= len(idxs) and idxs[code - 1] >= 0:
                        out[idxs[code - 1]] = 1.0
        return out

    def score(self, row: np.ndarray) -> np.ndarray:
        """Returns the row's k archetype coefficients (X row) by ridge
        least squares against Y (GlrmMojoModel x-solve role)."""
        a = (self._expand(np.asarray(row, np.float64)) - self.xm) \
            / np.maximum(self.xs, 1e-12)
        a = np.nan_to_num(a)
        G = self.Y @ self.Y.T + 1e-6 * np.eye(self.k)
        return np.linalg.solve(G, self.Y @ a)


# ---------------- IsolationForest --------------------------------------

def export_mojo_isofor(model, path: str) -> str:
    """IsolationForest MOJO: the v1.40 compressed-tree format the tree
    writer already emits (hex/genmodel/algos/isofor/IsolationForest
    MojoModel reads trees + min/max path length)."""
    from h2o3_tpu import telemetry
    from h2o3_tpu.mojo import _compress_tree
    feat, thr, spl = (np.asarray(a) for a in telemetry.device_get(
        (model._feat, model._thr, model._is_split), pipeline="export"))
    T = feat.shape[0]
    nal = np.zeros_like(spl)
    M = feat.shape[1]
    # leaf value = node depth (complete-array index → depth): scoring
    # averages the reached leaves' depths into the path length
    dv = np.floor(np.log2(np.arange(M) + 1)).astype(np.float32)
    blobs = {}
    for t in range(T):
        data, aux = _compress_tree(feat[t], thr[t], nal[t], spl[t], dv)
        blobs[f"trees/t00_{t:03d}.bin"] = data
        blobs[f"trees/t00_{t:03d}_aux.bin"] = aux
    kv = [f"n_trees = {T}",
          "n_trees_per_class = 1",
          f"min_path_length = {int(getattr(model, 'min_path_length', 0))}",
          f"max_path_length = {int(getattr(model, 'max_path_length', 0))}"]
    cols = list(model.feature_names)
    ini, doms = _ini_header(model, "isofor", "Isolation Forest",
                            "AnomalyDetection", cols, "1.40", kv)
    return _write_zip(path, ini, doms, blobs)


# ---------------- GAM ---------------------------------------------------

def export_mojo_gam(model, path: str) -> str:
    """GAM MOJO (hex/genmodel/algos/gam/GamMojoWriter role): the inner
    GLM's coefficients + the spline config (knots per gam column) so a
    reader can re-expand and score."""
    import json as _json
    inner = model.inner
    beta, cat_off, means_list = _beta_glm_layout(inner)
    kv = [f"cat_offsets = {_jarr(cat_off)}",
          f"num_means = {_jarr(means_list)}",
          f"family = {inner.family}",
          f"link = family_default",
          f"gam_columns = {','.join(model.gam_columns)}",
          f"bs = {_jarr([int(model.bs_map.get(c) or 0) for c in model.gam_columns])}",
          f"beta = {_jarr(beta)}",
          f"intercept = {inner.intercept_value}",
          f"exp_names = {','.join(inner.exp_names)}"]
    cols = list(model.feature_names) + ([model.response]
                                        if model.response else [])
    ini, doms = _ini_header(model, "gam", "GAM",
                            ("Binomial" if model.nclasses == 2
                             else "Regression"), cols, "1.00", kv)
    knots_blob = _json.dumps({k: list(map(float, v))
                              for k, v in model.knots.items()}).encode()
    return _write_zip(path, ini, doms, {"knots.json": knots_blob})


# ---------------- StackedEnsemble --------------------------------------

def export_mojo_ensemble(model, path: str) -> str:
    """StackedEnsemble MOJO (hex/genmodel/algos/ensemble/
    StackedEnsembleMojoWriter role): base model MOJOs nested under
    models/ + the metalearner MOJO + the base-model order."""
    import os as _os
    import tempfile as _tmp
    from h2o3_tpu.mojo import export_mojo
    blobs = {}
    names = []
    with _tmp.TemporaryDirectory() as td:
        for i, bm in enumerate(model.base_models):
            p = _os.path.join(td, f"base_{i}.zip")
            export_mojo(bm, p)
            with open(p, "rb") as f:
                blobs[f"models/base_{i}.zip"] = f.read()
            names.append(f"base_{i}")
        mp = _os.path.join(td, "meta.zip")
        export_mojo(model.meta_model, mp)
        with open(mp, "rb") as f:
            blobs["models/metalearner.zip"] = f.read()
    kv = [f"base_models = {','.join(names)}",
          f"n_base_models = {len(names)}"]
    cols = list(model.feature_names) + ([model.response]
                                        if model.response else [])
    ini, doms = _ini_header(model, "ensemble", "StackedEnsemble",
                            ("Binomial" if model.nclasses == 2 else
                             "Multinomial" if model.nclasses > 2
                             else "Regression"), cols, "1.00", kv)
    return _write_zip(path, ini, doms, blobs)


# ---------------- PCA ---------------------------------------------------
# hex/genmodel/algos/pca/PCAMojoReader: eigenvector matrix + the same
# standardization block the kmeans reader carries; score = projection
# of the standardized (NA-imputed) row onto k components.

def export_mojo_pca(model, path: str) -> str:
    if len(model.exp_names) != len(model.feature_names):
        raise NotImplementedError(
            "PCA MOJO export requires a numeric-only design: this model "
            "trained on an expanded (categorical) design and the MOJO "
            "row format carries raw columns (export the scores frame, "
            "or one-hot the frame before training)")
    columns = list(model.feature_names)
    ev = np.asarray(model.eigvec, np.float64)          # [Fe, k]
    extra = [
        "standardize = true",
        f"pca_means = {_jarr(np.asarray(model.xm, np.float64).tolist())}",
        f"pca_mults = {_jarr((1.0 / np.maximum(np.asarray(model.xs, np.float64), 1e-12)).tolist())}",
        f"k = {ev.shape[1]}",
    ] + [f"eigvec_{j} = {_jarr(ev[:, j].tolist())}"
         for j in range(ev.shape[1])]
    ini, doms = _ini_header(model, "pca", "Principal Components Analysis",
                            "DimReduction", columns, "1.00", extra)
    return _write_zip(path, ini, doms)


class PcaMojoScorer:
    def __init__(self, kv: Dict[str, str], columns, domains, response):
        self.means = np.asarray(_parse_jarr(kv["pca_means"]))
        self.mults = np.asarray(_parse_jarr(kv["pca_mults"]))
        k = int(kv["k"])
        self.eigvec = np.stack(
            [np.asarray(_parse_jarr(kv[f"eigvec_{j}"]))
             for j in range(k)], axis=1)               # [Fe, k]
        self.nclasses = 1
        self.columns = columns

    def score(self, row: np.ndarray) -> np.ndarray:
        x = np.asarray(row, np.float64)
        x = np.where(np.isnan(x), self.means, x)
        xs = (x - self.means) * self.mults
        return xs @ self.eigvec


# ---------------- Isotonic ----------------------------------------------
# hex/genmodel/algos/isotonic/IsotonicRegressionMojoReader: threshold
# knots; score = piecewise-linear interpolation clamped to [min, max].

def export_mojo_isotonic(model, path: str) -> str:
    columns = list(model.feature_names) + [model.response]
    tx = np.asarray(model.thresholds_x, np.float64)
    ty = np.asarray(model.thresholds_y, np.float64)
    extra = [
        f"thresholds_x = {_jarr(tx.tolist())}",
        f"thresholds_y = {_jarr(ty.tolist())}",
        f"min_x = {tx.min()}", f"max_x = {tx.max()}",
    ]
    ini, doms = _ini_header(model, "isotonic", "Isotonic Regression",
                            "Regression", columns, "1.00", extra)
    return _write_zip(path, ini, doms)


class IsotonicMojoScorer:
    def __init__(self, kv: Dict[str, str], columns, domains, response):
        self.tx = np.asarray(_parse_jarr(kv["thresholds_x"]))
        self.ty = np.asarray(_parse_jarr(kv["thresholds_y"]))
        self.nclasses = 1
        self.columns = columns

    def score(self, row: np.ndarray) -> np.ndarray:
        x = float(np.asarray(row, np.float64)[0])
        if np.isnan(x):
            return np.array([np.nan])
        return np.array([float(np.interp(x, self.tx, self.ty))])


# ---------------- PSVM --------------------------------------------------
# hex/genmodel/algos/psvm/KernelSvmMojoReader: support vectors + alphas
# + rho; score = sum_i alpha_i*y_i*K(sv_i, x) + b with the Gaussian
# kernel. Both of this build's regimes serialize: mode=exact carries
# the SVs, mode=rff carries the factorized (W, phase, beta) triple.

def export_mojo_psvm(model, path: str) -> str:
    if len(model.exp_names) != len(model.feature_names):
        raise NotImplementedError(
            "PSVM MOJO export requires a numeric-only design: this "
            "model trained on an expanded (categorical) design and the "
            "MOJO row format carries raw columns")
    columns = list(model.feature_names) + [model.response]
    extra = [
        f"svm_b = {model.b}",
        f"svm_means = {_jarr(np.asarray(model._xm, np.float64).tolist())}",
        f"svm_stds = {_jarr(np.asarray(model._xs, np.float64).tolist())}",
    ]
    blobs: Dict[str, bytes] = {}
    if getattr(model, "alpha_y", None) is not None:
        extra += [f"svm_mode = exact", f"svm_gamma = {model.gamma}",
                  f"sv_count = {model.sv_X.shape[0]}"]
        blobs["svm/sv_x.bin"] = np.asarray(
            model.sv_X, "<f8").tobytes()
        blobs["svm/alpha_y.bin"] = np.asarray(
            model.alpha_y, "<f8").tobytes()
    else:
        extra += ["svm_mode = rff",
                  f"rff_rank = {model.W.shape[1] if model.W is not None else 0}"]
        if model.W is not None:
            blobs["svm/rff_w.bin"] = np.asarray(model.W, "<f8").tobytes()
            blobs["svm/rff_phase.bin"] = np.asarray(
                model.phase, "<f8").tobytes()
        blobs["svm/beta.bin"] = np.asarray(model.beta, "<f8").tobytes()
    ini, doms = _ini_header(model, "psvm", "Support Vector Machine",
                            "Binomial", columns, "1.00", extra)
    return _write_zip(path, ini, doms, blobs=blobs)


class PsvmMojoScorer:
    def __init__(self, kv: Dict[str, str], columns, domains, response,
                 blobs=None):
        self.b = float(kv["svm_b"])
        self.means = np.asarray(_parse_jarr(kv["svm_means"]))
        self.stds = np.asarray(_parse_jarr(kv["svm_stds"]))
        self.mode = kv.get("svm_mode", "exact")
        F = len(self.means)
        if self.mode == "exact":
            self.gamma = float(kv["svm_gamma"])
            n = int(kv["sv_count"])
            self.sv = np.frombuffer(
                blobs["svm/sv_x.bin"], "<f8").reshape(n, -1)
            self.ay = np.frombuffer(blobs["svm/alpha_y.bin"], "<f8")
        else:
            r = int(kv["rff_rank"])
            self.W = (np.frombuffer(blobs["svm/rff_w.bin"],
                                    "<f8").reshape(F, r) if r else None)
            self.phase = (np.frombuffer(blobs["svm/rff_phase.bin"],
                                        "<f8") if r else None)
            self.beta = np.frombuffer(blobs["svm/beta.bin"], "<f8")
        self.nclasses = 2
        self.columns = columns

    def score(self, row: np.ndarray) -> np.ndarray:
        x = np.asarray(row, np.float64)
        x = np.where(np.isnan(x), self.means, x)
        xs = (x - self.means) / self.stds
        if self.mode == "exact":
            d2 = ((self.sv - xs[None, :]) ** 2).sum(1)
            dec = float(np.exp(-self.gamma * d2) @ self.ay + self.b)
        elif self.W is not None:
            z = np.sqrt(2.0 / self.W.shape[1]) * np.cos(
                xs @ self.W + self.phase)
            dec = float(z @ self.beta + self.b)
        else:
            dec = float(xs @ self.beta + self.b)
        p1 = 1.0 / (1.0 + np.exp(-2.0 * dec))
        return np.array([1.0 if dec >= 0 else 0.0, 1.0 - p1, p1])


# ---------------- TargetEncoder -----------------------------------------
# hex/genmodel/algos/targetencoder/TargetEncoderMojoReader: per-column
# category->(numerator, denominator) tables + prior + blending knobs;
# scoring-time transform is te = blend(sum/cnt, prior) per level (NA and
# unseen levels fall back to the prior).

def export_mojo_targetencoder(model, path: str) -> str:
    columns = list(model.feature_names) + [model.response]
    p = model.params
    extra = [
        f"te_prior = {model.prior}",
        f"te_blending = {'true' if p.get('blending', True) else 'false'}",
        f"te_inflection_point = {float(p.get('inflection_point', 10.0))}",
        f"te_smoothing = {float(p.get('smoothing', 20.0))}",
        f"te_cols = {_jarr(list(model.encodings), quote=True)}",
    ]
    blobs: Dict[str, bytes] = {}
    for c, (s, n) in model.encodings.items():
        blobs[f"te/{c}_sum.bin"] = np.asarray(s, "<f8").tobytes()
        blobs[f"te/{c}_cnt.bin"] = np.asarray(n, "<f8").tobytes()
    ini, doms = _ini_header(model, "targetencoder", "TargetEncoder",
                            "TargetEncoder", columns, "1.00", extra)
    return _write_zip(path, ini, doms, blobs=blobs)


class TargetEncoderMojoScorer:
    """Transforms a row's categorical codes to their blended encodings
    (EasyPredict transformWithTargetEncoding analog)."""

    def __init__(self, kv: Dict[str, str], columns, domains, response,
                 blobs=None):
        self.prior = float(kv["te_prior"])
        self.blending = kv.get("te_blending", "true") == "true"
        self.infl = float(kv.get("te_inflection_point", 10.0))
        self.smooth = float(kv.get("te_smoothing", 20.0))
        # _parse_jarr JSON-decodes quoted arrays — no extra stripping,
        # which would corrupt names that genuinely contain quotes
        self.te_cols = _parse_jarr(kv["te_cols"], typ=str)
        self.tables = {}
        for c in self.te_cols:
            s = np.frombuffer(blobs[f"te/{c}_sum.bin"], "<f8")
            n = np.frombuffer(blobs[f"te/{c}_cnt.bin"], "<f8")
            self.tables[c] = (s, n)
        self.columns = columns
        self.nclasses = 1

    def encode(self, col: str, code: float) -> float:
        s, n = self.tables[col]
        if not (0 <= code < len(n)) or code != code:
            return self.prior
        i = int(code)
        cnt = n[i]
        if cnt <= 0:
            return self.prior
        est = s[i] / cnt
        if not self.blending:
            return float(est)
        lam = 1.0 / (1.0 + np.exp((self.infl - cnt) / self.smooth))
        return float(lam * est + (1.0 - lam) * self.prior)

    def score(self, row: np.ndarray) -> np.ndarray:
        if not hasattr(self, "_col_idx"):
            self._col_idx = [self.columns.index(c) for c in self.te_cols]
        return np.asarray([self.encode(c, float(row[idx]))
                           for c, idx in zip(self.te_cols, self._col_idx)])
