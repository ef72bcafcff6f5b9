"""MOJO export/import — h2o-genmodel–compatible scoring artifacts.

Writer side of the reference's MOJO v1.40 tree format so `h2o-genmodel`
jars can score models trained here (the SURVEY §7.1.11 parity
checkpoint), plus an independent reader/scorer used both for round-trip
tests and to import H2O-written MOJOs as first-class models.

Format contracts implemented (all reverse-engineered from the READER,
which defines the wire format):
- zip layout + model.ini [info]/[columns]/[domains] sections:
  hex/genmodel/ModelMojoReader.java:286-364 (parseModelInfo,
  parseModelDomains; domains line = "<col>: <n> <file>")
- compressed tree bytes (little-endian, ByteOrder.nativeOrder on x86):
  hex/genmodel/algos/tree/SharedTreeMojoModel.java:134-249 (scoreTree):
  node = [u8 nodeType][u16 colId][u8 naSplitDir][f32 splitVal]
  [left: u8/u16/u24/u32 size + subtree | f32 leaf][right: subtree | f32];
  nodeType bits: 0,1=left-size-field width-1, 4,5(=48)=left leaf,
  2,3=split kind (0=float), 6,7(=0xC0)=right leaf; colId 65535 = root
  leaf marker (writer: hex/tree/DTree.java:845-935 compress/size)
- aux tree info (pre-order, internal nodes only, 40 bytes each):
  SharedTreeMojoModel.java:709-766 AuxInfo — [i32 nid][i32 numNodes of
  left subtree][f32 wL][f32 wR][f32 predL][f32 predR][f32 seL][f32 seR]
  [i32 nidL][i32 nidR]
- per-algo keys: GbmMojoReader.java (distribution/init_f/link_function),
  DrfMojoReader.java (binomial_double_trees),
  SharedTreeMojoReader.java:13-60 (n_trees, n_trees_per_class,
  trees/tCC_GGG.bin naming, _genmodel_encoding for v>=1.40)
"""
from __future__ import annotations

import io
import json
import struct
import uuid as _uuid
import zipfile
from typing import Dict, List, Optional, Tuple

import numpy as np

NA_LEFT = 2    # NaSplitDir.NALeft
NA_RIGHT = 3   # NaSplitDir.NARight


# ------------------------------------------------------------------ writer

def _compress_tree(feat, thr, na_left, is_split, value, right_bits=None
                   ) -> Tuple[bytes, bytes]:
    """Complete-binary-array tree → (tree_bytes, aux_bytes).
    ``right_bits`` maps a node that splits on a set of an enum's levels
    to a bool per level, True where the level goes RIGHT: such a node is
    written as a bitset node (``equal`` bits 12: i32 bit offset, i32 bit
    count, the bits), which SharedTreeMojoModel.scoreTree tests with
    ``contains(level)`` = go right."""
    right_bits = right_bits or {}
    ids = {}
    counter = [0]

    def assign(m):
        ids[m] = counter[0]
        counter[0] += 1
        if m < len(is_split) and is_split[m]:
            assign(2 * m + 1)
            assign(2 * m + 2)

    assign(0)

    def n_internal(m):
        if m >= len(is_split) or not is_split[m]:
            return 0
        return 1 + n_internal(2 * m + 1) + n_internal(2 * m + 2)

    def emit(m) -> bytes:
        if m >= len(is_split) or not is_split[m]:
            return struct.pack("<f", float(value[m]))
        left = emit(2 * m + 1)
        right = emit(2 * m + 2)
        left_leaf = not (2 * m + 1 < len(is_split) and is_split[2 * m + 1])
        right_leaf = not (2 * m + 2 < len(is_split) and is_split[2 * m + 2])
        node_type = 0
        if left_leaf:
            node_type |= 48
        else:
            lsz = len(left)
            slen = 0 if lsz < 256 else (1 if lsz < 65535 else
                                        (2 if lsz < (1 << 24) else 3))
            node_type |= slen
        if right_leaf:
            node_type |= 0xC0
        bits = right_bits.get(m)
        if bits is not None:
            node_type |= 12
        out = io.BytesIO()
        out.write(struct.pack("<BHB", node_type, int(feat[m]),
                              NA_LEFT if na_left[m] else NA_RIGHT))
        if bits is not None:
            out.write(struct.pack("<ii", 0, len(bits)))
            out.write(np.packbits(np.asarray(bits, bool),
                                  bitorder="little").tobytes())
        else:
            out.write(struct.pack("<f", float(thr[m])))
        if not left_leaf:
            lsz = len(left)
            if lsz < 256:
                out.write(struct.pack("<B", lsz))
            elif lsz < 65535:
                out.write(struct.pack("<H", lsz))
            elif lsz < (1 << 24):
                out.write(struct.pack("<I", lsz)[:3])
            else:
                out.write(struct.pack("<i", lsz))
        out.write(left)
        out.write(right)
        return out.getvalue()

    if not is_split[0]:
        # root is a leaf: special 65535 marker then the value
        return (struct.pack("<BHf", 0, 65535, float(value[0])), b"")
    body = emit(0)
    # aux records: strict pre-order over INTERNAL nodes, 40 bytes each
    aux = io.BytesIO()

    def emit_aux(m):
        if m >= len(is_split) or not is_split[m]:
            return
        lv = value[2 * m + 1] if not (
            2 * m + 1 < len(is_split) and is_split[2 * m + 1]) else 0.0
        rv = value[2 * m + 2] if not (
            2 * m + 2 < len(is_split) and is_split[2 * m + 2]) else 0.0
        aux.write(struct.pack(
            "<iiffffffii", ids[m], n_internal(2 * m + 1), 0.0, 0.0,
            float(lv), float(rv), 0.0, 0.0,
            ids[2 * m + 1], ids[2 * m + 2]))
        emit_aux(2 * m + 1)
        emit_aux(2 * m + 2)

    emit_aux(0)
    return body, aux.getvalue()


_LINK = {"bernoulli": "logit", "quasibinomial": "logit",
         "multinomial": "log", "poisson": "log", "gamma": "log",
         "tweedie": "log"}

_CATEGORY = {1: "Regression", 2: "Binomial"}


def export_mojo(model, path: str) -> str:
    """Write a model as an h2o-genmodel-readable MOJO zip. Trees carry
    the v1.40 wire format; GLM/KMeans/DeepLearning write their readers'
    kv formats (h2o3_tpu/genmodel.py)."""
    algo = model.algo
    if algo == "glm":
        from h2o3_tpu.genmodel import export_mojo_glm
        return export_mojo_glm(model, path)
    if algo == "kmeans":
        from h2o3_tpu.genmodel import export_mojo_kmeans
        return export_mojo_kmeans(model, path)
    if algo == "deeplearning":
        from h2o3_tpu.genmodel import export_mojo_deeplearning
        return export_mojo_deeplearning(model, path)
    if algo == "coxph":
        from h2o3_tpu.genmodel import export_mojo_coxph
        return export_mojo_coxph(model, path)
    if algo == "word2vec":
        from h2o3_tpu.genmodel import export_mojo_word2vec
        return export_mojo_word2vec(model, path)
    if algo == "glrm":
        from h2o3_tpu.genmodel import export_mojo_glrm
        return export_mojo_glrm(model, path)
    if algo == "pca":
        from h2o3_tpu.genmodel import export_mojo_pca
        return export_mojo_pca(model, path)
    if algo in ("isotonic", "isotonicregression"):
        from h2o3_tpu.genmodel import export_mojo_isotonic
        return export_mojo_isotonic(model, path)
    if algo == "psvm":
        from h2o3_tpu.genmodel import export_mojo_psvm
        return export_mojo_psvm(model, path)
    if algo == "targetencoder":
        from h2o3_tpu.genmodel import export_mojo_targetencoder
        return export_mojo_targetencoder(model, path)
    if algo in ("isolationforest", "isolation_forest"):
        from h2o3_tpu.genmodel import export_mojo_isofor
        return export_mojo_isofor(model, path)
    if algo == "gam":
        from h2o3_tpu.genmodel import export_mojo_gam
        return export_mojo_gam(model, path)
    if algo == "stackedensemble":
        from h2o3_tpu.genmodel import export_mojo_ensemble
        return export_mojo_ensemble(model, path)
    if algo not in ("gbm", "drf"):
        raise ValueError(f"MOJO export supports gbm/drf/glm/kmeans/"
                         f"deeplearning/coxph/word2vec/glrm/isofor/gam/"
                         f"stackedensemble (got '{algo}')")
    # ONE counted pytree fetch (telemetry.device_get) instead of five
    # raw jax.device_get calls: the bytes show up in the d2h counters
    # (they were invisible to the transfer budgets before) and the five
    # per-array syncs collapse into a single transfer
    from h2o3_tpu import telemetry
    feat, thr, nal, spl, val = telemetry.device_get(
        (model._feat, model._thr, model._na_left, model._is_split,
         model._value))
    feat = np.asarray(feat)
    thr = np.asarray(thr)
    nal = np.asarray(nal)
    spl = np.asarray(spl)
    val = np.array(val)
    # category-set splits (GBM on enum columns): per set node the levels
    # that go right, over the column's domain
    cat_set = is_set = None
    if getattr(model, "_cat_set", None) is not None:
        cat_set, is_set = (np.asarray(a) for a in telemetry.device_get(
            (model._cat_set, model._is_set)))
    K = model.nclasses if model.nclasses > 2 else 1
    T = model.ntrees_built
    f0 = np.asarray(model.f0, dtype=np.float64).reshape(-1) \
        if algo == "gbm" else None
    dist = model.dist_name if algo == "gbm" else None
    if algo == "gbm" and model.nclasses > 2:
        # MOJO carries ONE scalar init_f: fold the per-class prior into
        # every leaf of each class's first tree group
        for k in range(K):
            row = 0 * K + k
            leaf_mask = ~spl[row]
            val[row] = np.where(leaf_mask, val[row] + f0[k], val[row])
        init_f = 0.0
    elif algo == "gbm":
        init_f = float(f0[0])
    if algo == "drf" and model.nclasses == 2:
        # genmodel DRF binomial: preds[1] = avg(tree) = P(class 0)
        # (DrfMojoModel.java:46-48); our leaves store P(class 1)
        val = np.where(~spl, 1.0 - val, val)
    columns = list(model.feature_names) + (
        [model.response] if model.response else [])
    n_columns = len(columns)
    category = _CATEGORY.get(model.nclasses, "Multinomial")
    ini = ["[info]",
           "h2o_version = 3.46.0.1",
           "mojo_version = 1.40",
           "license = Apache License Version 2.0",
           f"algo = {algo}",
           "algorithm = %s" % ("Gradient Boosting Machine" if algo == "gbm"
                               else "Distributed Random Forest"),
           f"category = {category}",
           f"uuid = {int(_uuid.uuid4()) % (1 << 63)}",
           "supervised = true",
           f"n_features = {len(model.feature_names)}",
           f"n_classes = {max(model.nclasses, 1)}",
           f"n_columns = {n_columns}",
           "balance_classes = false",
           "default_threshold = 0.5",
           "prior_class_distrib = null",
           "model_class_distrib = null",
           "timestamp = 2026-01-01 00:00:00",
           "escape_domain_values = false",
           f"n_trees = {T}",
           f"n_trees_per_class = {K}",
           "_genmodel_encoding = AUTO",
           ]
    if algo == "gbm":
        ini += [f"distribution = {dist}",
                f"init_f = {init_f}",
                f"link_function = {_LINK.get(dist, 'identity')}"]
    else:
        ini += ["binomial_double_trees = false"]
    # domains
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
    ini_text = "\n".join(ini + dom_lines) + "\n"
    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as zf:
        zf.writestr("model.ini", ini_text)
        for fn, dom in dom_files:
            zf.writestr(f"domains/{fn}", "\n".join(str(d) for d in dom)
                        + "\n")
        for t in range(T):
            for k in range(K):
                row = t * K + k
                right_bits = None
                if is_set is not None:
                    from h2o3_tpu.models.tree import set_levels
                    right_bits = {
                        int(n): ~set_levels(cat_set[row, n], len(
                            model.cat_domains[model.feature_names[
                                int(feat[row, n])]]))
                        for n in np.flatnonzero(is_set[row])}
                tree, aux = _compress_tree(feat[row], thr[row], nal[row],
                                           spl[row], val[row], right_bits)
                zf.writestr(f"trees/t{k:02d}_{t:03d}.bin", tree)
                zf.writestr(f"trees/t{k:02d}_{t:03d}_aux.bin", aux)
    return path


# ------------------------------------------------------------------ reader

def _score_tree(tree: bytes, row: np.ndarray, domains) -> float:
    """Python port of SharedTreeMojoModel.scoreTree (the independent
    verification path for the writer above)."""
    pos = 0

    def u8():
        nonlocal pos
        v = tree[pos]; pos += 1
        return v

    def u16():
        nonlocal pos
        v = struct.unpack_from("<H", tree, pos)[0]; pos += 2
        return v

    def f32():
        nonlocal pos
        v = struct.unpack_from("<f", tree, pos)[0]; pos += 4
        return v

    while True:
        node_type = u8()
        col_id = u16()
        if col_id == 65535:
            return f32()
        na_dir = u8()
        na_vs_rest = na_dir == 1
        leftward = na_dir in (2, 4)
        lmask = node_type & 51
        equal = node_type & 12
        split_val = None
        bs_offset = bs_nbits = bs_bytes = None
        if not na_vs_rest:
            if equal == 0:
                split_val = f32()
            elif equal == 8:  # bitset fill2: u16 offset? (GenmodelBitSet)
                bs_offset = 0
                nb = u16()
                bs_bytes = tree[pos:pos + nb]
                pos += nb
            else:             # fill3: i32 offset + i32 nbits
                bs_offset = struct.unpack_from("<i", tree, pos)[0]; pos += 4
                nbits = struct.unpack_from("<i", tree, pos)[0]; pos += 4
                nb = (nbits + 7) // 8
                bs_bytes = tree[pos:pos + nb]
                pos += nb
        d = row[col_id]
        dom = domains[col_id] if domains else None
        is_na = (np.isnan(d) or
                 (dom is not None and int(d) >= len(dom)))
        if equal != 0 and not is_na and bs_bytes is not None:
            idx = int(d) - (bs_offset or 0)
            in_range = 0 <= idx < len(bs_bytes) * 8
            if not in_range:
                is_na = True
        if is_na:
            go_right = not leftward
        elif na_vs_rest:
            go_right = False
        elif equal == 0:
            go_right = d >= split_val
        else:
            idx = int(d) - (bs_offset or 0)
            go_right = bool(bs_bytes[idx >> 3] & (1 << (idx & 7)))
        if go_right:
            # NB: read the length FIRST (the reader functions advance
            # pos); `pos += u8()` would add to the pre-call pos
            if lmask == 0:
                sz = u8()
                pos += sz
            elif lmask == 1:
                sz = u16()
                pos += sz
            elif lmask == 2:
                v = tree[pos] | (tree[pos + 1] << 8) | (tree[pos + 2] << 16)
                pos += 3 + v
            elif lmask == 3:
                v = struct.unpack_from("<i", tree, pos)[0]
                pos += 4 + v
            elif lmask == 48:
                pos += 4
            lmask = (node_type & 0xC0) >> 2
        else:
            if lmask <= 3:
                pos += lmask + 1
        if lmask & 16:
            return f32()


class MojoModel:
    """Parsed MOJO: scores rows exactly like h2o-genmodel."""

    def __init__(self, info: Dict, columns: List[str], domains,
                 trees: Dict[Tuple[int, int], bytes]):
        self.info = info
        self.columns = columns
        self.domains = domains
        self.trees = trees
        self.algo = info.get("algo")
        self.n_classes = int(info.get("n_classes", 1))
        self.n_trees = int(info.get("n_trees", 0))
        self.tpc = int(info.get("n_trees_per_class",
                                1 if self.n_classes <= 2 else
                                self.n_classes))

    def score(self, row: np.ndarray) -> np.ndarray:
        """row: feature values (codes for enums, NaN for NA). Returns
        probabilities [K] or [1] margin-space prediction."""
        sums = np.zeros(max(self.tpc, 1))
        for t in range(self.n_trees):
            for k in range(self.tpc):
                b = self.trees.get((k, t))
                if b is not None:
                    sums[k] += _score_tree(b, row, self.domains)
        if self.algo == "gbm":
            init_f = float(self.info.get("init_f", 0.0))
            dist = self.info.get("distribution", "gaussian")
            if dist in ("bernoulli", "quasibinomial"):
                p1 = 1.0 / (1.0 + np.exp(-(sums[0] + init_f)))
                return np.array([1.0 - p1, p1])
            if dist == "multinomial":
                e = np.exp(sums - sums.max())
                return e / e.sum()
            return np.array([sums[0] + init_f])
        if self.algo == "drf":
            if self.n_classes == 2:
                p0 = sums[0] / max(self.n_trees, 1)
                return np.array([p0, 1.0 - p0])
            if self.n_classes > 2:
                s = sums.sum()
                return sums / s if s > 0 else sums
            return np.array([sums[0] / max(self.n_trees, 1)])
        if self.algo == "isofor":
            # leaf values carry node depth: preds[0] = mean path length
            # over trees (hex/genmodel/algos/isofor scoring contract;
            # callers normalize with min/max_path_length from the ini)
            return np.array([sums[0] / max(self.n_trees, 1)])
        raise ValueError(f"unsupported mojo algo '{self.algo}'")


def read_mojo(path: str) -> MojoModel:
    with zipfile.ZipFile(path) as zf:
        names = set(zf.namelist())
        ini = zf.read("model.ini").decode().splitlines()
        info: Dict[str, str] = {}
        columns: List[str] = []
        dom_map: Dict[int, str] = {}
        section = 0
        for line in ini:
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            if line == "[info]":
                section = 1
            elif line == "[columns]":
                section = 2
            elif line == "[domains]":
                section = 3
            elif section == 1:
                k, _, v = line.partition("=")
                info[k.strip()] = v.strip()
            elif section == 2:
                columns.append(line)
            elif section == 3:
                ci, _, rest = line.partition(":")
                dom_map[int(ci)] = rest.strip()
        domains: List[Optional[List[str]]] = [None] * len(columns)
        for ci, spec in dom_map.items():
            n, _, fn = spec.partition(" ")
            lines = zf.read(f"domains/{fn.strip()}").decode().splitlines()
            domains[ci] = lines[: int(n)]
        trees = {}
        T = int(info.get("n_trees", 0))
        K = int(info.get("n_trees_per_class", 1))
        for t in range(T):
            for k in range(K):
                nm = f"trees/t{k:02d}_{t:03d}.bin"
                if nm in names:
                    trees[(k, t)] = zf.read(nm)
    algo = info.get("algo", "")
    if algo in ("glm", "kmeans", "deeplearning", "coxph", "pca",
                "isotonic"):
        from h2o3_tpu.genmodel import (CoxPHMojoScorer,
                                       DeepLearningMojoScorer,
                                       GlmMojoScorer,
                                       IsotonicMojoScorer,
                                       KMeansMojoScorer, PcaMojoScorer)
        resp = columns[-1] if info.get("supervised") == "true" else None
        scorer_cls = {"glm": GlmMojoScorer, "kmeans": KMeansMojoScorer,
                      "deeplearning": DeepLearningMojoScorer,
                      "coxph": CoxPHMojoScorer, "pca": PcaMojoScorer,
                      "isotonic": IsotonicMojoScorer}[algo]
        s = scorer_cls(info, columns, domains, resp)
        s.info = info
        return s
    if algo in ("word2vec", "glrm", "psvm", "targetencoder"):
        from h2o3_tpu.genmodel import (GlrmMojoScorer, PsvmMojoScorer,
                                       TargetEncoderMojoScorer,
                                       Word2VecMojoScorer)
        with zipfile.ZipFile(path) as zf2:
            blobs = {n: zf2.read(n) for n in zf2.namelist()
                     if n.endswith((".bin", ".txt"))}
        cls2 = {"word2vec": Word2VecMojoScorer, "glrm": GlrmMojoScorer,
                "psvm": PsvmMojoScorer,
                "targetencoder": TargetEncoderMojoScorer}[algo]
        s = cls2(info, columns, domains, None, blobs=blobs)
        s.info = info
        return s
    return MojoModel(info, columns, domains, trees)


def import_mojo(path: str):
    """Load a MOJO as a first-class scoring model over Frames
    (hex/generic MOJO import analog)."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.vec import T_ENUM, Vec

    mm = read_mojo(path)
    n_feat = int(mm.info.get("n_features", len(mm.columns) - 1))
    feat_names = mm.columns[:n_feat]

    class _MojoFrameModel:
        """Duck-typed Model over MOJO bytes — carries the attributes the
        REST schema layer and keyed store dereference (training_metrics,
        output, scoring_history, run_time, params)."""
        algo = f"mojo_{mm.algo}"
        key = f"mojo_{abs(hash(path)) & 0xffffff:x}"
        nclasses = mm.n_classes
        feature_names = feat_names
        feature_is_cat = [mm.domains[j] is not None
                          for j in range(n_feat)]
        cat_domains = {feat_names[j]: tuple(mm.domains[j])
                       for j in range(n_feat) if mm.domains[j]}
        response = (mm.columns[n_feat] if n_feat < len(mm.columns)
                    else None)
        response_domain = (tuple(mm.domains[n_feat])
                           if n_feat < len(mm.columns)
                           and mm.domains[n_feat] else None)
        mojo = mm

        def __init__(self):
            self.params = {"path": path}
            self.output = {"mojo_source": path,
                           "algo": mm.algo}
            self.training_metrics = None
            self.validation_metrics = None
            self.cross_validation_metrics = None
            self.scoring_history = []
            self.run_time = 0.0

        def model_performance(self, frame=None):
            return self.training_metrics

        def _save_arrays(self):
            raise NotImplementedError(
                "an imported MOJO re-exports as-is: copy the original "
                "zip instead of save_model")

        def predict(self, frame: Frame) -> Frame:
            rows = frame.nrow
            X = np.full((rows, n_feat), np.nan)
            for j, fn in enumerate(feat_names):
                if fn not in frame:
                    continue
                v = frame.vec(fn)
                col = np.asarray(v.to_numpy(), dtype=np.float64)
                if v.is_categorical and mm.domains[j]:
                    remap = {lvl: i for i, lvl in
                             enumerate(mm.domains[j])}
                    src = v.domain or ()
                    lut = np.asarray([remap.get(l, np.nan) for l in src]
                                     + [np.nan])
                    col = lut[np.where(np.isnan(col), len(src),
                                       col).astype(int)]
                X[:, j] = col
            out = np.stack([mm.score(X[i]) for i in range(rows)])
            if mm.n_classes >= 2:
                lbl = np.argmax(out, axis=1).astype(np.int32)
                dom = self.response_domain or tuple(
                    str(i) for i in range(mm.n_classes))
                names = ["predict"] + [f"p{d}" for d in dom]
                vecs = [Vec.from_numpy(lbl, vtype=T_ENUM, domain=dom)]
                vecs += [Vec.from_numpy(out[:, k].astype(np.float32))
                         for k in range(mm.n_classes)]
                return Frame(names, vecs)
            return Frame(["predict"],
                         [Vec.from_numpy(out[:, 0].astype(np.float32))])

    return _MojoFrameModel()
