"""RuleFit — tree-derived rules + sparse linear model.

Reference: hex/rulefit/RuleFit.java:36 — grows tree ensembles over a
range of depths, converts every tree path into a conjunctive rule,
assembles a binary rule design (+ winsorized linear terms), and fits an
L1 GLM; nonzero-coefficient rules form the interpretable model.

TPU re-design: trees come from the existing histogram GBM (complete
binary arrays), rule extraction walks those arrays on host (bounded by
ntrees·2^depth, not rows), and rule-membership evaluation is a batched
device kernel: gather feature values per (rule, condition) and AND the
condition mask — rows stream through in blocks. The sparse fit is the
existing coordinate-descent elastic net on the MXU Gram."""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.vec import Vec
from h2o3_tpu.jobs import Job
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.models.model_base import Model, ModelBuilder
from h2o3_tpu.persist import (model_from_meta, model_to_meta,
                              register_model_class)

RULEFIT_DEFAULTS: Dict = dict(
    seed=-1, algorithm="auto", min_rule_length=1, max_rule_length=3,
    max_num_rules=-1, model_type="rules_and_linear",
    rule_generation_ntrees=50, distribution="auto",
)


def _extract_rules(feat, thr, na_left, is_split, max_depth: int):
    """Walk one tree's complete-binary arrays → list of rules, each a
    list of (feat, thr, na_left, go_right) conditions from the root."""
    rules = []

    def walk(node: int, path: List[Tuple[int, float, bool, bool]]):
        if node < len(is_split) and is_split[node]:
            c = (int(feat[node]), float(thr[node]), bool(na_left[node]))
            walk(2 * node + 1, path + [c + (False,)])
            walk(2 * node + 2, path + [c + (True,)])
        else:
            if path:
                rules.append(path)

    walk(0, [])
    return rules


def _rule_membership(X, cf, ct, cnl, cdir, active, block: int = 64):
    """[rows, R] float32 membership matrix. Conditions follow the tree
    routing semantics (tree.py predict_raw_stacked): NA goes right iff
    not na_left; numeric right iff x >= thr."""
    R, D = cf.shape
    outs = []
    for s in range(0, R, block):
        f = jnp.asarray(cf[s:s + block])          # [r, D]
        t = jnp.asarray(ct[s:s + block])
        nl = jnp.asarray(cnl[s:s + block])
        dr = jnp.asarray(cdir[s:s + block])
        ac = jnp.asarray(active[s:s + block])
        x = X[:, f]                                # [rows, r, D]
        isna = jnp.isnan(x)
        went_right = jnp.where(isna, ~nl[None], x >= t[None])
        sat = jnp.where(dr[None], went_right, ~went_right)
        member = jnp.where(ac[None], sat, True).all(axis=2)
        outs.append(member.astype(jnp.float32))
    return jnp.concatenate(outs, axis=1) if outs else \
        jnp.zeros((X.shape[0], 0), jnp.float32)


def _describe_rule(conds, names: List[str]) -> str:
    parts = []
    for (f, t, nl, right) in conds:
        n = names[f] if f < len(names) else f"f{f}"
        op = ">=" if right else "<"
        na = "" if (right != nl) else " or NA"  # NA routes with this side
        parts.append(f"({n} {op} {t:.6g}{na})")
    return " & ".join(parts)


class RuleFitModel(Model):
    algo = "rulefit"

    def __init__(self, key, params, spec, inner, cond_arrays, rule_names,
                 linear_cols, lin_lo, lin_hi):
        super().__init__(key, params, spec)
        self.inner = inner                        # GLMModel over rule design
        self.cf, self.ct, self.cnl, self.cdir, self.cactive = cond_arrays
        self.rule_names = list(rule_names)
        self.linear_cols = list(linear_cols)      # indices into feature_names
        self.lin_lo = np.asarray(lin_lo)          # winsorize bounds
        self.lin_hi = np.asarray(lin_hi)

    def _design(self, X):
        cols = []
        if len(self.rule_names):
            cols.append(_rule_membership(X, self.cf, self.ct, self.cnl,
                                         self.cdir, self.cactive))
        if self.linear_cols:
            lin = X[:, jnp.asarray(self.linear_cols)]
            lin = jnp.clip(jnp.nan_to_num(lin, nan=0.0),
                           jnp.asarray(self.lin_lo)[None],
                           jnp.asarray(self.lin_hi)[None])
            cols.append(lin)
        return jnp.concatenate(cols, axis=1) if cols else \
            jnp.zeros((X.shape[0], 0), jnp.float32)

    def _predict_matrix(self, X, offset=None):
        return self.inner._predict_matrix(self._design(X), offset=offset)

    def rule_importance(self):
        coefs = self.inner.coef()
        if self.inner.nclasses > 2:
            # multinomial: per-class coefficient maps — rank rules by the
            # largest |coefficient| across classes
            agg = {}
            for cls_map in coefs.values():
                for n, v in cls_map.items():
                    if abs(v) > abs(agg.get(n, 0.0)):
                        agg[n] = v
            coefs = agg
        rows = []
        for i, rn in enumerate(self.inner.feature_names):
            c = coefs.get(rn, 0.0)
            if abs(c) > 1e-10:
                rows.append({"variable": rn, "coefficient": c,
                             "rule": self.output.get("rule_descriptions",
                                                     {}).get(rn, rn)})
        rows.sort(key=lambda r: -abs(r["coefficient"]))
        return rows

    def _save_arrays(self):
        d = {f"inner__{k}": v for k, v in self.inner._save_arrays().items()}
        d.update({"cf": self.cf, "ct": self.ct, "cnl": self.cnl,
                  "cdir": self.cdir, "cactive": self.cactive,
                  "lin_cols": np.asarray(self.linear_cols, np.int32),
                  "lin_lo": self.lin_lo, "lin_hi": self.lin_hi})
        return d

    def _save_extra_meta(self):
        return {"inner_meta": model_to_meta(self.inner),
                "rule_names": self.rule_names}

    @classmethod
    def _restore(cls, meta, arrays):
        m = cls._restore_base(meta)
        ex = meta["extra"]
        inner_arrays = {k[len("inner__"):]: v for k, v in arrays.items()
                        if k.startswith("inner__")}
        m.inner = model_from_meta(ex["inner_meta"], inner_arrays)
        m.rule_names = list(ex["rule_names"])
        m.cf = arrays["cf"]; m.ct = arrays["ct"]; m.cnl = arrays["cnl"]
        m.cdir = arrays["cdir"]; m.cactive = arrays["cactive"]
        m.linear_cols = [int(v) for v in arrays["lin_cols"]]
        m.lin_lo = arrays["lin_lo"]; m.lin_hi = arrays["lin_hi"]
        return m


class H2ORuleFitEstimator(ModelBuilder):
    algo = "rulefit"

    def __init__(self, **params):
        merged = dict(RULEFIT_DEFAULTS)
        merged.update(params)
        super().__init__(**merged)

    def _train_impl(self, spec, valid_spec, job: Job):
        from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator
        p = self.params
        model_type = (p.get("model_type") or "rules_and_linear").lower()
        min_d = max(1, int(p.get("min_rule_length", 1)))
        max_d = max(min_d, int(p.get("max_rule_length", 3)))
        depths = list(range(min_d, max_d + 1))
        total_trees = int(p.get("rule_generation_ntrees", 50))
        per_depth = max(1, total_trees // len(depths))
        seed = int(p.get("seed", -1) or -1)
        X = spec.X
        rules = []          # (conds, name)
        if model_type in ("rules_and_linear", "rules"):
            frame = self._frame_from_spec(spec)
            for d in depths:
                gbm = H2OGradientBoostingEstimator(
                    ntrees=per_depth, max_depth=d, seed=seed,
                    learn_rate=0.1, distribution=p.get("distribution",
                                                       "auto"),
                    # a rule is a conjunction of thresholds: enum columns
                    # enter as their level index
                    categorical_encoding="label_encoder",
                    weights_column="__w" if "__w" in frame else None)
                gbm.train(y="__response", x=list(spec.names),
                          training_frame=frame)
                gm = gbm.model
                from h2o3_tpu.models.tree import refuse_set_splits
                refuse_set_splits(gm, "rule extraction")
                feat = np.asarray(jax.device_get(gm._feat))
                thr = np.asarray(jax.device_get(gm._thr))
                nal = np.asarray(jax.device_get(gm._na_left))
                spl = np.asarray(jax.device_get(gm._is_split))
                for t in range(feat.shape[0]):
                    for conds in _extract_rules(feat[t], thr[t], nal[t],
                                                spl[t], d):
                        rules.append((conds,
                                      f"M{d}T{t}N{len(rules)}"))
                job.update(0.0)
        # condition arrays padded to the max rule length
        D = max([len(c) for c, _ in rules], default=1)
        R = len(rules)
        cf = np.zeros((R, D), np.int32)
        ct = np.zeros((R, D), np.float32)
        cnl = np.zeros((R, D), bool)
        cdir = np.zeros((R, D), bool)
        act = np.zeros((R, D), bool)
        for i, (conds, _) in enumerate(rules):
            for j, (f, t, nl, right) in enumerate(conds):
                cf[i, j] = f; ct[i, j] = t; cnl[i, j] = nl
                cdir[i, j] = right; act[i, j] = True
        rule_names = [n for _, n in rules]
        # dedupe identical / constant rule columns on a sample
        if R:
            M = np.asarray(jax.device_get(_rule_membership(
                X, cf, ct, cnl, cdir, act)))
            live = np.asarray(jax.device_get(spec.w)) > 0
            Ms = M[live]
            keep = []
            seen = set()
            for i in range(R):
                col = Ms[:, i]
                mu = col.mean()
                if mu <= 1e-9 or mu >= 1 - 1e-9:
                    continue
                h = col.tobytes()
                if h in seen:
                    continue
                seen.add(h)
                keep.append(i)
            max_rules = int(p.get("max_num_rules", -1))
            if max_rules > 0 and len(keep) > max_rules:
                # keep the rules with support closest to 0.5 (highest
                # variance → most informative prior to the L1 fit)
                keep.sort(key=lambda i: abs(Ms[:, i].mean() - 0.5))
                keep = keep[:max_rules]
            cf, ct, cnl, cdir, act = (a[keep] for a in
                                      (cf, ct, cnl, cdir, act))
            rule_names = [rule_names[i] for i in keep]
            M = M[:, keep]
        else:
            M = np.zeros((X.shape[0], 0), np.float32)
        # linear block: winsorized numerics
        linear_cols, lin_lo, lin_hi = [], [], []
        if model_type in ("rules_and_linear", "linear"):
            live = np.asarray(jax.device_get(spec.w)) > 0
            Xh = np.asarray(jax.device_get(X))
            for i, (n, is_cat) in enumerate(zip(spec.names, spec.is_cat)):
                if is_cat:
                    continue
                v = Xh[live, i]
                v = v[~np.isnan(v)]
                if len(v) == 0:
                    continue
                linear_cols.append(i)
                lin_lo.append(float(np.quantile(v, 0.025)))
                lin_hi.append(float(np.quantile(v, 0.975)))
        # assemble the GLM training frame
        cols: Dict[str, np.ndarray] = {}
        names: List[str] = []
        for i, rn in enumerate(rule_names):
            cols[rn] = M[:, i]
            names.append(rn)
        Xh = np.asarray(jax.device_get(X))
        for i, ci in enumerate(linear_cols):
            nm = f"linear.{spec.names[ci]}"
            v = np.nan_to_num(Xh[:, ci], nan=0.0)
            cols[nm] = np.clip(v, lin_lo[i], lin_hi[i])
            names.append(nm)
        if not names:
            raise ValueError("rulefit produced no features (no rules and "
                             "no numeric linear terms)")
        nrow = spec.nrow
        data = {n: c[:nrow].astype(np.float32) for n, c in cols.items()}
        resp = self._response_values(spec)
        data["__response"] = resp[:nrow]
        wvals = np.asarray(jax.device_get(spec.w))[:nrow]
        data["__w"] = wvals.astype(np.float32)
        glm_frame = Frame(list(data.keys()),
                          [Vec.from_numpy(v) for v in data.values()])
        if spec.nclasses > 2:
            # multinomial path takes a single lambda (no search)
            glm = H2OGeneralizedLinearEstimator(
                alpha=1.0, Lambda=[1e-3], family="multinomial",
                weights_column="__w")
        else:
            glm = H2OGeneralizedLinearEstimator(
                alpha=1.0, lambda_search=True, nlambdas=30,
                family="binomial" if spec.nclasses == 2 else "gaussian",
                weights_column="__w")
        glm.train(y="__response", x=names, training_frame=glm_frame)
        inner = glm.model
        model = RuleFitModel(
            f"rf_{id(self) & 0xffffff:x}", self.params, spec, inner,
            (cf, ct, cnl, cdir, act), rule_names, linear_cols,
            np.asarray(lin_lo, np.float32), np.asarray(lin_hi, np.float32))
        descriptions = {rn: _describe_rule(rules_by_name, list(spec.names))
                        for rn, rules_by_name in
                        zip(rule_names,
                            (self._conds_of(cf, ct, cnl, cdir, act, i)
                             for i in range(len(rule_names))))}
        model.output["rule_descriptions"] = descriptions
        model.training_metrics = inner.training_metrics
        model.output["rule_importance"] = model.rule_importance()
        return model

    @staticmethod
    def _conds_of(cf, ct, cnl, cdir, act, i):
        return [(int(cf[i, j]), float(ct[i, j]), bool(cnl[i, j]),
                 bool(cdir[i, j]))
                for j in range(cf.shape[1]) if act[i, j]]

    def _frame_from_spec(self, spec) -> Frame:
        """Rebuild a Frame view of the spec for the internal tree fits."""
        nrow = spec.nrow
        data: Dict[str, np.ndarray] = {}
        Xh = np.asarray(jax.device_get(spec.X))[:nrow]
        for i, (n, is_cat) in enumerate(zip(spec.names, spec.is_cat)):
            col = Xh[:, i]
            if is_cat:
                dom = spec.cat_domains.get(n) or ()
                codes = np.where(np.isnan(col), -1,
                                 col).astype(np.int32)
                data[n] = Vec.from_numpy(codes, vtype="enum",
                                         domain=tuple(dom))
            else:
                data[n] = Vec.from_numpy(col.astype(np.float32))
        data["__response"] = Vec.from_numpy(self._response_values(spec))
        w = np.asarray(jax.device_get(spec.w))[:nrow]
        if not np.all(w == 1.0):
            data["__w"] = Vec.from_numpy(w.astype(np.float32))
        return Frame(list(data.keys()), list(data.values()))

    @staticmethod
    def _response_values(spec) -> np.ndarray:
        nrow = spec.nrow
        y = np.asarray(jax.device_get(spec.y))[:nrow]
        if spec.nclasses >= 2 and spec.response_domain:
            dom = np.asarray(spec.response_domain, dtype=object)
            return dom[np.clip(y.astype(np.int64), 0, len(dom) - 1)]
        return y.astype(np.float32)


register_model_class("rulefit", RuleFitModel)
