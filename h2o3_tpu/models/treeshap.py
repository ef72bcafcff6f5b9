"""TreeSHAP (predict_contributions) for the stacked complete-array trees.

Reference: h2o-genmodel/src/main/java/hex/genmodel/algos/tree/TreeSHAP.java
(Lundberg & Lee path-dependent TreeSHAP: recursive EXTEND/UNWIND over the
tree with cover fractions), surfaced as ``model.predict_contributions``
via hex/Model.java scoring options + hex/genmodel/.../PredictContributions.

TPU re-design: the reference walks each tree recursively per row with a
mutable path array. Complete binary-array trees (models/tree.py) make the
whole computation static-shaped and batchable instead:

- every node m has a STATIC depth and ancestor list, so all (leaf, path)
  pairs become constant index matrices [M, D] computed once on host;
- the polynomial EXTEND over a leaf's path is a product of D factors
  (r_j + o_j z) — r = cover fraction, o = 1 iff the row follows the
  edge — with neutral (1 + 0z) factors padding inactive/duplicate slots,
  so coefficients are an unrolled static loop on [rows, M, D+1] tensors;
- UNWIND (synthetic division) runs per path slot as another unrolled
  loop, vectorized over rows × leaves on the VPU;
- contributions scatter into features via a one-hot einsum (MXU), not a
  scatter-add.

Duplicate features on a path are merged exactly as the reference's
EXTEND/UNWIND sequence nets out: their cover fractions multiply and the
row must follow ALL edges (o = product), with a single Shapley slot for
the merged feature.

Property (asserted in tests/test_treeshap.py): for every row,
sum(contributions) + bias == margin(x) to float tolerance, where bias =
sum over trees of the cover-weighted expected leaf value (+ the model's
init f0, added by callers).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np


@lru_cache(maxsize=32)
def _path_constants(D: int):
    """Static path structure of a complete binary tree of depth D:
    for each node m (M = 2^(D+1)-1): its depth, the ancestor at each
    level j (path_par[m, j], root at j=0), the path child at level j+1
    (path_child[m, j]), whether that child is a right child, and the
    active-edge mask (j < depth[m])."""
    M = 2 ** (D + 1) - 1
    depth = np.zeros(M, np.int32)
    path_par = np.zeros((M, max(D, 1)), np.int32)
    path_child = np.zeros((M, max(D, 1)), np.int32)
    child_is_right = np.zeros((M, max(D, 1)), bool)
    active = np.zeros((M, max(D, 1)), bool)
    for m in range(M):
        d = int(np.floor(np.log2(m + 1)))
        depth[m] = d
        # ancestors root..m: (m+1) >> (d - j) - 1
        for j in range(d):
            p = ((m + 1) >> (d - j)) - 1
            c = ((m + 1) >> (d - j - 1)) - 1
            path_par[m, j] = p
            path_child[m, j] = c
            child_is_right[m, j] = (c % 2) == 0   # children 2p+1 (L), 2p+2 (R)
            active[m, j] = True
    # numpy (not jnp): these are lru-cached and may first be built inside
    # a jit trace — caching device arrays created there leaks tracers
    return depth, path_par, path_child, child_is_right, active


def _shapley_weight_table(D: int) -> jnp.ndarray:
    """wgt[k, s] = s! (k-1-s)! / k! for 1 <= k <= D, 0 <= s <= k-1
    (Shapley size weights over a path with k unique features)."""
    fact = [1.0]
    for i in range(1, D + 2):
        fact.append(fact[-1] * i)
    w = np.zeros((D + 1, max(D, 1)), np.float64)
    for k in range(1, D + 1):
        for s in range(k):
            w[k, s] = fact[s] * fact[k - 1 - s] / fact[k]
    return w.astype(np.float32)


def _one_tree_phi(X, feat, thr, na_left, is_split, node_w, value,
                  *, D: int, F: int):
    """Contributions of ONE tree: returns (phi [rows, F], bias scalar)."""
    rows = X.shape[0]
    depth, par, chd, cir, active = _path_constants(D)
    M = feat.shape[0]

    # per-node routing decision of every row: go_right[r, m]
    fcl = jnp.maximum(feat, 0)
    xf = jnp.take(X, fcl, axis=1)                      # [rows, M]
    go_right = jnp.where(jnp.isnan(xf), ~na_left[None, :],
                         xf >= thr[None, :])

    # per-edge data (leaf candidate m, edge slot j)
    f_e = jnp.where(active, feat[par], -1)             # [M, D]
    wp = node_w[par]
    wc = node_w[chd]
    r_e = jnp.where(active & (wp > 0), wc / jnp.maximum(wp, 1e-30), 1.0)
    r_e = jnp.clip(r_e, 0.0, 1.0)
    o_e = (jnp.take(go_right, par, axis=1) == cir[None, :, :])  # [rows, M, D]
    o_e = jnp.where(active[None, :, :], o_e, True)

    # effective-leaf validity: m is scored iff it is NOT split and every
    # ancestor IS split (rows can actually terminate there)
    anc_split = jnp.where(active, is_split[par], True).all(axis=1)
    valid = (~is_split) & anc_split                    # [M]

    # merge duplicate features on the path: first-occurrence grouping
    Dj = f_e.shape[1]
    same = (f_e[:, :, None] == f_e[:, None, :]) & active[:, :, None] \
        & active[:, None, :]                           # [M, D, D] j x j'
    lower = jnp.tril(jnp.ones((Dj, Dj), bool))         # j' <= j
    first = jnp.argmax(same & lower[None], axis=2)     # [M, D] first j'==f_j
    rep = active & (first == jnp.arange(Dj)[None, :])  # slot is representative
    group = (first[:, None, :] == jnp.arange(Dj)[None, :, None]) \
        & active[:, None, :]                           # [M, rep j0, member j]
    r_m = jnp.where(group, r_e[:, None, :], 1.0).prod(axis=2)   # [M, D]
    o_f = o_e.astype(jnp.float32)
    o_m = jnp.where(group[None], o_f[:, :, None, :], 1.0).prod(axis=3)
    # neutral factors for non-representative slots: (1 + 0 z)
    a = jnp.where(rep, r_m, 1.0)                       # [M, D]
    b_ = jnp.where(rep[None], o_m, 0.0)                # [rows, M, D]
    k = rep.sum(axis=1)                                # [M] unique count

    # EXTEND: P(z) = prod_j (a_j + b_j z), coeffs [rows, M, D+1]
    coef = jnp.zeros((rows, M, Dj + 1), jnp.float32).at[:, :, 0].set(1.0)
    for j in range(Dj):
        shifted = jnp.concatenate(
            [jnp.zeros((rows, M, 1), jnp.float32), coef[:, :, :-1]], axis=2)
        coef = a[None, :, j, None] * coef + b_[:, :, j, None] * shifted

    wgt_t = jnp.asarray(_shapley_weight_table(Dj))     # [D+1, D]
    wk = wgt_t[k]                                      # [M, D] weights per leaf
    leaf_val = jnp.where(valid, value, 0.0)            # [M]

    phi = jnp.zeros((rows, F), jnp.float32)
    for i in range(Dj):
        ri = a[:, i]                                   # merged r (neutral=1)
        oi = b_[:, :, i]                               # [rows, M]
        # UNWIND: divide P by (ri + oi z) -> Q coeffs q_0..q_{D-1}
        hot = oi > 0.5
        # hot branch: q_{D-1} = p_D; q_{j-1} = p_j - ri q_j
        q_hot = [None] * Dj
        run = coef[:, :, Dj]
        for s in range(Dj - 1, -1, -1):
            q_hot[s] = run
            run = coef[:, :, s] - ri[None, :] * run
        # cold branch: q_j = p_j / ri
        inv_r = 1.0 / jnp.maximum(ri, 1e-30)
        q = [jnp.where(hot, q_hot[s], coef[:, :, s] * inv_r[None, :])
             for s in range(Dj)]
        ssum = sum(q[s] * wk[None, :, s] for s in range(Dj))
        phi_i = (oi - ri[None, :]) * ssum * leaf_val[None, :]
        phi_i = jnp.where(rep[None, :, i], phi_i, 0.0)
        onehot = (f_e[:, i, None] == jnp.arange(F)[None, :]
                  ).astype(jnp.float32)                # [M, F]
        phi = phi + jax.lax.dot_general(
            phi_i, onehot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    # bias: cover-weighted expected value over effective leaves
    w0 = jnp.maximum(node_w[0], 1e-30)
    bias = (leaf_val * node_w / w0).sum()
    return phi, bias


@partial(jax.jit, static_argnames=("D", "F"))
def _shap_stack(X, feat, thr, na_left, is_split, node_w, value, D: int,
                F: int):
    """Sum contributions over a [T, M] stack of trees (lax.scan)."""
    def body(carry, t):
        phi_acc, bias_acc = carry
        phi, bias = _one_tree_phi(X, feat[t], thr[t], na_left[t],
                                  is_split[t], node_w[t], value[t], D=D, F=F)
        return (phi_acc + phi, bias_acc + bias), 0
    init = (jnp.zeros((X.shape[0], F), jnp.float32), jnp.float32(0.0))
    (phi, bias), _ = jax.lax.scan(body, init, jnp.arange(feat.shape[0]))
    return phi, bias


def tree_shap_contributions(X, feat, thr, na_left, is_split, node_w, value,
                            max_depth: int, n_features: int,
                            row_chunk: int = 8192,
                            tree_scale=None):
    """Per-row feature contributions for a stacked tree ensemble.

    X [rows, F] f32 (NaN = NA); tree arrays [T, M]. ``tree_scale``
    optionally scales every tree's phi/bias (DRF averaging = 1/T).
    Returns (phi [rows, F] np.float32, bias float) with
    sum(phi[r]) + bias == ensemble margin(r) (+ f0, added by callers).
    """
    rows = X.shape[0]
    F = n_features
    # per-chunk intermediates scale as rows·M·(D+1); shrink the chunk for
    # deep trees so depth-10+ models stay inside device memory
    M = 2 ** (max_depth + 1) - 1
    row_chunk = max(64, min(row_chunk, int(6e7 / (M * (max_depth + 1)))))
    out = np.zeros((rows, F), np.float32)
    bias = 0.0
    feat = jnp.asarray(feat)
    thr = jnp.asarray(thr)
    na_left = jnp.asarray(na_left)
    is_split = jnp.asarray(is_split)
    node_w = jnp.asarray(node_w)
    value = jnp.asarray(value)
    if tree_scale is not None:
        value = value * jnp.float32(tree_scale)
    for s in range(0, rows, row_chunk):
        e = min(s + row_chunk, rows)
        phi, b = _shap_stack(jnp.asarray(X[s:e]), feat, thr, na_left,
                             is_split, node_w, value, max_depth, F)
        out[s:e] = np.asarray(jax.device_get(phi))
        bias = float(jax.device_get(b))
    return out, bias


# ---------------- scoring options sharing the stacked layout ------------

@partial(jax.jit, static_argnames=("D",))
def _leaf_nodes_stack(X, feat, thr, na_left, is_split, D: int):
    rows = X.shape[0]

    def one_tree(carry, t):
        nid = jnp.zeros(rows, jnp.int32)
        path = jnp.zeros(rows, jnp.int32)  # bit d: went right at depth d
        plen = jnp.zeros(rows, jnp.int32)  # splits actually taken
        for d in range(D):
            f = jnp.maximum(feat[t], 0)[nid]
            s = is_split[t][nid]
            th = thr[t][nid]
            nl = na_left[t][nid]
            xv = jnp.take_along_axis(X, f[:, None], axis=1)[:, 0]
            go_right = jnp.where(jnp.isnan(xv), ~nl, xv >= th)
            path = jnp.where(s, path | (go_right.astype(jnp.int32) << d),
                             path)
            plen = plen + s.astype(jnp.int32)
            nid = jnp.where(s, 2 * nid + 1 + go_right.astype(jnp.int32), nid)
        return carry, (nid, path, plen)

    _, (nids, paths, plens) = jax.lax.scan(one_tree, None,
                                           jnp.arange(feat.shape[0]))
    return nids.T, paths.T, plens.T   # [rows, T]


def leaf_node_assignment(X, feat, thr, na_left, is_split, max_depth: int,
                         kind: str = "Path"):
    """predict_leaf_node_assignment (hex/Model.java LeafNodeAssignment):
    kind='Node_ID' returns terminal node indices [rows, T] (complete-array
    node ids); 'Path' returns 'LRLR...' strings."""
    nids, paths, plens = _leaf_nodes_stack(
        jnp.asarray(X), jnp.asarray(feat), jnp.asarray(thr),
        jnp.asarray(na_left), jnp.asarray(is_split), max_depth)
    nids = np.asarray(jax.device_get(nids))
    if kind.lower() in ("node_id", "node_ids"):
        return nids
    paths = np.asarray(jax.device_get(paths))
    plens = np.asarray(jax.device_get(plens))
    out = np.empty(paths.shape, dtype=object)
    for (r, t), p in np.ndenumerate(paths):
        out[r, t] = "".join("R" if (p >> d) & 1 else "L"
                            for d in range(plens[r, t]))
    return out


class TreeScoringOptionsMixin:
    """predict_contributions / leaf assignment / staged probabilities for
    models holding stacked tree arrays (_feat/_thr/_na_left/_is_split/
    _value/_node_w). Mirrors hex/Model.java scoring options + h2o-py's
    model.predict_contributions / predict_leaf_node_assignment /
    staged_predict_proba."""

    def _contrib_scale(self):
        return None                      # GBM: leaf values already lr-scaled

    def _contrib_f0(self) -> float:
        return 0.0

    def _score_attrs(self, X) -> dict:
        # which side of tree.scorer_node_form's rule a predict runs
        from h2o3_tpu.models.tree import scorer_node_form
        n_nodes = int(self._feat.shape[1])
        out = {"node_form": scorer_node_form(n_nodes, X.shape[0]),
               "n_nodes": n_nodes}
        if getattr(self, "_set_nodes", None) is not None:
            # nodes that test membership in a set of levels
            out["set_nodes"] = self._set_nodes
        return out

    def predict_contributions(self, frame, output_format: str = "original",
                              top_n: int = 0, bottom_n: int = 0,
                              compare_abs: bool = False):
        """TreeSHAP contributions Frame: one column per feature +
        BiasTerm; sum of each row == margin (GBM: link space; DRF:
        probability/response space), matching
        hex/genmodel/algos/tree/TreeSHAP.java via /3/Predictions
        predict_contributions.

        ``output_format`` 'original' and 'compact' coincide here: trees
        split on original columns directly (enum codes as floats), so
        there is no one-hot expansion to compact — unlike the reference's
        XGBoost path where 'original' re-expands 1-hot contributions."""
        from h2o3_tpu.models.tree import refuse_set_splits
        refuse_set_splits(self, "predict_contributions (TreeSHAP)")
        if str(output_format).lower() not in ("original", "compact"):
            raise ValueError(f"unknown output_format '{output_format}'")
        from h2o3_tpu.frame.frame import Frame
        from h2o3_tpu.frame.vec import Vec
        from h2o3_tpu.models.model_base import adapt_test_matrix
        if self.nclasses > 2:
            raise ValueError(
                "predict_contributions supports regression and binomial "
                "models only (reference restriction, hex/Model.java)")
        if getattr(self, "_node_w", None) is None:
            raise ValueError(
                "this model artifact predates contributions support "
                "(no per-node cover weights); retrain to enable")
        X = adapt_test_matrix(self, frame)
        phi, bias = tree_shap_contributions(
            np.asarray(jax.device_get(X)), self._feat, self._thr,
            self._na_left, self._is_split, self._node_w, self._value,
            self.max_depth, len(self.feature_names),
            tree_scale=self._contrib_scale())
        phi = phi[:frame.nrow]
        bias = bias + self._contrib_f0()
        names = list(self.feature_names) + ["BiasTerm"]
        cols = [phi[:, i] for i in range(phi.shape[1])]
        cols.append(np.full(phi.shape[0], bias, np.float32))
        if top_n or bottom_n:
            return _ranked_contrib_frame(names[:-1], phi, bias, top_n,
                                         bottom_n, compare_abs)
        return Frame(names, [Vec.from_numpy(c) for c in cols])

    def h(self, frame, variables):
        """Friedman-Popescu H statistic of `variables` on this model
        (hex/tree/FriedmanPopescusH.java; h2o-py model.h() via
        POST /3/FriedmansPopescusH). 0 = additive, larger = stronger
        interaction, NaN when spoiled by weak main effects."""
        from h2o3_tpu.models.hstat import friedman_popescu_h
        return friedman_popescu_h(self, frame, variables)

    def predict_leaf_node_assignment(self, frame, type: str = "Path"):
        """Terminal-node assignment per tree (hex/Model.java
        LeafNodeAssignment): type='Path' → 'LRLR' strings, 'Node_ID' →
        complete-array node indices."""
        from h2o3_tpu.frame.frame import Frame
        from h2o3_tpu.frame.vec import Vec
        from h2o3_tpu.models.model_base import adapt_test_matrix
        from h2o3_tpu.models.tree import refuse_set_splits
        refuse_set_splits(self, "predict_leaf_node_assignment")
        X = adapt_test_matrix(self, frame)
        out = leaf_node_assignment(
            np.asarray(jax.device_get(X)), self._feat, self._thr,
            self._na_left, self._is_split, self.max_depth, kind=type)
        out = out[:frame.nrow]
        T = out.shape[1]
        K = getattr(self, "_K", 1)
        names = [(f"T{t // K + 1}.C{t % K + 1}" if K > 1 else f"T{t + 1}")
                 for t in range(T)]
        if type.lower() in ("node_id", "node_ids"):
            vecs = [Vec.from_numpy(out[:, t].astype(np.float64))
                    for t in range(T)]
        else:
            from h2o3_tpu.frame.vec import T_STR
            vecs = [Vec.from_numpy(np.asarray(
                [str(v) for v in out[:, t]], dtype=object), vtype=T_STR)
                for t in range(T)]
        return Frame(names, vecs)

    def staged_predict_proba(self, frame):
        """Class probabilities after each boosting stage (binomial only,
        hex/Model.java staged_predict_proba)."""
        from h2o3_tpu.frame.frame import Frame
        from h2o3_tpu.frame.vec import Vec
        from h2o3_tpu.models.model_base import adapt_test_matrix
        if self.nclasses != 2:
            raise ValueError("staged_predict_proba is binomial-only")
        X = adapt_test_matrix(self, frame)
        margins = staged_margins(np.asarray(jax.device_get(X)), self._feat,
                                 self._thr, self._na_left, self._is_split,
                                 self._value, self.max_depth,
                                 getattr(self, "f0", 0.0),
                                 cat_set=getattr(self, "_cat_set", None),
                                 is_set=getattr(self, "_is_set", None))
        p1 = np.asarray(jax.device_get(
            1.0 / (1.0 + jnp.exp(-margins))))[:frame.nrow]
        T = p1.shape[1]
        names, vecs = [], []
        for t in range(T):
            names += [f"p0_T{t + 1}", f"p1_T{t + 1}"]
            vecs += [Vec.from_numpy(1.0 - p1[:, t]), Vec.from_numpy(p1[:, t])]
        return Frame(names, vecs)


def _ranked_contrib_frame(names, phi, bias, top_n, bottom_n, compare_abs):
    """top_n/bottom_n ranked output (h2o-py predict_contributions args):
    interleaved (feature, value) columns, ranked per row."""
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.vec import Vec
    rows, F = phi.shape
    keys = np.abs(phi) if compare_abs else phi
    order = np.argsort(-keys, axis=1)
    if top_n < 0 or top_n > F:
        top_n = F
    if bottom_n < 0 or bottom_n > F:
        bottom_n = F
    # each feature appears at most once: when top_n + bottom_n covers all
    # features the bottom block only takes ranks the top block didn't
    sel = list(range(top_n)) + [F - 1 - i for i in range(bottom_n)
                                if F - 1 - i >= top_n]
    out_names, vecs = [], []
    arr_names = np.asarray(names, dtype=object)
    for rank, pos in enumerate(sel):
        idx = order[:, pos]
        lab = "top" if rank < top_n else "bottom"
        n = rank + 1 if rank < top_n else rank - top_n + 1
        out_names += [f"{lab}_feature_{n}", f"{lab}_value_{n}"]
        from h2o3_tpu.frame.vec import T_STR
        vecs.append(Vec.from_numpy(np.asarray(
            [str(s) for s in arr_names[idx]], dtype=object), vtype=T_STR))
        vecs.append(Vec.from_numpy(phi[np.arange(rows), idx]))
    out_names.append("BiasTerm")
    vecs.append(Vec.from_numpy(np.full(rows, bias, np.float32)))
    return Frame(out_names, vecs)


def staged_margins(X, feat, thr, na_left, is_split, value, max_depth: int,
                   f0, K: int = 1, cat_set=None, is_set=None):
    """Cumulative margin after each boosting iteration
    (hex/Model.java staged_predict_proba): returns [rows, n_stages] (K=1)
    or [rows, n_stages, K]. ``cat_set`` / ``is_set``: the model's
    category-set splits (tree.predict_raw_stacked)."""
    from h2o3_tpu.models.tree import predict_raw_stacked
    contribs = predict_raw_stacked(jnp.asarray(X), jnp.asarray(feat),
                                   jnp.asarray(thr), jnp.asarray(na_left),
                                   jnp.asarray(is_split), jnp.asarray(value),
                                   max_depth, cat_set=cat_set,
                                   is_set=is_set)             # [rows, T]
    if K == 1:
        return jnp.asarray(f0) + jnp.cumsum(contribs, axis=1)
    rows = contribs.shape[0]
    T = contribs.shape[1] // K
    per = contribs.reshape(rows, T, K)
    return jnp.asarray(f0)[None, None, :] + jnp.cumsum(per, axis=1)
