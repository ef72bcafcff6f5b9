PARAM_DEFAULTS = {
    'quiet_mode': True,
    'eta': 0.3,
    'subsample': 1.0,
    'colsample_bylevel': 1.0,
    'colsample_bytree': 1.0,
    'colsample_bynode': 1.0,
    'max_delta_step': 0.0,
    'gamma': 0.0,
    'nthread': -1,
    'save_matrix_directory': None,
    'build_tree_one_node': False,
    'max_bins': 256,
    'max_leaves': 0,
    'sample_type': 'uniform',
    'normalize_type': 'tree',
    'rate_drop': 0.0,
    'one_drop': False,
    'skip_drop': 0.0,
    'tree_method': 'auto',
    'grow_policy': 'depthwise',
    'booster': 'gbtree',
    'dmatrix_type': 'auto',
    'backend': 'auto',
    'gpu_id': None,
    'scale_pos_weight': 1.0,
    'eval_metric': None,
    'score_eval_metric_only': False,
    'calibrate_model': False,
    'calibration_frame': None,
    'calibration_method': 'auto',
    'ntrees': 50,
    'max_depth': 6,
    'min_rows': 0.0,
    'learn_rate': 0.3,
    'learn_rate_annealing': 1.0,
    'sample_rate': 1.0,
    'sample_rate_per_class': None,
    'col_sample_rate': 1.0,
    'col_sample_rate_per_tree': 1.0,
    'col_sample_rate_change_per_level': 1.0,
    'nbins': 62,
    'nbins_cats': 1024,
    'distribution': 'auto',
    'tweedie_power': 1.5,
    'quantile_alpha': 0.5,
    'huber_alpha': 0.9,
    'min_split_improvement': 0.0,
    'seed': -1,
    'stopping_rounds': 0,
    'stopping_metric': 'auto',
    'stopping_tolerance': 0.001,
    'score_tree_interval': 0,
    'reg_lambda': 1.0,
    'checkpoint': None,
    'in_training_checkpoints_dir': None,
    'in_training_checkpoints_tree_interval': 1,
    'max_abs_leafnode_pred': 1e+30,
    'histogram_type': 'uniform_adaptive',
    'monotone_constraints': None,
    'interaction_constraints': None,
    'hist_kernel': 'auto',
    'histogram_precision': 'auto',
    'packed_codes': 'auto',
    'categorical_encoding': 'auto',
    'min_child_weight': 1.0,
    'reg_alpha': 0.0,
}


"""Generated by tools/gen_python.py from /3/ModelBuilders/xgboost
metadata — the h2o-bindings/bin/gen_python.py analog. Do not edit."""
from h2o3_tpu.estimators import H2OXGBoostEstimator as _Base


class GeneratedH2OXGBoostEstimator(_Base):
    """xgboost estimator (generated parameter surface)."""

    algo = "xgboost"

    def __init__(self, **kwargs):
        allowed = set(PARAM_DEFAULTS)
        for k in kwargs:
            if k not in allowed and k not in ("model_id",):
                raise TypeError(
                    f"unknown xgboost parameter {k!r}; known: "
                    f"{sorted(allowed)}")
        merged = dict(PARAM_DEFAULTS)
        merged.update(kwargs)
        super().__init__(**merged)

    @property
    def quiet_mode(self):
        """Type: bool; default: True."""
        return self.params.get('quiet_mode')

    @quiet_mode.setter
    def quiet_mode(self, value):
        self.params['quiet_mode'] = value

    @property
    def eta(self):
        """Type: float; default: 0.3."""
        return self.params.get('eta')

    @eta.setter
    def eta(self, value):
        self.params['eta'] = value

    @property
    def subsample(self):
        """Type: float; default: 1.0."""
        return self.params.get('subsample')

    @subsample.setter
    def subsample(self, value):
        self.params['subsample'] = value

    @property
    def colsample_bylevel(self):
        """Type: float; default: 1.0."""
        return self.params.get('colsample_bylevel')

    @colsample_bylevel.setter
    def colsample_bylevel(self, value):
        self.params['colsample_bylevel'] = value

    @property
    def colsample_bytree(self):
        """Type: float; default: 1.0."""
        return self.params.get('colsample_bytree')

    @colsample_bytree.setter
    def colsample_bytree(self, value):
        self.params['colsample_bytree'] = value

    @property
    def colsample_bynode(self):
        """Type: float; default: 1.0."""
        return self.params.get('colsample_bynode')

    @colsample_bynode.setter
    def colsample_bynode(self, value):
        self.params['colsample_bynode'] = value

    @property
    def max_delta_step(self):
        """Type: float; default: 0.0."""
        return self.params.get('max_delta_step')

    @max_delta_step.setter
    def max_delta_step(self, value):
        self.params['max_delta_step'] = value

    @property
    def gamma(self):
        """Type: float; default: 0.0."""
        return self.params.get('gamma')

    @gamma.setter
    def gamma(self, value):
        self.params['gamma'] = value

    @property
    def nthread(self):
        """Type: int; default: -1."""
        return self.params.get('nthread')

    @nthread.setter
    def nthread(self, value):
        self.params['nthread'] = value

    @property
    def save_matrix_directory(self):
        """Type: NoneType; default: None."""
        return self.params.get('save_matrix_directory')

    @save_matrix_directory.setter
    def save_matrix_directory(self, value):
        self.params['save_matrix_directory'] = value

    @property
    def build_tree_one_node(self):
        """Type: bool; default: False."""
        return self.params.get('build_tree_one_node')

    @build_tree_one_node.setter
    def build_tree_one_node(self, value):
        self.params['build_tree_one_node'] = value

    @property
    def max_bins(self):
        """Type: int; default: 256."""
        return self.params.get('max_bins')

    @max_bins.setter
    def max_bins(self, value):
        self.params['max_bins'] = value

    @property
    def max_leaves(self):
        """Type: int; default: 0."""
        return self.params.get('max_leaves')

    @max_leaves.setter
    def max_leaves(self, value):
        self.params['max_leaves'] = value

    @property
    def sample_type(self):
        """Type: str; default: 'uniform'."""
        return self.params.get('sample_type')

    @sample_type.setter
    def sample_type(self, value):
        self.params['sample_type'] = value

    @property
    def normalize_type(self):
        """Type: str; default: 'tree'."""
        return self.params.get('normalize_type')

    @normalize_type.setter
    def normalize_type(self, value):
        self.params['normalize_type'] = value

    @property
    def rate_drop(self):
        """Type: float; default: 0.0."""
        return self.params.get('rate_drop')

    @rate_drop.setter
    def rate_drop(self, value):
        self.params['rate_drop'] = value

    @property
    def one_drop(self):
        """Type: bool; default: False."""
        return self.params.get('one_drop')

    @one_drop.setter
    def one_drop(self, value):
        self.params['one_drop'] = value

    @property
    def skip_drop(self):
        """Type: float; default: 0.0."""
        return self.params.get('skip_drop')

    @skip_drop.setter
    def skip_drop(self, value):
        self.params['skip_drop'] = value

    @property
    def tree_method(self):
        """Type: str; default: 'auto'."""
        return self.params.get('tree_method')

    @tree_method.setter
    def tree_method(self, value):
        self.params['tree_method'] = value

    @property
    def grow_policy(self):
        """Type: str; default: 'depthwise'."""
        return self.params.get('grow_policy')

    @grow_policy.setter
    def grow_policy(self, value):
        self.params['grow_policy'] = value

    @property
    def booster(self):
        """Type: str; default: 'gbtree'."""
        return self.params.get('booster')

    @booster.setter
    def booster(self, value):
        self.params['booster'] = value

    @property
    def dmatrix_type(self):
        """Type: str; default: 'auto'."""
        return self.params.get('dmatrix_type')

    @dmatrix_type.setter
    def dmatrix_type(self, value):
        self.params['dmatrix_type'] = value

    @property
    def backend(self):
        """Type: str; default: 'auto'."""
        return self.params.get('backend')

    @backend.setter
    def backend(self, value):
        self.params['backend'] = value

    @property
    def gpu_id(self):
        """Type: NoneType; default: None."""
        return self.params.get('gpu_id')

    @gpu_id.setter
    def gpu_id(self, value):
        self.params['gpu_id'] = value

    @property
    def scale_pos_weight(self):
        """Type: float; default: 1.0."""
        return self.params.get('scale_pos_weight')

    @scale_pos_weight.setter
    def scale_pos_weight(self, value):
        self.params['scale_pos_weight'] = value

    @property
    def eval_metric(self):
        """Type: NoneType; default: None."""
        return self.params.get('eval_metric')

    @eval_metric.setter
    def eval_metric(self, value):
        self.params['eval_metric'] = value

    @property
    def score_eval_metric_only(self):
        """Type: bool; default: False."""
        return self.params.get('score_eval_metric_only')

    @score_eval_metric_only.setter
    def score_eval_metric_only(self, value):
        self.params['score_eval_metric_only'] = value

    @property
    def calibrate_model(self):
        """Type: bool; default: False."""
        return self.params.get('calibrate_model')

    @calibrate_model.setter
    def calibrate_model(self, value):
        self.params['calibrate_model'] = value

    @property
    def calibration_frame(self):
        """Type: NoneType; default: None."""
        return self.params.get('calibration_frame')

    @calibration_frame.setter
    def calibration_frame(self, value):
        self.params['calibration_frame'] = value

    @property
    def calibration_method(self):
        """Type: str; default: 'auto'."""
        return self.params.get('calibration_method')

    @calibration_method.setter
    def calibration_method(self, value):
        self.params['calibration_method'] = value

    @property
    def ntrees(self):
        """Type: int; default: 50."""
        return self.params.get('ntrees')

    @ntrees.setter
    def ntrees(self, value):
        self.params['ntrees'] = value

    @property
    def max_depth(self):
        """Type: int; default: 6."""
        return self.params.get('max_depth')

    @max_depth.setter
    def max_depth(self, value):
        self.params['max_depth'] = value

    @property
    def min_rows(self):
        """Type: float; default: 0.0."""
        return self.params.get('min_rows')

    @min_rows.setter
    def min_rows(self, value):
        self.params['min_rows'] = value

    @property
    def learn_rate(self):
        """Type: float; default: 0.3."""
        return self.params.get('learn_rate')

    @learn_rate.setter
    def learn_rate(self, value):
        self.params['learn_rate'] = value

    @property
    def learn_rate_annealing(self):
        """Type: float; default: 1.0."""
        return self.params.get('learn_rate_annealing')

    @learn_rate_annealing.setter
    def learn_rate_annealing(self, value):
        self.params['learn_rate_annealing'] = value

    @property
    def sample_rate(self):
        """Type: float; default: 1.0."""
        return self.params.get('sample_rate')

    @sample_rate.setter
    def sample_rate(self, value):
        self.params['sample_rate'] = value

    @property
    def sample_rate_per_class(self):
        """Type: NoneType; default: None."""
        return self.params.get('sample_rate_per_class')

    @sample_rate_per_class.setter
    def sample_rate_per_class(self, value):
        self.params['sample_rate_per_class'] = value

    @property
    def col_sample_rate(self):
        """Type: float; default: 1.0."""
        return self.params.get('col_sample_rate')

    @col_sample_rate.setter
    def col_sample_rate(self, value):
        self.params['col_sample_rate'] = value

    @property
    def col_sample_rate_per_tree(self):
        """Type: float; default: 1.0."""
        return self.params.get('col_sample_rate_per_tree')

    @col_sample_rate_per_tree.setter
    def col_sample_rate_per_tree(self, value):
        self.params['col_sample_rate_per_tree'] = value

    @property
    def col_sample_rate_change_per_level(self):
        """Type: float; default: 1.0."""
        return self.params.get('col_sample_rate_change_per_level')

    @col_sample_rate_change_per_level.setter
    def col_sample_rate_change_per_level(self, value):
        self.params['col_sample_rate_change_per_level'] = value

    @property
    def nbins(self):
        """Type: int; default: 62."""
        return self.params.get('nbins')

    @nbins.setter
    def nbins(self, value):
        self.params['nbins'] = value

    @property
    def nbins_cats(self):
        """Type: int; default: 1024."""
        return self.params.get('nbins_cats')

    @nbins_cats.setter
    def nbins_cats(self, value):
        self.params['nbins_cats'] = value

    @property
    def distribution(self):
        """Type: str; default: 'auto'."""
        return self.params.get('distribution')

    @distribution.setter
    def distribution(self, value):
        self.params['distribution'] = value

    @property
    def tweedie_power(self):
        """Type: float; default: 1.5."""
        return self.params.get('tweedie_power')

    @tweedie_power.setter
    def tweedie_power(self, value):
        self.params['tweedie_power'] = value

    @property
    def quantile_alpha(self):
        """Type: float; default: 0.5."""
        return self.params.get('quantile_alpha')

    @quantile_alpha.setter
    def quantile_alpha(self, value):
        self.params['quantile_alpha'] = value

    @property
    def huber_alpha(self):
        """Type: float; default: 0.9."""
        return self.params.get('huber_alpha')

    @huber_alpha.setter
    def huber_alpha(self, value):
        self.params['huber_alpha'] = value

    @property
    def min_split_improvement(self):
        """Type: float; default: 0.0."""
        return self.params.get('min_split_improvement')

    @min_split_improvement.setter
    def min_split_improvement(self, value):
        self.params['min_split_improvement'] = value

    @property
    def seed(self):
        """Type: int; default: -1."""
        return self.params.get('seed')

    @seed.setter
    def seed(self, value):
        self.params['seed'] = value

    @property
    def stopping_rounds(self):
        """Type: int; default: 0."""
        return self.params.get('stopping_rounds')

    @stopping_rounds.setter
    def stopping_rounds(self, value):
        self.params['stopping_rounds'] = value

    @property
    def stopping_metric(self):
        """Type: str; default: 'auto'."""
        return self.params.get('stopping_metric')

    @stopping_metric.setter
    def stopping_metric(self, value):
        self.params['stopping_metric'] = value

    @property
    def stopping_tolerance(self):
        """Type: float; default: 0.001."""
        return self.params.get('stopping_tolerance')

    @stopping_tolerance.setter
    def stopping_tolerance(self, value):
        self.params['stopping_tolerance'] = value

    @property
    def score_tree_interval(self):
        """Type: int; default: 0."""
        return self.params.get('score_tree_interval')

    @score_tree_interval.setter
    def score_tree_interval(self, value):
        self.params['score_tree_interval'] = value

    @property
    def reg_lambda(self):
        """Type: float; default: 1.0."""
        return self.params.get('reg_lambda')

    @reg_lambda.setter
    def reg_lambda(self, value):
        self.params['reg_lambda'] = value

    @property
    def checkpoint(self):
        """Type: NoneType; default: None."""
        return self.params.get('checkpoint')

    @checkpoint.setter
    def checkpoint(self, value):
        self.params['checkpoint'] = value

    @property
    def in_training_checkpoints_dir(self):
        """Type: NoneType; default: None."""
        return self.params.get('in_training_checkpoints_dir')

    @in_training_checkpoints_dir.setter
    def in_training_checkpoints_dir(self, value):
        self.params['in_training_checkpoints_dir'] = value

    @property
    def in_training_checkpoints_tree_interval(self):
        """Type: int; default: 1."""
        return self.params.get('in_training_checkpoints_tree_interval')

    @in_training_checkpoints_tree_interval.setter
    def in_training_checkpoints_tree_interval(self, value):
        self.params['in_training_checkpoints_tree_interval'] = value

    @property
    def max_abs_leafnode_pred(self):
        """Type: float; default: 1e+30."""
        return self.params.get('max_abs_leafnode_pred')

    @max_abs_leafnode_pred.setter
    def max_abs_leafnode_pred(self, value):
        self.params['max_abs_leafnode_pred'] = value

    @property
    def histogram_type(self):
        """Type: str; default: 'uniform_adaptive'."""
        return self.params.get('histogram_type')

    @histogram_type.setter
    def histogram_type(self, value):
        self.params['histogram_type'] = value

    @property
    def monotone_constraints(self):
        """Type: NoneType; default: None."""
        return self.params.get('monotone_constraints')

    @monotone_constraints.setter
    def monotone_constraints(self, value):
        self.params['monotone_constraints'] = value

    @property
    def interaction_constraints(self):
        """Type: NoneType; default: None."""
        return self.params.get('interaction_constraints')

    @interaction_constraints.setter
    def interaction_constraints(self, value):
        self.params['interaction_constraints'] = value

    @property
    def hist_kernel(self):
        """Type: str; default: 'auto'."""
        return self.params.get('hist_kernel')

    @hist_kernel.setter
    def hist_kernel(self, value):
        self.params['hist_kernel'] = value

    @property
    def histogram_precision(self):
        """Type: str; default: 'auto'."""
        return self.params.get('histogram_precision')

    @histogram_precision.setter
    def histogram_precision(self, value):
        self.params['histogram_precision'] = value

    @property
    def packed_codes(self):
        """Type: str; default: 'auto'."""
        return self.params.get('packed_codes')

    @packed_codes.setter
    def packed_codes(self, value):
        self.params['packed_codes'] = value

    @property
    def categorical_encoding(self):
        """Type: str; default: 'auto'."""
        return self.params.get('categorical_encoding')

    @categorical_encoding.setter
    def categorical_encoding(self, value):
        self.params['categorical_encoding'] = value

    @property
    def min_child_weight(self):
        """Type: float; default: 1.0."""
        return self.params.get('min_child_weight')

    @min_child_weight.setter
    def min_child_weight(self, value):
        self.params['min_child_weight'] = value

    @property
    def reg_alpha(self):
        """Type: float; default: 0.0."""
        return self.params.get('reg_alpha')

    @reg_alpha.setter
    def reg_alpha(self, value):
        self.params['reg_alpha'] = value
