PARAM_DEFAULTS = {
    'max_confusion_matrix_size': 20,
    'nbins_top_level': 1024,
    'r2_stopping': None,
    'build_tree_one_node': False,
    'pred_noise_bandwidth': 0.0,
    'custom_distribution_func': None,
    'check_constant_response': True,
    'auto_rebalance': True,
    'balance_classes': False,
    'class_sampling_factors': None,
    'max_after_balance_size': 5.0,
    'calibrate_model': False,
    'calibration_frame': None,
    'calibration_method': 'auto',
    'ntrees': 50,
    'max_depth': 5,
    'min_rows': 10.0,
    'learn_rate': 0.1,
    'learn_rate_annealing': 1.0,
    'sample_rate': 1.0,
    'sample_rate_per_class': None,
    'col_sample_rate': 1.0,
    'col_sample_rate_per_tree': 1.0,
    'col_sample_rate_change_per_level': 1.0,
    'nbins': 20,
    'nbins_cats': 1024,
    'distribution': 'auto',
    'tweedie_power': 1.5,
    'quantile_alpha': 0.5,
    'huber_alpha': 0.9,
    'min_split_improvement': 1e-05,
    'seed': -1,
    'stopping_rounds': 0,
    'stopping_metric': 'auto',
    'stopping_tolerance': 0.001,
    'score_tree_interval': 0,
    'reg_lambda': 0.0,
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
}


"""Generated by tools/gen_python.py from /3/ModelBuilders/gbm
metadata — the h2o-bindings/bin/gen_python.py analog. Do not edit."""
from h2o3_tpu.estimators import H2OGradientBoostingEstimator as _Base


class GeneratedH2OGradientBoostingEstimator(_Base):
    """gbm estimator (generated parameter surface)."""

    algo = "gbm"

    def __init__(self, **kwargs):
        allowed = set(PARAM_DEFAULTS)
        for k in kwargs:
            if k not in allowed and k not in ("model_id",):
                raise TypeError(
                    f"unknown gbm parameter {k!r}; known: "
                    f"{sorted(allowed)}")
        merged = dict(PARAM_DEFAULTS)
        merged.update(kwargs)
        super().__init__(**merged)

    @property
    def max_confusion_matrix_size(self):
        """Type: int; default: 20."""
        return self.params.get('max_confusion_matrix_size')

    @max_confusion_matrix_size.setter
    def max_confusion_matrix_size(self, value):
        self.params['max_confusion_matrix_size'] = value

    @property
    def nbins_top_level(self):
        """Type: int; default: 1024."""
        return self.params.get('nbins_top_level')

    @nbins_top_level.setter
    def nbins_top_level(self, value):
        self.params['nbins_top_level'] = value

    @property
    def r2_stopping(self):
        """Type: NoneType; default: None."""
        return self.params.get('r2_stopping')

    @r2_stopping.setter
    def r2_stopping(self, value):
        self.params['r2_stopping'] = value

    @property
    def build_tree_one_node(self):
        """Type: bool; default: False."""
        return self.params.get('build_tree_one_node')

    @build_tree_one_node.setter
    def build_tree_one_node(self, value):
        self.params['build_tree_one_node'] = value

    @property
    def pred_noise_bandwidth(self):
        """Type: float; default: 0.0."""
        return self.params.get('pred_noise_bandwidth')

    @pred_noise_bandwidth.setter
    def pred_noise_bandwidth(self, value):
        self.params['pred_noise_bandwidth'] = value

    @property
    def custom_distribution_func(self):
        """Type: NoneType; default: None."""
        return self.params.get('custom_distribution_func')

    @custom_distribution_func.setter
    def custom_distribution_func(self, value):
        self.params['custom_distribution_func'] = value

    @property
    def check_constant_response(self):
        """Type: bool; default: True."""
        return self.params.get('check_constant_response')

    @check_constant_response.setter
    def check_constant_response(self, value):
        self.params['check_constant_response'] = value

    @property
    def auto_rebalance(self):
        """Type: bool; default: True."""
        return self.params.get('auto_rebalance')

    @auto_rebalance.setter
    def auto_rebalance(self, value):
        self.params['auto_rebalance'] = value

    @property
    def balance_classes(self):
        """Type: bool; default: False."""
        return self.params.get('balance_classes')

    @balance_classes.setter
    def balance_classes(self, value):
        self.params['balance_classes'] = value

    @property
    def class_sampling_factors(self):
        """Type: NoneType; default: None."""
        return self.params.get('class_sampling_factors')

    @class_sampling_factors.setter
    def class_sampling_factors(self, value):
        self.params['class_sampling_factors'] = value

    @property
    def max_after_balance_size(self):
        """Type: float; default: 5.0."""
        return self.params.get('max_after_balance_size')

    @max_after_balance_size.setter
    def max_after_balance_size(self, value):
        self.params['max_after_balance_size'] = value

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
        """Type: int; default: 5."""
        return self.params.get('max_depth')

    @max_depth.setter
    def max_depth(self, value):
        self.params['max_depth'] = value

    @property
    def min_rows(self):
        """Type: float; default: 10.0."""
        return self.params.get('min_rows')

    @min_rows.setter
    def min_rows(self, value):
        self.params['min_rows'] = value

    @property
    def learn_rate(self):
        """Type: float; default: 0.1."""
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
        """Type: int; default: 20."""
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
        """Type: float; default: 1e-05."""
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
        """Type: float; default: 0.0."""
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
