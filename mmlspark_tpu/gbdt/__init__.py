"""Gradient-boosted decision trees, TPU-native (LightGBM-on-Spark parity).

The reference wraps LightGBM's C++ core (histogram GBDT with socket-ring allreduce,
SURVEY §2.1/§3.2). This package re-implements the algorithm TPU-first:

  - quantile feature binning (binning.py; LGBM_DatasetCreateFromMat equivalent)
  - binned histogram accumulation + split finding as jitted XLA kernels
    (histogram.py) with a Pallas MXU one-hot-contraction kernel for the hot
    scatter on TPU (pallas_hist.py)
  - leaf-wise tree growth with the parent-minus-sibling histogram subtraction
    trick (tree.py; LightGBM's core data structure)
  - boosting loop with gbdt/rf/dart/goss variants, binary/multiclass/regression/
    ranking objectives, early stopping, continued training (booster.py;
    LGBM_BoosterUpdateOneIter parity)
  - data-parallel training: per-shard histograms psum'd over the mesh data axis —
    the socket-ring allreduce collapses into one XLA collective (distributed.py)
  - pipeline stages with the reference's param surface (stages.py;
    LightGBMClassifier/Regressor/Ranker, lightgbm/LightGBMParams.scala:1-259)
"""

from .binning import BinMapper
from .booster import Booster, TrainParams
from .stages import (
    LightGBMClassificationModel,
    LightGBMClassifier,
    LightGBMRanker,
    LightGBMRankerModel,
    LightGBMRegressionModel,
    LightGBMRegressor,
)

__all__ = [
    "BinMapper", "Booster", "LightGBMClassificationModel", "LightGBMClassifier",
    "LightGBMRanker", "LightGBMRankerModel", "LightGBMRegressionModel",
    "LightGBMRegressor", "TrainParams",
]
