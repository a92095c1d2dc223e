"""mmlspark_tpu: a TPU-native ML pipelines framework.

A brand-new JAX/XLA/Pallas framework with the capabilities of MMLSpark
(tbiiann/mmlspark): composable Estimator/Transformer pipelines over columnar
data, deep-network scoring and pjit data-parallel training, a from-scratch
distributed GBDT engine, image ops, AutoML featurization/training/evaluation/
tuning, a SAR recommender, LIME interpretation, and an HTTP serving layer.

The execution model is TPU-first: columnar batches become pytrees of device
arrays; the reference's per-partition native C++ calls become per-host sharded
``jit`` dispatch; its socket/MPI communication becomes XLA collectives over a
``jax.sharding.Mesh``.
"""

from mmlspark_tpu.version import __version__

from mmlspark_tpu.core.dataframe import DataFrame
from mmlspark_tpu.core.environment import (
    accelerator_count, describe, environment_info, place_compile_cache,
)
from mmlspark_tpu.core.params import Param
from mmlspark_tpu.core.stage import Transformer, Estimator, Model, Evaluator, PipelineStage
from mmlspark_tpu.core.pipeline import Pipeline, PipelineModel

place_compile_cache()

__all__ = [
    "__version__",
    "DataFrame",
    "Param",
    "PipelineStage",
    "Transformer",
    "Estimator",
    "Model",
    "Evaluator",
    "Pipeline",
    "PipelineModel",
]
