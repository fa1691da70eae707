"""distkeras_tpu — a TPU-native distributed deep-learning framework.

A from-scratch rebuild of the capabilities of ``xclmj/dist-keras`` (the Spark-based
asynchronous-SGD framework for Keras; see SURVEY.md for the full structural analysis of
the reference) on JAX/XLA:

* The reference's Spark-executor **workers** (``distkeras/workers.py`` -> ``Worker``,
  ``ADAGWorker``, ``AEASGDWorker``...) become per-chip model replicas running
  jit-compiled local-step loops (:mod:`distkeras_tpu.workers`).
* The reference's socket-served **parameter servers**
  (``distkeras/parameter_servers.py`` -> ``DeltaParameterServer``,
  ``ADAGParameterServer``, ``DynSGDParameterServer``) become deterministic ICI
  collective *folds* of worker deltas into a replicated center variable
  (:mod:`distkeras_tpu.parallel.disciplines`).
* The reference's pickle-over-TCP **networking** (``distkeras/networking.py``) becomes
  XLA collectives (``psum`` / ``all_gather`` / ``ppermute``) over a
  :class:`jax.sharding.Mesh` (:mod:`distkeras_tpu.runtime.mesh`).
* The reference's Spark **DataFrame data plane** (``distkeras/transformers.py``,
  ``utils.py``) becomes a columnar, numpy-backed frame with the same transformer set
  (:mod:`distkeras_tpu.data`).
* The **trainer taxonomy** (``distkeras/trainers.py`` -> ``SingleTrainer``,
  ``DOWNPOUR``, ``ADAG``, ``DynSGD``, ``AEASGD``, ``EAMSGD``, ``AveragingTrainer``,
  ``EnsembleTrainer``) is kept class-for-class with the same constructor-kwargs
  surface and the same ``Trainer.train(dataframe)`` entry point
  (:mod:`distkeras_tpu.trainers`).
"""

__version__ = "0.5.0"

from distkeras_tpu.runtime.mesh import (  # noqa: F401
    DATA_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
    PIPE_AXIS,
    EXPERT_AXIS,
    data_mesh,
    hybrid_mesh,
    device_count,
)
from distkeras_tpu.runtime.serialization import (  # noqa: F401
    serialize_model,
    deserialize_model,
    serialize_params,
    deserialize_params,
)

from distkeras_tpu.trainers import (  # noqa: F401
    ADAG,
    AEASGD,
    AveragingTrainer,
    DOWNPOUR,
    DynSGD,
    EAMSGD,
    EnsembleTrainer,
    ParallelTrainer,
    SingleTrainer,
    SynchronousDistributedTrainer,
    Trainer,
    TransformerTrainer,
)
from distkeras_tpu.data import (  # noqa: F401
    DataFrame,
    ShardedDataFrame,
    ShardStore,
    ShardWriter,
    merge_manifests,
    write_shards,
    DenseTransformer,
    LabelIndexTransformer,
    MinMaxTransformer,
    OneHotTransformer,
    ReshapeTransformer,
    Transformer,
)
from distkeras_tpu.models import Model  # noqa: F401
from distkeras_tpu.predictors import (  # noqa: F401
    ClassPredictor,
    ModelPredictor,
    ProbabilityPredictor,
)
from distkeras_tpu.evaluators import (  # noqa: F401
    AccuracyEvaluator,
    F1Evaluator,
    LossEvaluator,
)
from distkeras_tpu.resilience import (  # noqa: F401
    FaultPlan,
    Supervisor,
    supervise,
)
from distkeras_tpu.fleet import (  # noqa: F401
    ElasticTraining,
    FleetJob,
    FleetScheduler,
)
from distkeras_tpu.runtime.compile_cache import watch_compiles

watch_compiles()  # every compilation's phases, onto the telemetry timeline

__all__ = [
    "Trainer",
    "SingleTrainer",
    "SynchronousDistributedTrainer",
    "DOWNPOUR",
    "ADAG",
    "DynSGD",
    "AEASGD",
    "EAMSGD",
    "AveragingTrainer",
    "EnsembleTrainer",
    "ParallelTrainer",
    "TransformerTrainer",
    "DataFrame",
    "ShardedDataFrame",
    "ShardStore",
    "ShardWriter",
    "merge_manifests",
    "write_shards",
    "Transformer",
    "LabelIndexTransformer",
    "OneHotTransformer",
    "MinMaxTransformer",
    "ReshapeTransformer",
    "DenseTransformer",
    "ModelPredictor",
    "ProbabilityPredictor",
    "ClassPredictor",
    "AccuracyEvaluator",
    "F1Evaluator",
    "LossEvaluator",
    "FaultPlan",
    "Supervisor",
    "supervise",
    "FleetScheduler",
    "FleetJob",
    "ElasticTraining",
    "Model",
    "DATA_AXIS",
    "MODEL_AXIS",
    "SEQ_AXIS",
    "PIPE_AXIS",
    "EXPERT_AXIS",
    "data_mesh",
    "hybrid_mesh",
    "device_count",
    "serialize_model",
    "deserialize_model",
    "serialize_params",
    "deserialize_params",
]
