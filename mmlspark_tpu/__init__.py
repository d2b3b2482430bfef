"""mmlspark_tpu — a TPU-native rebuild of MMLSpark (Azure/mmlspark).

MMLSpark is an ecosystem of SparkML-compatible estimators/transformers wrapping
native ML engines (LightGBM, VowpalWabbit, CNTK, OpenCV), web services, and
serving infrastructure.  This package re-creates that capability surface
TPU-first:

- compute is JAX/XLA (jit, shard_map over a `jax.sharding.Mesh`); the hot
  ops are formulated MXU-first (histograms as one-hot matmul contractions,
  blockwise ring attention) and left to XLA to fuse — a hand-written Pallas
  histogram kernel was raced and retired (PARITY.md);
- cross-device communication is XLA collectives over ICI/DCN (`psum`,
  `all_gather`, `ppermute`) instead of the reference's socket allreduce rings
  (LightGBM ring, VW spanning tree — see reference `TrainUtils.scala:236-343`,
  `VowpalWabbitBase.scala:434-462`);
- the pipeline contract (Estimator/Transformer/Params, reference
  `core/contracts/Params.scala`) is preserved over a partitioned columnar
  DataFrame instead of Spark rows.

Layout mirrors the reference's module map (SURVEY.md §1-2):

- ``core``      — DataFrame, Params, Pipeline, serialization (ref L1)
- ``utils``     — cluster topology, stopwatch, fault tolerance (ref L1)
- ``parallel``  — device-mesh bootstrap, shardings, collectives, ring attention
- ``ops``       — XLA kernels (histogram, segment ops, image, hashing)
- ``models``    — flax model zoo (ResNet, BiLSTM, transformer) + GBDT booster
- ``lightgbm``  — LightGBMClassifier/Regressor/Ranker (ref ``lightgbm/``)
- ``vw``        — VowpalWabbit learners + featurizer (ref ``vw/``)
- ``dl``        — JaxModel + ImageFeaturizer (ref ``deep-learning/``)
- ``io``        — HTTP-on-frame, binary/image IO, PowerBI (ref ``core/.../io``)
- ``serving``   — low-latency web serving (ref Spark Serving)
- ``cognitive`` — cognitive-service transformers (ref ``cognitive/``)
- ``stages``    — generic plumbing transformers (ref ``stages/``)
- ``featurize`` — automatic featurization (ref ``featurize/``)
- ``train``     — TrainClassifier/Regressor, ComputeModelStatistics
- ``explainers``— LIME/KernelSHAP (ref ``explainers/``, ``lime/``)
- ``nn``        — BallTree KNN (ref ``nn/``)
- ``recommendation`` — SAR + ranking eval (ref ``recommendation/``)
- ``automl``    — TuneHyperparameters / FindBestModel (ref ``automl/``)
- ``isolationforest`` — IsolationForest (ref ``isolationforest/``)
- ``cyber``     — access-anomaly detection (ref ``core/src/main/python/mmlspark/cyber``)
- ``codegen``   — stage reflection, stub/doc generation (ref ``codegen/``)
- ``observability`` — metrics registry (+/metrics exposition), tracing spans,
  breaker instrumentation (ref BasicLogging telemetry, unified)
"""

__version__ = "0.2.0"
