# Auto-generated by mmlspark_tpu.codegen.rgen - do not edit.

#' LambdaRank ranker (ref ``LightGBMRanker.scala``); requires group_col.
#'
#' @param prediction_col name of the prediction column
#' @param weight_col name of the sample-weight column
#' @param label_col name of the label column
#' @param features_col name of the features column
#' @param group_col query-group id column
#' @param max_position NDCG truncation
#' @param num_iterations number of boosting iterations
#' @param learning_rate shrinkage rate
#' @param num_leaves max leaves per tree (leaf-wise best-first growth, LightGBM numLeaves semantics)
#' @param max_depth max tree depth; set alone it selects level-wise depth growth, with num_leaves it caps leaf-wise depth
#' @param max_bin max histogram bins per feature
#' @param boosting_type gbdt|rf|dart|goss
#' @param lambda_l1 L1 regularization
#' @param lambda_l2 L2 regularization
#' @param min_data_in_leaf min rows per leaf
#' @param min_sum_hessian_in_leaf min hessian per leaf
#' @param min_gain_to_split min split gain
#' @param bagging_fraction row subsample fraction
#' @param bagging_freq bagging frequency (0=off)
#' @param feature_fraction feature subsample fraction
#' @param top_rate GOSS large-gradient keep rate
#' @param other_rate GOSS small-gradient sample rate
#' @param drop_rate DART tree drop rate
#' @param max_drop DART max dropped trees
#' @param skip_drop DART skip probability
#' @param max_delta_step max leaf output
#' @param early_stopping_round stop if no valid improvement
#' @param metric eval metric name ('' = objective default)
#' @param validation_indicator_col bool column marking validation rows
#' @param model_string warm-start model string
#' @param num_batches split training into sequential batches (LightGBMBase.scala:46-61)
#' @param growth tree growth strategy: leaf (LightGBM best-first) | level (depth-wise) | auto (leaf unless only max_depth is set)
#' @param seed random seed
#' @param parallelism data_parallel (full histogram psum) | voting_parallel (top-k feature voting, O(k*B) comm) | serial
#' @param top_k voting_parallel: local top-k features voted per node (reference TrainParams topK)
#' @param shard_rows shard rows over the active device mesh
#' @param categorical_features feature indices treated as categorical (one-vs-rest below max_cat_to_onehot cardinality, sorted-subset many-vs-many above; reference getCategoricalIndexes, LightGBMBase.scala:168)
#' @param max_cat_to_onehot cardinality threshold below which categorical features split one-vs-rest instead of sorted-subset
#' @param cat_smooth grad/hess ratio smoothing when ordering categories for subset splits
#' @param cat_l2 extra L2 regularization applied when scoring sorted-subset categorical splits
#' @param max_cat_threshold max categories on the smaller side of a sorted-subset split
#' @param use_quantized_grad quantized training (LightGBM 4.x): stochastically round per-row grad/hess to integer levels once per iteration and build packed integer histograms, rescaling only at split-gain time; unset = auto (on for accelerator backends, off on CPU)
#' @param num_grad_quant_bins quantization levels for grad/hess under quantized training (reference name; 4-128, reference default 4 — 16 here holds every repo accuracy gate)
#' @param checkpoint_dir directory for periodic atomic booster checkpoints: the run snapshots booster + iteration + PRNG state every checkpoint_every iterations and auto-resumes from the newest valid snapshot (docs/RESILIENCE.md: training fault tolerance)
#' @param checkpoint_every checkpoint cadence in boosting iterations (0 = off; requires checkpoint_dir)
#' @param monitor_port serve live training telemetry over HTTP while fit() runs: GET /progress (step, rows/sec, ETA, loss tail), /metrics, /debug/dump, /debug/profile (0 = ephemeral port; unset = no server; docs/OBSERVABILITY.md: training plane)
#' @param monitor_stall_timeout_s arm the training stall watchdog with a FIXED timeout in seconds instead of the EWMA-scaled default (a trip books mmlspark_training_stalls_total and writes a train_stall flight dump); setting this alone enables the watchdog without the HTTP server
#' @return a reticulate handle to LightGBMRanker
#' @export
mt_light_gbm_ranker <- function(
    prediction_col = "prediction",
    weight_col = NULL,
    label_col = "label",
    features_col = "features",
    group_col = "group",
    max_position = 30,
    num_iterations = 100,
    learning_rate = 0.1,
    num_leaves = 31,
    max_depth = NULL,
    max_bin = 255,
    boosting_type = "gbdt",
    lambda_l1 = 0.0,
    lambda_l2 = 0.0,
    min_data_in_leaf = 20,
    min_sum_hessian_in_leaf = 0.001,
    min_gain_to_split = 0.0,
    bagging_fraction = 1.0,
    bagging_freq = 0,
    feature_fraction = 1.0,
    top_rate = 0.2,
    other_rate = 0.1,
    drop_rate = 0.1,
    max_drop = 50,
    skip_drop = 0.5,
    max_delta_step = 0.0,
    early_stopping_round = 0,
    metric = "",
    validation_indicator_col = NULL,
    model_string = NULL,
    num_batches = 0,
    growth = "auto",
    seed = 0,
    parallelism = "data_parallel",
    top_k = 20,
    shard_rows = FALSE,
    categorical_features = NULL,
    max_cat_to_onehot = 4,
    cat_smooth = 10.0,
    cat_l2 = 10.0,
    max_cat_threshold = 32,
    use_quantized_grad = NULL,
    num_grad_quant_bins = 16,
    checkpoint_dir = NULL,
    checkpoint_every = 0,
    monitor_port = NULL,
    monitor_stall_timeout_s = NULL
) {
  stage <- mmlspark_tpu_engine()$lightgbm$estimators$LightGBMRanker()
  if (!is.null(prediction_col)) stage$set("prediction_col", prediction_col)
  if (!is.null(weight_col)) stage$set("weight_col", weight_col)
  if (!is.null(label_col)) stage$set("label_col", label_col)
  if (!is.null(features_col)) stage$set("features_col", features_col)
  if (!is.null(group_col)) stage$set("group_col", group_col)
  if (!is.null(max_position)) stage$set("max_position", max_position)
  if (!is.null(num_iterations)) stage$set("num_iterations", num_iterations)
  if (!is.null(learning_rate)) stage$set("learning_rate", learning_rate)
  if (!is.null(num_leaves)) stage$set("num_leaves", num_leaves)
  if (!is.null(max_depth)) stage$set("max_depth", max_depth)
  if (!is.null(max_bin)) stage$set("max_bin", max_bin)
  if (!is.null(boosting_type)) stage$set("boosting_type", boosting_type)
  if (!is.null(lambda_l1)) stage$set("lambda_l1", lambda_l1)
  if (!is.null(lambda_l2)) stage$set("lambda_l2", lambda_l2)
  if (!is.null(min_data_in_leaf)) stage$set("min_data_in_leaf", min_data_in_leaf)
  if (!is.null(min_sum_hessian_in_leaf)) stage$set("min_sum_hessian_in_leaf", min_sum_hessian_in_leaf)
  if (!is.null(min_gain_to_split)) stage$set("min_gain_to_split", min_gain_to_split)
  if (!is.null(bagging_fraction)) stage$set("bagging_fraction", bagging_fraction)
  if (!is.null(bagging_freq)) stage$set("bagging_freq", bagging_freq)
  if (!is.null(feature_fraction)) stage$set("feature_fraction", feature_fraction)
  if (!is.null(top_rate)) stage$set("top_rate", top_rate)
  if (!is.null(other_rate)) stage$set("other_rate", other_rate)
  if (!is.null(drop_rate)) stage$set("drop_rate", drop_rate)
  if (!is.null(max_drop)) stage$set("max_drop", max_drop)
  if (!is.null(skip_drop)) stage$set("skip_drop", skip_drop)
  if (!is.null(max_delta_step)) stage$set("max_delta_step", max_delta_step)
  if (!is.null(early_stopping_round)) stage$set("early_stopping_round", early_stopping_round)
  if (!is.null(metric)) stage$set("metric", metric)
  if (!is.null(validation_indicator_col)) stage$set("validation_indicator_col", validation_indicator_col)
  if (!is.null(model_string)) stage$set("model_string", model_string)
  if (!is.null(num_batches)) stage$set("num_batches", num_batches)
  if (!is.null(growth)) stage$set("growth", growth)
  if (!is.null(seed)) stage$set("seed", seed)
  if (!is.null(parallelism)) stage$set("parallelism", parallelism)
  if (!is.null(top_k)) stage$set("top_k", top_k)
  if (!is.null(shard_rows)) stage$set("shard_rows", shard_rows)
  if (!is.null(categorical_features)) stage$set("categorical_features", categorical_features)
  if (!is.null(max_cat_to_onehot)) stage$set("max_cat_to_onehot", max_cat_to_onehot)
  if (!is.null(cat_smooth)) stage$set("cat_smooth", cat_smooth)
  if (!is.null(cat_l2)) stage$set("cat_l2", cat_l2)
  if (!is.null(max_cat_threshold)) stage$set("max_cat_threshold", max_cat_threshold)
  if (!is.null(use_quantized_grad)) stage$set("use_quantized_grad", use_quantized_grad)
  if (!is.null(num_grad_quant_bins)) stage$set("num_grad_quant_bins", num_grad_quant_bins)
  if (!is.null(checkpoint_dir)) stage$set("checkpoint_dir", checkpoint_dir)
  if (!is.null(checkpoint_every)) stage$set("checkpoint_every", checkpoint_every)
  if (!is.null(monitor_port)) stage$set("monitor_port", monitor_port)
  if (!is.null(monitor_stall_timeout_s)) stage$set("monitor_stall_timeout_s", monitor_stall_timeout_s)
  stage
}
