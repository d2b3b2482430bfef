"""JaxModel — the CNTKModel equivalent: broadcast graph, minibatched on-device inference.

Reference: ``deep-learning/.../cntk/CNTKModel.scala`` — a SparkML Model that
broadcasts a serialized CNTK graph, coerces dtypes, runs minibatched
``model.evaluate`` per partition via JNI, and unbatches (``applyCNTKFunction``
:34-73, ``applyModel`` :88-140, ``transform`` :500-545).

TPU-native redesign:

- the "graph" is a flax module (or any ``apply(variables, batch) -> array``
  callable) plus its variables pytree — pickled/NPZ-serialized instead of
  CNTK protobuf bytes;
- minibatches are padded to fixed bucket shapes so ``jit`` compiles once per
  bucket instead of once per batch shape (XLA static-shape semantics);
- per-partition inference becomes one jitted call per minibatch on the
  executor's local chip; with a multi-device mesh the batch dim is sharded
  over ``data`` and params replicated (inference DP, SURVEY.md §2.11);
- dtype coercion (reference ``coerceDFAndFeedDict`` :450-466) maps numeric /
  vector / image columns onto the model's input dtype.

Since ISSUE 9 the jit/pad/bucket machinery itself lives in
``models/runner.py``: ``JaxModel`` holds the payload and the column
semantics, and ``_transform`` scores through a lazily-bound ``ModelRunner``
(rebuilt by ``_post_load`` after deserialization, so a loaded model re-binds
through the runner instead of rebuilding private jit state).
"""
from __future__ import annotations

import os

from ..utils import pickling as pickle
from typing import Any, Callable, Dict, Optional

import numpy as np

from ..core import (ComplexParam, DataFrame, HasInputCol, HasOutputCol, Model,
                    Param, Saveable)
from ..core.schema import ColumnType, stack_vector_column


class FlaxModelPayload(Saveable):
    """Serializable (module, variables, method kwargs) bundle.

    The analogue of the reference's ``SerializableFunction`` wrapper around
    CNTK JNI graphs (``com/microsoft/CNTK/SerializableFunction.scala``).
    """

    def __init__(self, module=None, variables=None, apply_fn: Optional[Callable] = None,
                 apply_kwargs: Optional[Dict[str, Any]] = None):
        if module is None and apply_fn is None:
            raise ValueError("need a flax module or an apply_fn")
        self.module = module
        self.variables = variables
        self.apply_fn = apply_fn
        self.apply_kwargs = dict(apply_kwargs or {})

    def apply(self, batch):
        return self.pure_apply(self.variables, batch)

    @property
    def pure_apply(self) -> Callable:
        """(variables, batch) -> output — the jit-compilable form."""
        if self.apply_fn is not None:
            return self.apply_fn
        module, kw = self.module, self.apply_kwargs
        def fn(variables, batch):
            return module.apply(variables, batch, **kw)
        return fn

    # ------------------------------------------------------------ persistence
    def save(self, path: str) -> None:
        os.makedirs(path, exist_ok=True)
        import jax
        from flax import traverse_util, core as flax_core
        with open(os.path.join(path, "module.pkl"), "wb") as f:
            pickle.dump({"module": self.module, "apply_fn": self.apply_fn,
                         "apply_kwargs": self.apply_kwargs}, f)
        if self.variables is not None:
            var_dict = self.variables
            if isinstance(var_dict, flax_core.FrozenDict):
                var_dict = var_dict.unfreeze()
            flat = traverse_util.flatten_dict(var_dict, sep="/")
            np.savez(os.path.join(path, "variables.npz"),
                     **{k: np.asarray(v) for k, v in flat.items()})

    @classmethod
    def load(cls, path: str) -> "FlaxModelPayload":
        from flax import traverse_util
        with open(os.path.join(path, "module.pkl"), "rb") as f:
            meta = pickle.load(f)
        variables = None
        vpath = os.path.join(path, "variables.npz")
        if os.path.exists(vpath):
            with np.load(vpath, allow_pickle=False) as z:
                flat = {k: z[k] for k in z.files}
            variables = traverse_util.unflatten_dict(flat, sep="/")
        return cls(module=meta["module"], variables=variables,
                   apply_fn=meta["apply_fn"], apply_kwargs=meta["apply_kwargs"])


class JaxModel(Model, HasInputCol, HasOutputCol):
    """Minibatched on-device inference over a column of vectors/arrays."""

    model = ComplexParam("model", "FlaxModelPayload to evaluate")
    batch_size = Param("batch_size", "rows per device minibatch", "int", default=64,
                       validator=lambda v: v > 0)
    input_shape = Param("input_shape", "per-row input shape (list), e.g. [32,32,3]; "
                                       "1-d vectors inferred if unset", "list")
    input_dtype = Param("input_dtype", "numpy dtype name for model input", "string",
                        default="float32")
    output_mode = Param("output_mode", "'vector' (object column of arrays) or "
                                       "'dense' (2-d float column)", "string",
                        default="vector")

    def __init__(self, uid: Optional[str] = None, **kwargs):
        super().__init__(uid)
        self._runner = None
        if kwargs:
            self.set_params(**kwargs)

    def _post_load(self):
        # the runner handle is live jit state and never serializes; a loaded
        # model re-binds through a fresh ModelRunner on first use (ISSUE 9
        # small fix: no private jit state to rebuild)
        self._runner = None

    # ------------------------------------------------------------ helpers
    def set_model(self, module=None, variables=None, apply_fn=None, apply_kwargs=None):
        self.set("model", FlaxModelPayload(module, variables, apply_fn, apply_kwargs))
        self._runner = None
        return self

    def runner(self):
        """The lazily-bound ``ModelRunner`` scoring this payload — built on
        first use (and after every load/set_model), shared across transform
        calls so the lower-once executable cache survives the stage's whole
        life.  Exposed so serving glue can reuse the SAME runner (and its
        compiled buckets) this stage scores batch transforms through."""
        if self._runner is None:
            from ..models.runner import ModelRunner
            self._runner = ModelRunner(self.get_or_fail("model"),
                                       name="dl.jax_model",
                                       batch_size=self.get("batch_size"))
        return self._runner

    def _stack_input(self, col: np.ndarray):
        """What the runner scores: a dense column as one array in the
        model's input dtype, sliced by the runner as it is; an object
        column as a ``RowSource`` over its rows, which the runner stacks
        chunk by chunk into its own staging buffers."""
        shape = self.get("input_shape")
        dtype = np.dtype(self.get("input_dtype"))
        if col.dtype == object:
            from ..models.runner import RowSource
            row_shape = tuple(shape) if shape else \
                (np.shape(col[0]) or (1,))
            return RowSource(
                col, row_shape, dtype,
                as_row=lambda v: np.asarray(v).reshape(row_shape))
        x = np.asarray(col)
        if x.ndim == 1:
            x = x[:, None]
        if shape:
            x = x.reshape((x.shape[0], *shape))
        return x.astype(dtype, copy=False)

    def _transform(self, df: DataFrame) -> DataFrame:
        bs = self.get("batch_size")
        in_col, out_col = self.get_or_fail("input_col"), self.get_or_fail("output_col")
        runner = self.runner()

        def per_part(p):
            col = p[in_col]
            n = len(col)
            if n == 0:
                return {**p, out_col: np.empty(0, dtype=object)}
            x = self._stack_input(col)
            # pad/bucket/shard and the lower-once executable cache all live
            # in the runner now (ISSUE 9) — one copy of the glue for batch
            # transform, serving, and decode alike
            y = runner.apply_batch(x, front="transform", batch_size=bs)
            if self.get("output_mode") == "dense" and y.ndim == 2:
                out_val = y
            else:
                out_val = np.empty(n, dtype=object)
                for i in range(n):
                    out_val[i] = y[i]
            return {**p, out_col: out_val}

        return df.map_partitions(per_part)

    def transform_schema(self, schema):
        schema.require(self.get_or_fail("input_col"))
        return schema.add(self.get_or_fail("output_col"), ColumnType.VECTOR)
