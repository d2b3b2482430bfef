"""The image featurizer as a system under test: ``ImageFeaturizer`` ->
``JaxModel`` -> ``ModelRunner.apply_batch`` over a backbone from
``mmlspark_tpu.models``, with weights made on the device from the seed.  It
serves both kinds of traffic: whole-table transforms, and single images
behind ``PipelineServer``.  The plain reference is
``image_featurizer_reference.py`` beside this file.

Module level imports stay off JAX: the load generator's child process
imports this file for ``request_bodies`` and ``decode_reply`` only.
"""
from __future__ import annotations

import functools
import json
from typing import Any, Callable, Dict, List

import numpy as np

from benchmark import datagen
from benchmark.families import image_featurizer_reference as reference


def build(run) -> "FeaturizerSystem":
    return FeaturizerSystem(run)


# --------------------------------------------------------- request framing

def request_bodies(config: Dict[str, Any], mix: Dict[str, Any],
                   seed: int) -> List[bytes]:
    """The pool of request bodies: raw uint8 HWC pixels, one image each."""
    pool = datagen.images(seed, int(mix["pool_size"]), config["image_size"])
    return [img.tobytes() for img in pool]


def decode_reply(body: bytes) -> np.ndarray:
    return np.asarray(json.loads(body), np.float32)


# -------------------------------------------------------------- the system

def make_variables(module, seed: int, init_size: int = 64):
    """The backbone's variables, made on the device in one jitted call from
    the seed, in float32 as ``ModelRunner`` holds them.  A fresh flax
    ResNet is nearly inert (the last batch-norm scale of every block is
    zero, every stored mean 0 and variance 1), which would hide an error in
    a block's main branch from any check; so every batch-norm scale and
    variance is drawn from U(0.5, 1.5) and every bias and mean from
    N(0, 0.1^2).  Parameter shapes do not depend on the image size, so the
    module is initialised at a small one."""
    import jax
    import jax.numpy as jnp

    def init(key):
        v = module.init(key, jnp.zeros((1, init_size, init_size, 3),
                                       jnp.float32))
        leaves, treedef = jax.tree_util.tree_flatten_with_path(v)
        out = []
        for n, (path, leaf) in enumerate(leaves):
            name = getattr(path[-1], "key", "")
            k = jax.random.fold_in(key, n + 1)
            if name in ("scale", "var"):
                leaf = jax.random.uniform(k, leaf.shape, leaf.dtype, 0.5, 1.5)
            elif name in ("bias", "mean"):
                leaf = 0.1 * jax.random.normal(k, leaf.shape, leaf.dtype)
            out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(init)(jax.random.PRNGKey(seed))


def _object_column(images: np.ndarray) -> np.ndarray:
    col = np.empty(len(images), dtype=object)
    for i in range(len(images)):
        col[i] = images[i]
    return col


class FeaturizerSystem:
    op_name = "transform"

    def __init__(self, run):
        import jax.numpy as jnp
        from mmlspark_tpu import models
        cfg = self.cfg = run.config
        self.run = run
        with run.spans.span("make_weights"):
            self.module = getattr(models, cfg["arch"])(
                num_classes=cfg["num_classes"], dtype=jnp.dtype(cfg["dtype"]))
            self.variables = make_variables(self.module, run.seed)
        self.transforms: List[bool] = []

    def make_stage(self, input_col: str, output_col: str):
        """The fitted stage a user holds, over the module and variables."""
        from mmlspark_tpu.dl import ImageFeaturizer
        cfg = self.cfg
        stage = ImageFeaturizer(input_col=input_col, output_col=output_col,
                                height=cfg["image_size"],
                                width=cfg["image_size"],
                                batch_size=cfg["batch_size"])
        stage.set_model(module=self.module, variables=self.variables)
        # the harness's own span around the runner's batch call, put on from
        # outside: the share of a transform spent elsewhere is the pipeline
        # layer's metric
        runner = stage._build_runner().runner()
        inner, spans = runner.apply_batch, self.run.spans

        @functools.wraps(inner)
        def apply_batch(*args, **kwargs):
            with spans.span("apply_batch"):
                return inner(*args, **kwargs)
        runner.apply_batch = apply_batch
        return stage

    def check_reference(self, images: np.ndarray, got: np.ndarray) -> None:
        """The stage's features of a few images against the float32
        reference at full matmul precision."""
        import jax
        cfg = self.cfg
        fwd = jax.jit(functools.partial(
            reference.forward, stage_sizes=tuple(cfg["stage_sizes"])))
        want = np.asarray(fwd(self.variables, images.astype(np.float32)))
        rel = reference.relative_l2(got, want)
        tol = cfg["reference"]["relative_l2_tolerance"]
        self.run.facts["reference_relative_l2"] = float(rel.max())
        self.run.note(f"features of {len(images)} images vs the float32 "
                      f"reference: relative L2 {rel.max():.5f} (tolerance {tol})")
        if got.shape != want.shape or not np.isfinite(got).all() \
                or not rel.max() <= tol:
            self.run.fail(f"features differ from the float32 reference: "
                          f"relative L2 {rel.max():.5f} > {tol}")

    def compiled_buckets(self) -> List[str]:
        from mmlspark_tpu.observability.compute import compile_report
        fn = compile_report()["functions"].get("runner.dl.jax_model", {})
        return [s["signature"].split(", ")[-1] for s in fn.get("signatures", [])]

    # ------------------------------------------------- back-to-back traffic
    def warm_up(self) -> None:
        from mmlspark_tpu.core import DataFrame
        run, cfg, mix = self.run, self.cfg, self.run.mix
        with run.spans.span("make_data"):
            self.images = datagen.images(run.seed, int(mix["images"]),
                                         cfg["image_size"])
            self.df = DataFrame.from_dict(
                {"image": _object_column(self.images)})
        self.bulk = self.make_stage("image", "features")
        self.k = int(cfg["reference"]["images"])
        with run.spans.span("warm_transform"):
            self.sample = self._transform()
        run.note(f"compiled buckets: {self.compiled_buckets()}")
        with run.spans.span("check_reference"):
            self.check_reference(self.images[:self.k], self.sample)

    def _transform(self) -> np.ndarray:
        """Transform the whole table; the features of its first rows."""
        out = self.bulk.transform(self.df).collect()["features"]
        if len(out) != len(self.images):
            raise RuntimeError(f"{len(out)} rows out of {len(self.images)}")
        return np.stack([np.asarray(v, np.float32) for v in out[:self.k]])

    def operation(self, i: int) -> float:
        """One whole transform of the table, collected: the features are
        host arrays when it returns."""
        head = self._transform()
        # the same images through the same program give the same features
        self.transforms.append(bool(np.array_equal(head, self.sample)))
        return float(len(self.images))

    def failed_operations(self) -> int:
        bad = self.transforms.count(False)
        if bad:
            self.run.fail(f"{bad} transforms of the window did not reproduce "
                          f"the warm-up's features")
        return bad

    def window_facts(self) -> Dict[str, Any]:
        return {"transforms": len(self.transforms),
                "images_per_transform": len(self.images)}

    # ------------------------------------------------------- served traffic
    def _featurize(self, images: np.ndarray) -> np.ndarray:
        """The served stage's own transform of a few images."""
        from mmlspark_tpu.core import DataFrame
        df = DataFrame.from_dict({"request": _object_column(images)})
        out = self.served.transform(df).collect()["reply"]
        return np.stack([np.asarray(v, np.float32) for v in out])

    def warm_up_serving(self):
        """The stage as it is served, request -> reply, warmed through the
        stage's own transform at every batch size the server can form and
        at no other."""
        run, cfg, mix = self.run, self.cfg, self.run.mix
        self.served = self.make_stage("request", "reply")
        with run.spans.span("make_data"):
            # the images whose bytes the generator's process sends
            self.pool = datagen.images(run.seed, int(mix["pool_size"]),
                                       cfg["image_size"])
        k = int(cfg["reference"]["images"])
        with run.spans.span("warm_buckets"):
            warm = {int(b): self._featurize(self.pool[:int(b)])
                    for b in mix["warm_batches"]}
        run.note(f"compiled buckets: {self.compiled_buckets()}")
        with run.spans.span("check_reference"):
            self.check_reference(self.pool[:k], warm[max(warm)][:k])
        return self.served

    def request_parser(self) -> Callable[[bytes], np.ndarray]:
        size = self.cfg["image_size"]

        def parse(body: bytes) -> np.ndarray:
            if len(body) != size * size * 3:
                raise ValueError(f"body of {len(body)} bytes is not a "
                                 f"{size}x{size}x3 uint8 image")
            return np.frombuffer(body, np.uint8).reshape(size, size, 3)
        return parse

    def wrong_replies(self, sampled) -> int:
        """How many of the sampled ``(pool index, reply body)`` pairs differ
        from the stage's own transform of the same images.  Called after the
        window; the transform runs in the warmed batch sizes."""
        tol = self.cfg["reference"]["relative_l2_tolerance"]
        step = max(int(b) for b in self.run.mix["warm_batches"])
        picks = [pick for pick, _ in sampled]
        wants = [w for i in range(0, len(picks), step)
                 for w in self._featurize(self.pool[picks[i:i + step]])]
        wrong, worst = 0, 0.0
        for (_, body), want in zip(sampled, wants):
            got = decode_reply(body)
            rel = float(np.linalg.norm(got - want) / np.linalg.norm(want)) \
                if got.shape == want.shape else float("inf")
            worst = max(worst, rel)
            wrong += rel > tol
        self.run.note(f"{len(sampled)} sampled replies vs the stage's "
                      f"transform of the same images: worst relative L2 "
                      f"{worst:.5f} (tolerance {tol})")
        if wrong:
            self.run.fail(f"{wrong} sampled replies differ from the stage's "
                          f"transform (worst relative L2 {worst:.5f})")
        return wrong
