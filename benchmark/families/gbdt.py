"""The GBDT trainer as a system under test: ``lightgbm.train`` on a seeded
table, alone or row-sharded over a mesh, fitted again and again over one
binned matrix.  The plain reference is ``gbdt_reference.py`` beside this file.
"""
from __future__ import annotations

import contextlib
import os
from typing import Any, Dict, List

import numpy as np

from benchmark import datagen
from benchmark.families import gbdt_reference as reference


def build(run) -> "GBDTSystem":
    return GBDTSystem(run)


class GBDTSystem:
    #: the name of one operation, for spans and for the trace
    op_name = "fit"

    def __init__(self, run):
        from mmlspark_tpu.lightgbm import GBDTParams
        cfg = self.cfg = run.config
        self.run = run
        self.params = GBDTParams(num_iterations=cfg["iterations_per_fit"],
                                 **cfg["params"])
        self.flips = int(run.mix["label_flips"])
        threads = max(1, min(16, (os.cpu_count() or 2) - 1))
        with run.spans.span("make_data"):
            self.X, self.y, self.X_hold, self.y_hold = datagen.tabular(
                run.seed, cfg["rows"], cfg["features"], cfg["block_rows"],
                cfg["holdout_rows"], cfg["label_noise"], threads=threads)
        self.threads = threads
        self.mesh = None
        if cfg.get("shard_rows"):
            from mmlspark_tpu.parallel import data_parallel_mesh
            # built once and kept: the program caches its sharded grower
            # under id(mesh)
            self.mesh = data_parallel_mesh(cfg["mesh_devices"])
        #: binning and, on one chip, the upload happen in the first fit only
        self.bin_cache: Dict[str, Any] = {}
        self.units_per_fit = float(cfg["rows"]) * cfg["iterations_per_fit"]
        self.fits: List[Dict[str, Any]] = []
        self.root_feature = None

    # --------------------------------------------------------------- train
    def _train(self, y: np.ndarray, callbacks=None):
        from mmlspark_tpu.lightgbm import train
        from mmlspark_tpu.parallel import active_mesh
        scope = active_mesh(self.mesh) if self.mesh is not None \
            else contextlib.nullcontext()
        with scope:
            return train(self.X, y, self.params, bin_cache=self.bin_cache,
                         shard_rows=self.mesh is not None, callbacks=callbacks)

    def warm_up(self) -> None:
        """The set-up fit: bins, uploads, compiles or loads every program,
        and is the fit whose outputs are checked against the reference."""
        from mmlspark_tpu.observability.collector import get_collector
        from mmlspark_tpu.observability.tracing import trace_span
        run, cfg = self.run, self.cfg
        shards: Dict[str, Any] = {}

        def watch_shards(_it, _ev):
            if shards:
                return
            import jax
            f = cfg["features"]
            for a in jax.live_arrays():
                if a.dtype == np.uint8 and a.ndim == 2 and a.shape[1] == f \
                        and a.shape[0] >= cfg["rows"]:
                    shards["devices"] = sorted(
                        int(s.device.id) for s in a.addressable_shards)
                    shards["rows"] = sorted(
                        {int(s.data.shape[0]) for s in a.addressable_shards})

        with run.spans.span("warm_fit"), \
                trace_span("benchmark.warm_fit") as sp:
            res = self._train(self.y, callbacks=[watch_shards]
                              if self.mesh is not None else None)
        spans = [s for s in get_collector().trace(sp.trace_id)
                 if s.name == "lightgbm.train"]
        if not spans:
            raise RuntimeError("the program recorded no lightgbm.train span")
        attrs = dict(spans[-1].attributes)
        path = {k: attrs.get(k) for k in ("hist_backend", "quantized", "chunk")}
        run.facts["gbdt_path"] = path
        run.facts["binning_s"] = attrs.get("phase.binning_s")
        run.note(f"path that ran: {path} binning_s={run.facts['binning_s']}")
        for key, want in cfg.get("expect_path", {}).items():
            if path.get(key) != want:
                run.fail(f"path {key}={path.get(key)!r}, the configuration "
                         f"expects {want!r}")
        if self.mesh is not None:
            want = sorted(int(d.id) for d in self.mesh.devices.flat)
            run.note(f"binned shards: {shards}")
            if shards.get("devices") != want:
                run.fail(f"binned matrix shards on devices "
                         f"{shards.get('devices')}, not one on each of {want}")
        with run.spans.span("check_reference"):
            self._check_against_reference(res.booster)

    def _check_against_reference(self, booster) -> None:
        run, cfg, p = self.run, self.cfg, self.params
        iters = cfg["iterations_per_fit"]
        if booster.num_trees != iters:
            run.fail(f"{booster.num_trees} trees, wanted {iters}")
        binned = self.bin_cache["binned"]
        gains = reference.root_split_gains(
            binned[:cfg["rows"]], self.y, int(self.bin_cache["mapper"].num_bins),
            p.min_data_in_leaf, p.min_sum_hessian_in_leaf, p.lambda_l2,
            threads=self.threads)
        self.root_feature = int(booster.split_feature[0, 0])
        ok, note = reference.check_root_split(
            gains, self.root_feature, int(booster.threshold_bin[0, 0]))
        run.note(note)
        if not ok:
            run.fail("first tree's " + note)
        acc = reference.holdout_accuracy(reference.booster_arrays(booster),
                                         self.X_hold, self.y_hold)
        ref = cfg["reference"]
        run.facts["holdout_accuracy"] = acc
        run.note(f"held-out accuracy {acc:.4f}; reference "
                 f"{ref['holdout_accuracy']:.4f} +- {ref['tolerance']}")
        if abs(acc - ref["holdout_accuracy"]) > ref["tolerance"]:
            run.fail(f"held-out accuracy {acc:.4f} is not within "
                     f"{ref['tolerance']} of the reference's "
                     f"{ref['holdout_accuracy']:.4f}")

    # ------------------------------------------------------------ measured
    def operation(self, i: int) -> float:
        """One whole fit on labels no other fit saw.  ``train`` ends in the
        ``device_get`` of its trees, so it returns on finished work."""
        y = datagen.flip_labels(self.y, self.run.seed, i, self.flips)
        booster = self._train(y).booster
        self.fits.append({"trees": booster.num_trees,
                          "root_feature": int(booster.split_feature[0, 0]),
                          "finite": bool(np.isfinite(booster.leaf_value).all())})
        return self.units_per_fit

    def failed_operations(self) -> int:
        """Fits of the window whose booster is not what a fit must give."""
        want = self.cfg["iterations_per_fit"]
        bad = [f for f in self.fits
               if f["trees"] != want or not f["finite"]
               or f["root_feature"] != self.root_feature]
        if bad:
            self.run.fail(f"{len(bad)} fits of the window failed their "
                          f"check, the first {bad[0]}")
        return len(bad)

    def window_facts(self) -> Dict[str, Any]:
        return {"fits": len(self.fits),
                "iterations": len(self.fits) * self.cfg["iterations_per_fit"]}
