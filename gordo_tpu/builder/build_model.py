"""
ModelBuilder: the train-one-Machine pipeline
(reference parity: gordo/builder/build_model.py).

data fetch -> model from definition -> cross-validation (with per-tag and
aggregate scorers) -> full fit -> BuildMetadata assembly -> artifact dump,
with a content-hash build cache via the disk registry.

TPU notes: seeding goes through JAX's splittable PRNG discipline — the
evaluation seed becomes the default ``jax.random.PRNGKey`` for estimator
fits (set_seed), alongside numpy/python seeds for the sklearn edges.
"""

import hashlib
import json
import logging
import os
import random
import time
from datetime import datetime, timezone
from functools import partial
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np
import pandas as pd
from sklearn import metrics
from sklearn.base import BaseEstimator, TransformerMixin
from sklearn.model_selection import cross_validate
from sklearn.pipeline import Pipeline

from gordo_tpu import MAJOR_VERSION, MINOR_VERSION, __version__, serializer
from gordo_tpu.data import _get_dataset
from gordo_tpu.observability import tracing
from gordo_tpu.observability.profiler import maybe_trace
from gordo_tpu.machine import Machine
from gordo_tpu.machine.metadata import (
    BuildMetadata,
    CrossValidationMetaData,
    DatasetBuildMetadata,
    ModelBuildMetadata,
)
from gordo_tpu.models.base import GordoBase
from gordo_tpu.models.utils import metric_wrapper
from gordo_tpu.utils import disk_registry

logger = logging.getLogger(__name__)


class ModelBuilder:
    def __init__(self, machine: Machine):
        """
        Build a model for a given Machine.

        Example
        -------
        >>> from gordo_tpu.machine import Machine
        >>> machine = Machine(
        ...     name="special-model-name",
        ...     model={"sklearn.decomposition.PCA": {"svd_solver": "auto"}},
        ...     dataset={
        ...         "type": "RandomDataset",
        ...         "train_start_date": "2017-12-25 06:00:00Z",
        ...         "train_end_date": "2017-12-30 06:00:00Z",
        ...         "tag_list": [["Tag 1", None], ["Tag 2", None]],
        ...     },
        ...     project_name='test-proj',
        ... )
        >>> builder = ModelBuilder(machine=machine)
        >>> len(builder.cache_key)
        128
        """
        # copy via dict round-trip so we never mutate the caller's machine;
        # skip re-validation (the caller's Machine already passed it)
        self.machine = Machine.unvalidated(**machine.to_dict())
        self._cached_model_path: Optional[Union[os.PathLike, str]] = None

    @property
    def cached_model_path(self) -> Union[os.PathLike, str, None]:
        return self._cached_model_path

    @cached_model_path.setter
    def cached_model_path(self, value):
        self._cached_model_path = value

    def build(
        self,
        output_dir: Optional[Union[os.PathLike, str]] = None,
        model_register_dir: Optional[Union[os.PathLike, str]] = None,
        replace_cache: bool = False,
    ) -> Tuple[BaseEstimator, Machine]:
        """
        Return (model, machine-with-build-metadata); optionally persisting to
        ``output_dir`` and caching via ``model_register_dir``
        (reference: build_model.py:83-158).
        """
        cv_only = (
            str(self.machine.evaluation.get("cv_mode", "")).lower()
            == "cross_val_only"
        )

        cached = None
        if model_register_dir:
            if replace_cache:
                logger.info("replace_cache=True, deleting any existing cache entry")
                disk_registry.delete_value(model_register_dir, self.cache_key)
            else:
                self.cached_model_path = self.check_cache(model_register_dir)
                cached = self._restore_cached(model_register_dir)

        if cached is not None:
            model, machine = cached
        else:
            model, machine = self._build()
            # never cache/persist a cross_val_only result: the model is
            # unfitted and a later cache hit would serve it as trained
            if model_register_dir and output_dir and not cv_only:
                self.cached_model_path = self._save_model(
                    model=model, machine=machine, output_dir=output_dir
                )
                logger.info("Built model, deposited at %s", self.cached_model_path)
                disk_registry.write_key(
                    model_register_dir, self.cache_key, str(self.cached_model_path)
                )

        if (
            output_dir
            and str(self.cached_model_path or "") != str(output_dir)
            and not cv_only
        ):
            self.cached_model_path = self._save_model(
                model=model, machine=machine, output_dir=output_dir
            )
        return model, machine

    def _restore_cached(
        self, model_register_dir
    ) -> Optional[Tuple[BaseEstimator, Machine]]:
        """
        Rehydrate (model, machine) from a registry hit, grafting the current
        request's user metadata and runtime onto the stored build metadata.
        A hit whose artifact lost its metadata is invalidated instead.
        """
        if not self.cached_model_path:
            return None
        stored = serializer.load_metadata(self.cached_model_path)
        if "metadata" not in stored:
            logger.warning(
                "Cached artifact at %s has no metadata; rebuilding",
                self.cached_model_path,
            )
            disk_registry.delete_value(model_register_dir, self.cache_key)
            self.cached_model_path = None
            return None
        stored["metadata"]["user_defined"] = self.machine.metadata.user_defined
        stored["runtime"] = self.machine.runtime
        return serializer.load(self.cached_model_path), Machine.unvalidated(**stored)

    def _build(self) -> Tuple[BaseEstimator, Machine]:
        """Run the actual build (reference: build_model.py:160-303),
        profiler-traced when GORDO_TPU_PROFILE_DIR is configured (its
        spans then lie on that trace's host plane) and span-logged when
        GORDO_TPU_TRACE_LOG is."""
        with maybe_trace(f"build-{self.machine.name}"), tracing.start_span(
            "build.machine", machine=self.machine.name
        ):
            return self._build_traced()

    DEFAULT_CV = {"sklearn.model_selection.TimeSeriesSplit": {"n_splits": 3}}

    def _build_traced(self) -> Tuple[BaseEstimator, Machine]:
        evaluation = self.machine.evaluation
        self.set_seed(seed=evaluation.get("seed", 0))

        dataset = _get_dataset(self.machine.dataset.to_dict())
        start = time.time()
        with tracing.start_span(
            "build.fetch", machine=self.machine.name
        ):
            X, y = dataset.get_data()
        fetch_secs = time.time() - start

        model = serializer.from_definition(self.machine.model)
        self._inject_seed(model, evaluation.get("seed", 0))

        # the returned machine is a working copy that carries build metadata
        machine = Machine.unvalidated(**self.machine.to_dict())

        cv_mode = str(evaluation.get("cv_mode", "full_build")).lower()
        cv_meta = CrossValidationMetaData()
        if cv_mode in ("cross_val_only", "full_build"):
            cv_meta = self._run_cross_validation(model, X, y)
            if cv_mode == "cross_val_only":
                machine.metadata.build_metadata = self._assemble_metadata(
                    dataset, fetch_secs, cv_meta
                )
                return model, machine

        start = time.time()
        with tracing.start_span(
            "build.fit", machine=self.machine.name
        ):
            model.fit(X, y)
        fit_secs = time.time() - start

        machine.metadata.build_metadata = self._assemble_metadata(
            dataset, fetch_secs, cv_meta, fitted=(model, X, fit_secs)
        )
        return model, machine

    def _run_cross_validation(self, model, X, y) -> CrossValidationMetaData:
        """
        Cross-validate with per-tag + aggregate scorers and package the fold
        scores/splits (behavioral parity: reference build_model.py:203-257).
        Models without a ``predict`` surface produce empty metadata.
        """
        if not hasattr(model, "predict"):
            logger.debug("Unable to score model; it has no 'predict' attribute")
            return CrossValidationMetaData()

        start = time.time()
        evaluation = self.machine.evaluation
        scorers = self.build_metrics_dict(
            self.metrics_from_list(evaluation.get("metrics")),
            y,
            scaler=evaluation.get("scoring_scaler"),
        )
        splitter = serializer.from_definition(evaluation.get("cv", self.DEFAULT_CV))

        # anomaly models own their CV (threshold derivation rides along)
        run = getattr(model, "cross_validate", None) or partial(cross_validate, model)
        with tracing.start_span(
            "build.cv", machine=self.machine.name
        ):
            cv = run(X=X, y=y, scoring=scorers, return_estimator=True, cv=splitter)

        return CrossValidationMetaData(
            cv_duration_sec=time.time() - start,
            scores={
                name: self._fold_stats(cv[f"test_{name}"]) for name in scorers
            },
            splits=self.build_split_dict(X, splitter),
        )

    @staticmethod
    def _fold_stats(fold_values) -> Dict[str, Any]:
        """Summary stats plus each fold's raw value for one scorer."""
        summary = {
            "fold-mean": fold_values.mean(),
            "fold-std": fold_values.std(),
            "fold-max": fold_values.max(),
            "fold-min": fold_values.min(),
        }
        summary.update(
            {f"fold-{n}": value for n, value in enumerate(fold_values.tolist(), 1)}
        )
        return summary

    def _assemble_metadata(
        self,
        dataset,
        fetch_secs: float,
        cv_meta: CrossValidationMetaData,
        fitted: Optional[Tuple[BaseEstimator, Any, float]] = None,
    ) -> BuildMetadata:
        """
        BuildMetadata for this build. ``fitted=(model, X, fit_secs)`` adds
        the trained-model fields (offset, creation date, harvested
        GordoBase metadata); cross_val_only builds leave them default.
        """
        if fitted is None:
            model_meta = ModelBuildMetadata(cross_validation=cv_meta)
        else:
            model, X, fit_secs = fitted
            model_meta = ModelBuildMetadata(
                model_offset=self._determine_offset(model, X),
                model_creation_date=str(datetime.now(timezone.utc).astimezone()),
                model_builder_version=__version__,
                model_training_duration_sec=fit_secs,
                cross_validation=cv_meta,
                model_meta=self._extract_metadata_from_model(model),
            )
        return BuildMetadata(
            model=model_meta,
            dataset=DatasetBuildMetadata(
                query_duration_sec=fetch_secs,
                dataset_meta=dataset.get_metadata(),
            ),
        )

    @staticmethod
    def set_seed(seed: int):
        """
        Seed the host-side RNG domains the sklearn edges use
        (reference seeds tf/np/random: build_model.py:305-309). JAX fits are
        seeded explicitly per estimator via :meth:`_inject_seed` — no global
        device-RNG state exists to set.
        """
        logger.info("Setting random seed: %r", seed)
        np.random.seed(seed)
        random.seed(seed)

    @staticmethod
    def _inject_seed(model: BaseEstimator, seed: int):
        """
        Give every JAX estimator in the model tree an explicit PRNG seed
        (unless its config already pins one) — the splittable-PRNG analogue
        of the reference's global tf seeding.
        """
        from gordo_tpu.models.core import BaseJaxEstimator

        if isinstance(model, BaseJaxEstimator):
            model.kwargs.setdefault("seed", seed)
        if isinstance(model, Pipeline):
            for _, step in model.steps:
                ModelBuilder._inject_seed(step, seed)
            return
        for val in getattr(model, "__dict__", {}).values():
            if isinstance(val, (Pipeline, BaseEstimator)):
                ModelBuilder._inject_seed(val, seed)

    @staticmethod
    def build_split_dict(X: pd.DataFrame, split_obj) -> dict:
        """Cross-validation train/test split metadata (reference: :310-339)."""
        split_metadata: Dict[str, Any] = dict()
        for i, (train_ind, test_ind) in enumerate(split_obj.split(X)):
            split_metadata.update(
                {
                    f"fold-{i + 1}-train-start": X.index[train_ind[0]],
                    f"fold-{i + 1}-train-end": X.index[train_ind[-1]],
                    f"fold-{i + 1}-test-start": X.index[test_ind[0]],
                    f"fold-{i + 1}-test-end": X.index[test_ind[-1]],
                    f"fold-{i + 1}-n-train": len(train_ind),
                    f"fold-{i + 1}-n-test": len(test_ind),
                }
            )
        return split_metadata

    @staticmethod
    def build_metrics_dict(
        metrics_list: list,
        y: pd.DataFrame,
        scaler: Optional[Union[TransformerMixin, str, dict]] = None,
    ) -> dict:
        """
        Per-tag ('{score}-{tag}') and aggregate ('{score}') scorers for
        sklearn cross_validate (reference: :341-411).
        """
        if scaler:
            if isinstance(scaler, (str, dict)):
                scaler = serializer.from_definition(scaler)
            # bare array keeps later ndarray transforms warning-free
            scaler.fit(np.asarray(y))

        def _score_factory(metric_func, col_index):
            def _score_per_tag(y_true, y_pred):
                y_true = getattr(y_true, "values", y_true)
                y_pred = getattr(y_pred, "values", y_pred)
                return metric_func(y_true[:, col_index], y_pred[:, col_index])

            return _score_per_tag

        metrics_dict = {}
        for metric in metrics_list:
            metric_str = metric.__name__.replace("_", "-")
            for index, col in enumerate(y.columns):
                metrics_dict[
                    f"{metric_str}-{str(col).replace(' ', '-')}"
                ] = metrics.make_scorer(
                    metric_wrapper(
                        _score_factory(metric, index),
                        scaler=scaler if scaler else None,
                    )
                )
            metrics_dict[metric_str] = metrics.make_scorer(
                metric_wrapper(metric, scaler=scaler if scaler else None)
            )
        return metrics_dict

    @staticmethod
    def metrics_from_list(metric_list: Optional[List[str]] = None) -> List[Callable]:
        """Resolve metric function paths (or bare sklearn.metrics names)."""
        from gordo_tpu.workflow.config_elements.normalized_config import (
            NormalizedConfig,
        )

        import pydoc

        defaults = NormalizedConfig.DEFAULT_CONFIG_GLOBALS["evaluation"]["metrics"]
        funcs = []
        for func_path in metric_list or defaults:
            func = pydoc.locate(func_path)
            funcs.append(func if func is not None else getattr(metrics, func_path))
        return funcs

    @staticmethod
    def _determine_offset(model: BaseEstimator, X) -> int:
        """len(X) - len(model output): the model's output offset."""
        out = model.predict(X) if hasattr(model, "predict") else model.transform(X)
        return len(X) - len(out)

    @staticmethod
    def _save_model(model, machine, output_dir):
        os.makedirs(output_dir, exist_ok=True)
        serializer.dump(
            model,
            output_dir,
            metadata=machine.to_dict() if isinstance(machine, Machine) else machine,
        )
        return output_dir

    @staticmethod
    def _extract_metadata_from_model(
        model: BaseEstimator, metadata: Optional[dict] = None
    ) -> dict:
        """
        Recursively harvest GordoBase.get_metadata() from a (possibly nested)
        estimator (reference: :468-519).
        """
        metadata = dict(metadata or {})
        if isinstance(model, Pipeline):
            metadata.update(
                ModelBuilder._extract_metadata_from_model(model.steps[-1][1])
            )
            return metadata
        if isinstance(model, GordoBase):
            metadata.update(model.get_metadata())
        for val in model.__dict__.values():
            if isinstance(val, Pipeline):
                metadata.update(
                    ModelBuilder._extract_metadata_from_model(val.steps[-1][1])
                )
            elif isinstance(val, (GordoBase, BaseEstimator)):
                metadata.update(ModelBuilder._extract_metadata_from_model(val))
        return metadata

    @property
    def cache_key(self) -> str:
        return self.calculate_cache_key(self.machine)

    @staticmethod
    def calculate_cache_key(machine: Machine) -> str:
        """
        Content hash identifying "the same build": everything that changes
        the produced model re-keys the cache (name, model config, dataset
        config, evaluation config, framework major.minor), while
        runtime/metadata — which don't affect training — deliberately do
        not. sha3_512 for parity with the reference registry's key width.
        """
        fingerprint = {
            "name": machine.name,
            "model_config": machine.model,
            "data_config": machine.dataset.to_dict(),
            "evaluation_config": machine.evaluation,
            "gordo-tpu-major-version": MAJOR_VERSION,
            "gordo-tpu-minor-version": MINOR_VERSION,
        }
        payload = json.dumps(fingerprint, sort_keys=True, default=str)
        return hashlib.sha3_512(payload.encode("ascii")).hexdigest()

    def check_cache(
        self, model_register_dir: Union[os.PathLike, str]
    ) -> Optional[str]:
        """Return the cached artifact path for this build, if present."""
        existing = disk_registry.get_value(model_register_dir, self.cache_key)
        if existing and Path(existing).exists():
            logger.debug("Found existing model at %s", existing)
            return existing
        if existing:
            logger.warning(
                "Registry entry %s points at a missing path %s", self.cache_key, existing
            )
        return None
