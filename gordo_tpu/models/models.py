"""
Concrete estimator classes (reference parity: gordo/machine/model/models.py).

``AutoEncoder`` / ``LSTMAutoEncoder`` / ``LSTMForecast`` mirror
KerasAutoEncoder / KerasLSTMAutoEncoder / KerasLSTMForecast (models.py:294,
639, 633); ``RawModelRegressor`` mirrors KerasRawModelRegressor (:332).
Legacy class names are importable aliases so reference YAML configs and
pickles keep working.
"""

import logging
from pprint import pformat
from typing import Any, Callable, Dict, Optional, Tuple, Union

import numpy as np
import pandas as pd
from sklearn.base import TransformerMixin
from sklearn.exceptions import NotFittedError
from sklearn.metrics import explained_variance_score

from gordo_tpu.models.core import BaseJaxEstimator, _batch_bucket
from gordo_tpu.models.specs import ModelSpec, SequentialNet, make_optimizer, resolve_dtype
from gordo_tpu.ops.windowing import num_windows

# ensure factories register on import
from gordo_tpu.models import factories  # noqa: F401

logger = logging.getLogger(__name__)


class AutoEncoder(BaseJaxEstimator, TransformerMixin):
    """
    Feedforward autoencoder scoring by explained variance of reconstruction
    (reference: models.py:294-329).
    """

    def score(
        self,
        X: Union[np.ndarray, pd.DataFrame],
        y: Union[np.ndarray, pd.DataFrame],
        sample_weight: Optional[np.ndarray] = None,
    ) -> float:
        if not hasattr(self, "params_"):
            raise NotFittedError(
                f"This {self.__class__.__name__} has not been fitted yet."
            )
        out = self.predict(X)
        yv = y.values if hasattr(y, "values") else np.asarray(y)
        return explained_variance_score(yv, out)

    def transform(self, X):
        return self.predict(X)


class LSTMBaseEstimator(BaseJaxEstimator, TransformerMixin):
    """
    Many-to-one LSTM base (reference: models.py:391-548). Samples are
    sliding windows of ``lookback_window`` rows; the target row is offset by
    ``lookahead`` (0 = reconstruct window end, 1 = forecast next step).
    """

    def __init__(
        self,
        kind: Union[Callable, str],
        lookback_window: int = 1,
        batch_size: int = 32,
        **kwargs,
    ) -> None:
        kwargs["lookback_window"] = lookback_window
        kwargs["batch_size"] = batch_size
        super().__init__(kind, **kwargs)
        self.lookback_window = lookback_window
        self.batch_size = batch_size

    @property
    def lookahead(self) -> int:
        raise NotImplementedError()

    @property
    def _windowed(self) -> bool:
        return True

    def get_metadata(self):
        metadata = super().get_metadata()
        metadata.update({"forecast_steps": self.lookahead})
        return metadata

    @staticmethod
    def _validate_and_fix_size_of_X(X: np.ndarray) -> np.ndarray:
        if X.ndim == 1:
            logger.info("Reshaping X from an array to a matrix of shape (%d, 1)", len(X))
            X = X.reshape(len(X), 1)
        return X

    def fit(self, X: np.ndarray, y: np.ndarray, **kwargs):
        X = X.values if hasattr(X, "values") else np.asarray(X)
        y = y.values if hasattr(y, "values") else np.asarray(y)
        X = self._validate_and_fix_size_of_X(X)
        if y.ndim == 1:
            y = y.reshape(len(y), 1)
        if len(X) < self.lookback_window + self.lookahead:
            raise ValueError(
                f"Found {len(X)} timesteps; need at least "
                f"lookback_window + lookahead = "
                f"{self.lookback_window + self.lookahead}"
            )
        return super().fit(X, y, **kwargs)

    def predict(self, X: np.ndarray, **kwargs) -> np.ndarray:
        """
        Returns (n_samples - lookback_window + 1 - lookahead) x n_features_out
        predictions, aligned so row i predicts the window ending at
        X[i + lookback_window - 1 + lookahead] (reference: models.py:550-595).

        The raw (rows, features) frame ships to the device ONCE and the
        windows are gathered inside the compiled program (chunked —
        FleetTrainer's predict machinery with a fleet of one): a host-side
        gather would transfer every row ``lookback_window`` times, the
        dominant request cost on PCIe links. Rows are padded to a
        power-of-two bucket so jit sees a bounded set of shapes.
        """
        X = X.values if hasattr(X, "values") else np.asarray(X)
        X = self._validate_and_fix_size_of_X(X).astype(np.float32, copy=False)
        # padded-bucket artifacts take real-width inputs; the program
        # wants its padded width (pad columns are inert — core.py)
        X = self._pad_active_input(X)
        n_out = num_windows(len(X), self.lookback_window, self.lookahead)
        if n_out <= 0:
            # same loud contract as ops.windowing's index builder
            raise ValueError(
                f"Not enough timesteps ({len(X)}) for "
                f"lookback_window={self.lookback_window}, "
                f"lookahead={self.lookahead}"
            )
        bucket = _batch_bucket(len(X), cap=None, base=2)
        if bucket > len(X):
            X = np.pad(X, ((0, bucket - len(X)), (0, 0)))
        trainer = self._spec_serving_trainer()
        params = getattr(self, "_device_params_stacked", None)
        if params is None:
            import jax

            params = jax.tree.map(lambda a: a[None], jax.device_put(self.params_))
            self._device_params_stacked = params
        out = trainer.predict(params, X[None])[0]
        return self._strip_pad_output(np.asarray(out[:n_out]))

    def _spec_serving_trainer(self):
        """
        A FleetTrainer shared ON the spec (like the solo apply fn,
        core.py): every estimator of a bucket reuses one set of compiled
        chunked-window predict programs instead of tracing per estimator.
        """
        if not hasattr(self, "params_"):
            raise NotFittedError(
                f"This {self.__class__.__name__} has not been fitted yet."
            )
        spec = self.spec_
        trainers = getattr(spec, "_serving_trainers", None)
        if trainers is None:
            trainers = spec._serving_trainers = {}
        trainer = trainers.get(self.lookahead)
        if trainer is None:
            from gordo_tpu.parallel.fleet import FleetTrainer

            trainer = FleetTrainer(spec, lookahead=self.lookahead, donate=False)
            trainers[self.lookahead] = trainer
        return trainer

    def score(
        self,
        X: Union[np.ndarray, pd.DataFrame],
        y: Union[np.ndarray, pd.DataFrame],
        sample_weight: Optional[np.ndarray] = None,
    ) -> float:
        if not hasattr(self, "params_"):
            raise NotFittedError(
                f"This {self.__class__.__name__} has not been fitted yet."
            )
        out = self.predict(X)
        yv = y.values if hasattr(y, "values") else np.asarray(y)
        return explained_variance_score(yv[-len(out):], out)


class LSTMForecast(LSTMBaseEstimator):
    """1-step-ahead forecaster (reference: models.py:633-636)."""

    @property
    def lookahead(self) -> int:
        return 1


class LSTMAutoEncoder(LSTMBaseEstimator):
    """Window-end reconstructor (reference: models.py:639-642)."""

    @property
    def lookahead(self) -> int:
        return 0


class TransformerAutoEncoder(LSTMBaseEstimator):
    """
    Transformer-encoder window reconstructor — new backend beyond the
    reference (BASELINE.json config #5). Same windowed many-to-one contract
    as LSTMAutoEncoder; architecture from factories/transformer.py.
    """

    @property
    def lookahead(self) -> int:
        return 0


class TransformerForecast(LSTMBaseEstimator):
    """Transformer-encoder 1-step-ahead forecaster (new backend)."""

    @property
    def lookahead(self) -> int:
        return 1


class TCNAutoEncoder(LSTMBaseEstimator):
    """
    Dilated-causal-conv (TCN) window reconstructor — new backend beyond the
    reference (BASELINE.json config #5); architecture from factories/tcn.py.
    """

    @property
    def lookahead(self) -> int:
        return 0


class TCNForecast(LSTMBaseEstimator):
    """TCN 1-step-ahead forecaster (new backend)."""

    @property
    def lookahead(self) -> int:
        return 1


class GRUAutoEncoder(LSTMBaseEstimator):
    """
    Stacked-GRU window reconstructor — a recurrent family beyond the
    reference's LSTM-only zoo (3 gates to the LSTM's 4: ~25% fewer
    recurrent FLOPs/params at equal width). Architecture from
    factories/gru.py; same windowed contract as LSTMAutoEncoder.
    """

    @property
    def lookahead(self) -> int:
        return 0


class GRUForecast(LSTMBaseEstimator):
    """Stacked-GRU 1-step-ahead forecaster (new backend)."""

    @property
    def lookahead(self) -> int:
        return 1


# layer path/name -> SequentialNet layer kind
_RAW_LAYER_KINDS = {
    "dense": "dense",
    "lstm": "lstm",
    "dropout": "dropout",
    "activation": "activation",
    "flatten": "flatten",
}


def _parse_raw_layer(entry: Union[str, Dict[str, Any]]) -> Tuple[str, Tuple]:
    """One raw-spec layer entry -> (kind, frozen kwargs)."""
    if isinstance(entry, str):
        path, kwargs = entry, {}
    elif isinstance(entry, dict) and len(entry) == 1:
        path, kwargs = next(iter(entry.items()))
        kwargs = dict(kwargs or {})
    else:
        raise ValueError(f"Cannot parse raw layer entry: {entry!r}")
    name = path.rsplit(".", 1)[-1].lower()
    if name not in _RAW_LAYER_KINDS:
        raise ValueError(
            f"Unsupported raw layer type {path!r}; supported: "
            f"{sorted(_RAW_LAYER_KINDS)}"
        )
    return _RAW_LAYER_KINDS[name], tuple(sorted(kwargs.items()))


class RawModelRegressor(AutoEncoder):
    """
    Estimator built from a raw architecture config
    (reference: models.py:332-388)::

        compile:
          loss: mse
          optimizer: adam
        spec:
          layers:
            - Dense: {units: 4, activation: tanh}
            - Dense: {units: 1}

    Legacy reference specs using ``tensorflow.keras.models.Sequential`` /
    ``tensorflow.keras.layers.*`` paths parse too: the terminal class name
    selects the layer type.
    """

    _expected_keys = ("spec", "compile")

    def load_kind(self, kind):
        return kind

    def __repr__(self):
        return f"{self.__class__.__name__}(kind: {pformat(self.kind)})"

    def _build_spec(self) -> ModelSpec:
        if not all(k in self.kind for k in self._expected_keys):
            raise ValueError(
                f"Expected spec to have keys: {self._expected_keys}, "
                f"but found {list(self.kind)}"
            )
        spec_cfg = self.kind["spec"]
        # unwrap a legacy {"...Sequential": {"layers": [...]}} nesting
        if isinstance(spec_cfg, dict) and "layers" not in spec_cfg and len(spec_cfg) == 1:
            spec_cfg = next(iter(spec_cfg.values()))
        layers = tuple(_parse_raw_layer(entry) for entry in spec_cfg["layers"])

        compile_cfg = dict(self.kind.get("compile") or {})
        optimizer = compile_cfg.get("optimizer", "Adam")
        optimizer_kwargs = dict(compile_cfg.get("optimizer_kwargs", {}))
        if isinstance(optimizer, dict) and len(optimizer) == 1:
            path, okw = next(iter(optimizer.items()))
            optimizer = path.rsplit(".", 1)[-1]
            optimizer_kwargs.update(okw or {})

        module = SequentialNet(
            layers=layers, dtype=resolve_dtype(self.kwargs.get("dtype", "float32"))
        )
        # validate the optimizer name eagerly for a clear config error
        make_optimizer(optimizer, optimizer_kwargs)
        return ModelSpec(
            module=module,
            optimizer=optimizer,
            optimizer_kwargs=optimizer_kwargs,
            loss=compile_cfg.get("loss", "mse"),
        )


# -- legacy aliases (reference class names) -------------------------------
KerasAutoEncoder = AutoEncoder
KerasLSTMBaseEstimator = LSTMBaseEstimator
KerasLSTMAutoEncoder = LSTMAutoEncoder
KerasLSTMForecast = LSTMForecast
KerasRawModelRegressor = RawModelRegressor
