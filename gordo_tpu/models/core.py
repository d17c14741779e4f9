"""
The sklearn-API <-> JAX bridge: BaseJaxEstimator.

Reference parity: gordo/machine/model/models.py:35-291 (KerasBaseEstimator) —
same contract (``kind``-selected factory, sklearn fit/predict/score/
get_params, from_definition/into_definition hooks, pickling, history
metadata) with the engine swapped for Flax + optax under ``jax.jit``:

- training runs as one jitted epoch program: in-jit shuffle
  (``jax.random.permutation``), ``lax.scan`` over fixed-size minibatches,
  masked loss for the ragged tail — static shapes, no recompilation between
  epochs, data stays device-resident for the whole fit;
- sequence models window via device-side gathers (gordo_tpu.ops.windowing)
  instead of Keras TimeseriesGenerator;
- pickling host-materializes the param pytree (``jax.device_get``) the way
  the reference round-trips Keras weights through in-memory HDF5
  (models.py:158-185).
"""

import copy
import logging
import math
import time
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pandas as pd
from sklearn.base import BaseEstimator
from sklearn.exceptions import NotFittedError
from sklearn.metrics import explained_variance_score

from gordo_tpu.models.base import GordoBase
from gordo_tpu.models.register import register_model_builder
from gordo_tpu.models.specs import ModelSpec, per_sample_loss
from gordo_tpu.observability import attribution

logger = logging.getLogger(__name__)


def _materialize_callbacks(raw) -> list:
    """
    fit-arg ``callbacks`` -> list of Callback objects. The serializer
    already materializes definitions inside model configs; raw dicts
    (single-key definition form) are built here for direct constructor use.
    """
    if not raw:
        return []
    from gordo_tpu.models.callbacks import Callback

    out = []
    for item in raw:
        if isinstance(item, Callback):
            out.append(item)
        elif isinstance(item, dict):
            from gordo_tpu.serializer import from_definition

            try:
                obj = from_definition(item)
            except ValueError:
                # e.g. ReduceLROnPlateau / ModelCheckpoint — Keras callback
                # types with no native equivalent. These were silently
                # ignored before callbacks ran at all; keep configs loading
                # but say so
                logger.warning(
                    "Ignoring unsupported training callback %s",
                    next(iter(item), "?"),
                )
                continue
            if not isinstance(obj, Callback):
                logger.warning(
                    "Ignoring non-Callback training callback %s",
                    type(obj).__name__,
                )
                continue
            out.append(obj)
        else:
            # e.g. a real keras callback object (bare `keras` may be
            # importable even though the engine here is JAX): skip like
            # the pre-callback-support behavior, loudly
            logger.warning(
                "Ignoring unsupported training callback object %s",
                type(item).__name__,
            )
    return out

# attributes never pickled (compiled/jitted/device state)
_EPHEMERAL_ATTRS = (
    "_apply_fn",
    "_train_epoch_fn",
    "_device_params",
    "_device_params_stacked",
)


def _batch_bucket(n: int, cap: Optional[int] = None, base: int = 4) -> int:
    """
    Smallest power of ``base`` >= n, optionally capped (XLA shape
    bucketing). base=4 bounds compiles hardest (<=4x padded compute);
    base=2 halves the padding waste at twice the distinct shapes.
    """
    bucket = 1
    while bucket < n and (cap is None or bucket < cap):
        bucket *= base
    return bucket if cap is None else min(bucket, cap)

# Default PRNG seed for fits without an explicit ``seed`` kwarg (the builder
# injects the Machine's evaluation seed into each estimator's kwargs).
DEFAULT_SEED = 0


def solo_init_key(seed: int) -> jax.Array:
    """
    The param-init PRNG key a solo ``fit`` with this seed uses. The fleet
    builder derives its per-machine keys through this same function so the
    same machine initializes with IDENTICAL params on either build path —
    the reference's global-seed behavior (every pod with the same seed gets
    the same Keras init for the same architecture).
    """
    return jax.random.split(jax.random.PRNGKey(int(seed)))[1]


class BaseJaxEstimator(GordoBase, BaseEstimator):

    supported_fit_args = [
        "batch_size",
        "epochs",
        "verbose",
        "callbacks",
        "validation_split",
        "shuffle",
        # accepted from older machine configs and ignored (it once chose
        # a training schedule): listed here so that a config carrying it
        # still builds and the key never reaches a model factory
        "epoch_chunk",
        "class_weight",
        "initial_epoch",
        "steps_per_epoch",
        "validation_batch_size",
        "max_queue_size",
        "workers",
        "use_multiprocessing",
    ]

    # window geometry defaults; sequence subclasses override
    lookback_window: int = 1

    @property
    def lookahead(self) -> int:
        return 0

    @property
    def _windowed(self) -> bool:
        return False

    def __init__(self, kind: Union[str, Callable], **kwargs) -> None:
        self.kind = self.load_kind(kind)
        self.kwargs = kwargs

    # -- registry / serializer protocol ----------------------------------
    @property
    def registry_type(self) -> str:
        return self.__class__.__name__

    def load_kind(self, kind):
        if callable(kind):
            register_model_builder(type=self.registry_type)(kind)
            return kind.__name__
        if kind not in register_model_builder.factories.get(self.registry_type, {}):
            raise ValueError(
                f"kind: {kind} is not an available model for type: "
                f"{self.registry_type}!"
            )
        return kind

    @classmethod
    def from_definition(cls, definition: dict):
        definition = copy.copy(definition)
        kind = definition.pop("kind")
        return cls(kind, **definition)

    def into_definition(self) -> dict:
        definition = copy.copy(self.kwargs)
        if definition.get("callbacks"):
            from gordo_tpu.serializer.into_definition import _decompose_node

            decomposed = []
            for cb in definition["callbacks"]:
                if isinstance(cb, (str, dict)):
                    decomposed.append(cb)
                elif hasattr(type(cb), "get_params"):
                    decomposed.append(_decompose_node(cb))
                else:
                    # foreign callback objects (e.g. real keras ones) are
                    # ignored at fit time; drop them from the expanded
                    # definition so it stays truthful and serializable
                    logger.warning(
                        "Dropping unsupported callback %s from expanded "
                        "model definition",
                        type(cb).__name__,
                    )
            definition["callbacks"] = decomposed
        definition["kind"] = self.kind
        return {f"{type(self).__module__}.{type(self).__name__}": definition}

    @classmethod
    def extract_supported_fit_args(cls, kwargs):
        return {k: kwargs[k] for k in cls.supported_fit_args if k in kwargs}

    def get_params(self, deep=False):
        params = {"kind": self.kind}
        params.update(self.kwargs)
        return params

    def set_params(self, **params):
        if "kind" in params:
            self.kind = self.load_kind(params.pop("kind"))
        self.kwargs.update(params)
        return self

    # -- spec / factory ---------------------------------------------------
    def _build_spec(self) -> ModelSpec:
        build_fn = register_model_builder.factories[self.registry_type][self.kind]
        factory_kwargs = {
            k: v for k, v in self.kwargs.items() if k not in self.supported_fit_args
        }
        spec = build_fn(**factory_kwargs)
        if not isinstance(spec, ModelSpec):
            raise TypeError(
                f"Factory {self.kind!r} returned {type(spec)}, expected ModelSpec"
            )
        return spec

    # -- fit --------------------------------------------------------------
    def fit(self, X: np.ndarray, y: np.ndarray, **kwargs):
        X = X.values if hasattr(X, "values") else np.asarray(X)
        y = y.values if hasattr(y, "values") else np.asarray(y)
        if X.ndim == 1:
            X = X.reshape(-1, 1)
        if y.ndim == 1:
            y = y.reshape(-1, 1)

        self.kwargs.update({"n_features": X.shape[-1], "n_features_out": y.shape[-1]})

        fit_args = dict(self.extract_supported_fit_args(self.kwargs))
        fit_args.update(kwargs)
        epochs = int(fit_args.get("epochs", 1))
        batch_size = int(fit_args.get("batch_size", 32))
        shuffle = bool(fit_args.get("shuffle", not self._windowed))
        seed = int(self.kwargs.get("seed", DEFAULT_SEED))
        validation_split = float(fit_args.get("validation_split") or 0.0)
        if not 0.0 <= validation_split < 1.0:
            raise ValueError(
                f"validation_split must be in [0, 1), got {validation_split}"
            )
        callbacks = _materialize_callbacks(fit_args.get("callbacks"))

        spec = self._build_spec()
        self.spec_ = spec

        lb = spec.lookback_window if spec.windowed else 1
        la = self.lookahead if spec.windowed else 0
        n = len(X)
        n_samples = n - lb + 1 - la if spec.windowed else n
        if n_samples <= 0:
            raise ValueError(
                f"Not enough samples ({n}) for lookback_window={lb}, lookahead={la}"
            )

        Xd = jnp.asarray(X, dtype=jnp.float32)
        yd = jnp.asarray(y, dtype=jnp.float32)

        # init through the shared derivation so the fleet path can't drift
        key = jax.random.split(jax.random.PRNGKey(seed))[0]
        init_key = solo_init_key(seed)
        if spec.windowed:
            example = Xd[:1][:, None, :].repeat(lb, axis=1)  # (1, lb, f)
        else:
            example = Xd[:1]
        params = spec.module.init(init_key, example)

        optimizer = spec.make_optimizer()
        opt_state = optimizer.init(params)

        # Keras validation_split semantics: the LAST fraction of samples
        # (windows, for sequence models) is held out, before any shuffling
        n_val = int(n_samples * validation_split)
        n_train = n_samples - n_val
        if n_train <= 0:
            raise ValueError(
                f"validation_split={validation_split} leaves no training "
                f"samples (of {n_samples})"
            )

        n_batches = max(1, math.ceil(n_train / batch_size))
        n_pad = n_batches * batch_size
        sample_ids = np.zeros(n_pad, dtype=np.int32)
        sample_ids[:n_train] = np.arange(n_train, dtype=np.int32)
        weights = np.zeros(n_pad, dtype=np.float32)
        weights[:n_train] = 1.0
        ids_d = jnp.asarray(sample_ids)
        w_d = jnp.asarray(weights)

        windowed = spec.windowed
        loss_name = spec.loss
        module = spec.module

        def gather_batch(Xfull, yfull, sel):
            if windowed:
                rows = sel[:, None] + jnp.arange(lb, dtype=jnp.int32)[None, :]
                xb = Xfull[rows]  # (batch, lb, f)
            else:
                xb = Xfull[sel]
            yb = yfull[sel + (lb - 1 + la)] if windowed else yfull[sel]
            return xb, yb

        def loss_fn(p, xb, yb, wb, dropout_key):
            out, penalty = module.apply(
                p, xb, deterministic=False, rngs={"dropout": dropout_key}
            )
            per = per_sample_loss(loss_name, out, yb)
            total_w = jnp.maximum(jnp.sum(wb), 1.0)
            return jnp.sum(per * wb) / total_w + penalty, jnp.sum(per * wb)

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        # NB: gather from function args, not closures, so jit doesn't embed
        # the dataset as a compile-time constant.
        def train_epoch(p, o, epoch_key, Xfull, yfull, ids, w):
            if shuffle:
                perm = jax.random.permutation(epoch_key, n_pad)
                sel_all = ids[perm].reshape(n_batches, batch_size)
                w_all = w[perm].reshape(n_batches, batch_size)
            else:
                sel_all = ids.reshape(n_batches, batch_size)
                w_all = w.reshape(n_batches, batch_size)

            def step(carry, batch):
                pp, oo = carry
                sel, wb, step_idx = batch
                xb, yb = gather_batch(Xfull, yfull, sel)
                dropout_key = jax.random.fold_in(epoch_key, step_idx)
                (_, loss_sum), grads = grad_fn(pp, xb, yb, wb, dropout_key)
                updates, oo = optimizer.update(grads, oo, pp)
                pp = optax.apply_updates(pp, updates)
                return (pp, oo), loss_sum

            step_ids = jnp.arange(n_batches, dtype=jnp.int32)
            (p, o), loss_sums = jax.lax.scan(step, (p, o), (sel_all, w_all, step_ids))
            epoch_loss = jnp.sum(loss_sums) / n_train
            return p, o, epoch_loss

        train_epoch_jit = jax.jit(train_epoch, donate_argnums=(0, 1))

        if n_val:
            # chunked like training, so the validation gather never
            # materializes more than (batch_size, lb, f) at once
            n_val_batches = math.ceil(n_val / batch_size)
            n_val_pad = n_val_batches * batch_size
            val_ids = np.full(n_val_pad, n_train, dtype=np.int32)
            val_ids[:n_val] = np.arange(n_train, n_samples, dtype=np.int32)
            val_w = np.zeros(n_val_pad, dtype=np.float32)
            val_w[:n_val] = 1.0
            val_sel_d = jnp.asarray(val_ids.reshape(n_val_batches, batch_size))
            val_w_d = jnp.asarray(val_w.reshape(n_val_batches, batch_size))

            def val_loss_fn(p, Xfull, yfull):
                def one_chunk(args):
                    sel, wb = args
                    xb, yb = gather_batch(Xfull, yfull, sel)
                    out, _ = module.apply(p, xb)
                    return jnp.sum(per_sample_loss(loss_name, out, yb) * wb)

                sums = jax.lax.map(one_chunk, (val_sel_d, val_w_d))
                return jnp.sum(sums) / n_val

            val_loss_jit = jax.jit(val_loss_fn)

        for cb in callbacks:
            cb.on_train_begin()

        losses: list = []
        val_losses: list = []
        for epoch in range(epochs):
            key, epoch_key = jax.random.split(key)
            params, opt_state, epoch_loss = train_epoch_jit(
                params, opt_state, epoch_key, Xd, yd, ids_d, w_d
            )
            # the solo path syncs per epoch BY CONTRACT: the Keras-style
            # callback protocol below consumes host floats every epoch
            # (early stopping, checkpoints). The fleet path keeps its
            # losses on the device and fetches them once after the loop.
            losses.append(float(epoch_loss))  # lint: disable=host-sync
            logs = {"loss": losses[-1]}
            if n_val:
                val_losses.append(float(val_loss_jit(params, Xd, yd)))  # lint: disable=host-sync
                logs["val_loss"] = val_losses[-1]
            # every callback sees every epoch (no short-circuit): a stop
            # vote from one must not hide this epoch's metrics from others
            if callbacks and any(
                [cb.update(epoch, logs, params) for cb in callbacks]
            ):
                break
        for cb in callbacks:
            params = cb.finalize(params)
            # drop any param snapshot so pickled estimators stay small
            if getattr(cb, "best_params", None) is not None:
                cb.best_params = None

        self.params_ = params
        self.history_ = {
            "loss": losses,
            "params": {
                "epochs": epochs,
                "steps": n_batches,
                "batch_size": batch_size,
                # training samples after the validation holdout, so
                # samples/steps/batch_size stay mutually consistent
                "samples": n_train,
                "metrics": ["loss"] + (["val_loss"] if n_val else []),
            },
        }
        if n_val:
            self.history_["val_loss"] = val_losses
        self.n_features_ = X.shape[-1]
        self.n_features_out_ = y.shape[-1]
        self._apply_fn = None  # rebuilt lazily
        self._device_params_stacked = None  # ditto (refit must not serve stale params)
        return self

    # -- predict ----------------------------------------------------------
    def _ensure_apply_fn(self):
        if not hasattr(self, "params_"):
            raise NotFittedError(
                f"This {self.__class__.__name__} has not been fitted yet."
            )
        if getattr(self, "_apply_fn", None) is None:
            # the jitted apply is cached ON the spec: every estimator
            # sharing a spec (a whole fleet bucket) reuses one compiled
            # program instead of tracing+compiling per estimator.
            # Precision keys the cache attribute — a calibration-fallback
            # float32 machine must not reuse its bucket-mates' bf16
            # program (docs/performance.md "Mixed precision")
            spec = self.spec_
            precision = getattr(self, "precision_", "float32")
            attr = (
                "_shared_apply_fn"
                if precision == "float32"
                else f"_shared_apply_fn_{precision}"
            )
            shared = getattr(spec, attr, None)
            if shared is None:
                module = spec.module
                if precision == "bf16":
                    # the same cast walk the fleet scorer compiles:
                    # bf16 params + in-program input cast, output
                    # upcast — responses keep their float32 dtype
                    shared = jax.jit(
                        lambda p, x: module.apply(p, x.astype(jnp.bfloat16))[
                            0
                        ].astype(jnp.float32)
                    )
                else:
                    shared = jax.jit(lambda p, x: module.apply(p, x)[0])
                setattr(spec, attr, shared)
            self._apply_fn = shared
            params = self.params_
            if precision == "bf16":
                from gordo_tpu.parallel.precision import cast_params

                params = cast_params(params, jnp.bfloat16)
            self._device_params = jax.device_put(params)
        return self._apply_fn

    def _pad_active_input(self, X: np.ndarray) -> np.ndarray:
        """
        Widen a real-width input up to the model's program width with
        zero pad COLUMNS — the serving half of the padded bucket policy
        (docs/parallelism.md "Bucketing compiler"): an artifact built
        into a padded program records its real width as
        ``n_active_features_`` and its module expects ``n_features_``
        columns. Exact-bucket artifacts (no active attrs) pass through
        untouched.
        """
        n_active = getattr(self, "n_active_features_", None)
        f_prog = getattr(self, "n_features_", None)
        if (
            n_active is None
            or f_prog is None
            or X.shape[-1] != n_active
            or n_active >= f_prog
        ):
            return X
        pad = [(0, 0)] * (X.ndim - 1) + [(0, f_prog - n_active)]
        return np.pad(np.asarray(X), pad)

    def _strip_pad_output(self, out: np.ndarray) -> np.ndarray:
        """Drop inert pad columns from a padded program's output, so
        responses carry exactly the machine's real target width."""
        n_active_out = getattr(self, "n_active_features_out_", None)
        if n_active_out is None or out.shape[-1] <= n_active_out:
            return out
        return out[..., :n_active_out]

    def _forward(self, X: np.ndarray, batch_size: int = 10000) -> np.ndarray:
        """
        Apply the model to prepared model-inputs (already windowed if
        needed). Each chunk is zero-padded up to a power-of-4 bucket
        (1, 4, 16, ..., batch_size) so ``jax.jit`` sees a bounded set of
        shapes — arbitrary request lengths would otherwise each pay an XLA
        compile; padding rows are sliced off the output.
        """
        apply_fn = self._ensure_apply_fn()
        params = getattr(self, "_device_params", self.params_)
        if len(X) == 0:
            n_out = getattr(self, "n_active_features_out_", None) or getattr(
                self, "n_features_out_", 0
            )
            return np.empty((0, n_out), dtype=np.float32)
        X = self._pad_active_input(X)
        outs = []
        for start in range(0, len(X), batch_size):
            xb_host = np.asarray(X[start : start + batch_size], dtype=np.float32)
            n = len(xb_host)
            bucket = _batch_bucket(n, batch_size)
            if bucket > n:
                pad_width = ((0, bucket - n),) + ((0, 0),) * (xb_host.ndim - 1)
                xb_host = np.pad(xb_host, pad_width)
            # phase ledger: host->device staging is "transfer"; the
            # apply + device->host output sync is "device" (np.asarray
            # blocks until the computation delivers)
            t0 = time.perf_counter()
            xb_dev = jnp.asarray(xb_host)
            t1 = time.perf_counter()
            attribution.record_current("transfer", t1 - t0)
            out = apply_fn(params, xb_dev)
            outs.append(self._strip_pad_output(np.asarray(out[:n])))
            attribution.record_current("device", time.perf_counter() - t1)
        return np.concatenate(outs, axis=0) if len(outs) > 1 else outs[0]

    def predict(self, X: np.ndarray, **kwargs) -> np.ndarray:
        X = X.values if hasattr(X, "values") else np.asarray(X)
        if X.ndim == 1:
            X = X.reshape(-1, 1)
        return self._forward(X)

    def score(
        self,
        X: Union[np.ndarray, pd.DataFrame],
        y: Union[np.ndarray, pd.DataFrame],
        sample_weight: Optional[np.ndarray] = None,
    ) -> float:
        out = self.predict(X)
        yv = y.values if hasattr(y, "values") else np.asarray(y)
        return explained_variance_score(yv[-len(out):], out)

    # -- metadata / persistence ------------------------------------------
    def get_metadata(self):
        if hasattr(self, "history_"):
            history = dict(self.history_)
            return {"history": history}
        return {}

    def __getstate__(self):
        state = self.__dict__.copy()
        for attr in _EPHEMERAL_ATTRS:
            state.pop(attr, None)
        spec = state.get("spec_")
        ephemeral_spec_attrs = (
            "_shared_apply_fn",
            "_shared_apply_fn_bf16",
            "_serving_trainers",
        )
        if spec is not None and any(
            hasattr(spec, attr) for attr in ephemeral_spec_attrs
        ):
            # jitted functions / compiled-program caches don't pickle;
            # shallow-copy so the live (possibly fleet-shared) spec keeps
            # its cached programs
            spec = copy.copy(spec)
            for attr in ephemeral_spec_attrs:
                if hasattr(spec, attr):
                    delattr(spec, attr)
            state["spec_"] = spec
        if "params_" in state:
            state["params_"] = jax.device_get(state["params_"])
        return state

    def __setstate__(self, state):
        self.__dict__ = state
        return self

    def __repr__(self):
        return f"{self.__class__.__name__}(kind={self.kind!r})"
