"""
Warm-start refit + shadow scoring (docs/lifecycle.md).

The refit itself is just a :class:`FleetModelBuilder` run over the
drifted subset with ``initial_params`` = the served revision's stacked
params (``FleetTrainer.fit(params=...)``) and ``fault_sites=("train",
"refit")`` so the chaos harness can poison refit builds specifically.
This module holds the pieces around it: extracting warm params from
served artifacts, and the shadow-scoring gate that decides promotion.
"""

import dataclasses
import logging
import os
import typing

import numpy as np

from gordo_tpu import serializer
from gordo_tpu.builder.fleet_build import _find_jax_estimator

logger = logging.getLogger(__name__)

#: refit candidates may not regress the live model's holdout error by
#: more than this fraction by default (docs/lifecycle.md)
DEFAULT_SHADOW_TOLERANCE = 0.10


def warm_params_from_models(
    models: typing.Mapping[str, typing.Any],
) -> typing.Dict[str, typing.Any]:
    """
    ``machine name -> host param pytree`` extracted from already-loaded
    models — the ``initial_params`` a refit build warm starts from
    (the lifecycle tick holds the drifted machines' live models from
    the drift scan; re-deserializing them would be pure waste).
    Machines holding no fitted JAX estimator are skipped (logged): they
    refit cold rather than not at all.
    """
    out: typing.Dict[str, typing.Any] = {}
    for name, model in models.items():
        est = _find_jax_estimator(model)
        params = getattr(est, "params_", None) if est is not None else None
        if params is None:
            logger.warning(
                "Warm start: artifact for %s holds no fitted JAX "
                "estimator; it will refit cold",
                name,
            )
            continue
        out[name] = params
    return out


def warm_params_from_artifacts(
    collection_dir: typing.Union[str, os.PathLike],
    names: typing.Iterable[str],
) -> typing.Dict[str, typing.Any]:
    """
    :func:`warm_params_from_models` over the named artifacts under
    ``collection_dir``, loading each first. Machines whose artifact
    doesn't load are skipped (logged), like param-less ones.
    """
    models: typing.Dict[str, typing.Any] = {}
    for name in names:
        try:
            models[name] = serializer.load(
                os.path.join(str(collection_dir), name)
            )
        except Exception as exc:  # noqa: BLE001 - per-machine tolerance
            logger.warning(
                "Warm start: artifact for %s does not load (%s)", name, exc
            )
    return warm_params_from_models(models)


def shadow_score(model: typing.Any, X, y) -> float:
    """
    One model's holdout error: mean absolute error between its output
    on ``X`` and ``y``, aligned by the model's output offset (a
    windowed model's prediction is ``lookback - 1 + lookahead`` rows
    shorter than its input — the same arithmetic as
    ``ModelBuilder._determine_offset``). Candidate and live revision
    are scored by this one function on the SAME frames, so the gate
    compares like with like.
    """
    out = np.asarray(
        model.predict(X) if hasattr(model, "predict") else model.transform(X)
    )
    y_arr = np.asarray(y, dtype=np.float64)
    offset = len(y_arr) - len(out)
    if offset < 0:
        raise ValueError(
            f"Model output ({len(out)} rows) is longer than the holdout "
            f"targets ({len(y_arr)} rows)"
        )
    if offset:
        y_arr = y_arr[offset:]
    return float(np.mean(np.abs(np.asarray(out, dtype=np.float64) - y_arr)))


def shadow_gate(
    live_score: float,
    candidate_score: float,
    tolerance: float = DEFAULT_SHADOW_TOLERANCE,
) -> bool:
    """
    True when the candidate may replace the live model: its holdout
    error is within ``(1 + tolerance)`` of the live revision's (a
    refit's job is adapting to drifted data, not beating the old model
    on every window — but a DEGRADED candidate must never ship). A
    non-finite candidate score always fails; a non-finite live score
    always passes (the incumbent is already broken on this window, so
    any finite candidate is an improvement).
    """
    if not np.isfinite(candidate_score):
        return False
    if not np.isfinite(live_score):
        return True
    return candidate_score <= live_score * (1.0 + float(tolerance))


@dataclasses.dataclass
class ShadowVerdict:
    """One candidate's shadow-scoring outcome (promotion_report.json)."""

    machine: str
    live_score: float
    candidate_score: float
    tolerance: float
    promote: bool

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def degrade_params(params: typing.Any, scale: float) -> typing.Any:
    """
    The ``refit:degrade`` chaos seam's payload: every leaf of the
    candidate's param tree multiplied by ``scale`` — a deterministic,
    unmistakably-worse candidate the shadow gate must reject
    (robustness/faults.py).
    """
    import jax

    return jax.tree_util.tree_map(
        lambda leaf: np.asarray(leaf) * float(scale), params
    )
