"""
Hyperparameter sweeps as ONE compiled fleet program.

The reference runs hyperparameter search by launching one Kubernetes pod
per trial and printing CV scores for Katib to parse (gordo/cli/cli.py
katib output, --model-parameter jinja expansion). Here a sweep over
*optimizer* hyperparameters (learning rate, weight decay, ...) is just a
fleet whose machines share architecture and data but differ in optimizer
state: ``optax.inject_hyperparams`` moves the hyperparameters into the
optimizer state pytree, the fleet ``vmap`` stacks that state on the
machine axis, and every trial trains simultaneously on the TPU — one
compile, one program, N trials.

Model-architecture hyperparameters (layer dims, window sizes) change
tensor shapes and therefore stay one-compile-per-value — use the CLI's
--model-parameter expansion for those, exactly like the reference.
"""

import inspect
import logging
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from gordo_tpu.models.specs import ModelSpec, resolve_optimizer
from gordo_tpu.parallel.fleet import FleetTrainer, StackedData

logger = logging.getLogger(__name__)


class HyperparamSweep:
    """
    Train N optimizer-hyperparameter variants of one model in one program.

    Parameters
    ----------
    spec
        The architecture (a factory's ModelSpec). Its ``optimizer`` /
        ``optimizer_kwargs`` provide the base configuration.
    grid
        ``{hyperparam_name: [value per variant, ...]}``; all lists must
        share one length (the number of variants). Names must be accepted
        by the underlying optax constructor (e.g. ``learning_rate``,
        ``b1``, ``weight_decay`` for adamw).
    lookahead, mesh
        Passed through to FleetTrainer — a sweep shards over the mesh's
        fleet axis like any other fleet.
    """

    def __init__(
        self,
        spec: ModelSpec,
        grid: Dict[str, Sequence[float]],
        lookahead: int = 0,
        mesh: Optional[Any] = None,
    ):
        if not grid:
            raise ValueError("grid must name at least one hyperparameter")
        lengths = {len(v) for v in grid.values()}
        if len(lengths) != 1:
            raise ValueError(
                f"All grid value lists must share one length, got {lengths}"
            )
        (self.n_variants,) = lengths
        if self.n_variants == 0:
            raise ValueError("grid value lists are empty")
        from gordo_tpu.models.specs import _OPT_KWARG_ALIASES

        # accept the reference dialect's spellings ("lr", "decay") the same
        # way optimizer_kwargs does
        self.grid = {
            _OPT_KWARG_ALIASES.get(k, k): [float(x) for x in v]
            for k, v in grid.items()
        }
        self.spec = spec
        # even shardings need the variant axis padded to the mesh size;
        # padding variants reuse the last grid values and are dropped from
        # results (SweepResult slices to n_variants)
        self.n_padded = FleetTrainer.pad_fleet_size(self.n_variants, mesh)

        # same alias translation + defaults as spec.make_optimizer()
        ctor, kwargs = resolve_optimizer(spec.optimizer, spec.optimizer_kwargs)
        # hyperparams being swept must reach inject_hyperparams as floats
        # (they become state); non-swept kwargs pass through unchanged
        for name in self.grid:
            if name in inspect.signature(ctor).parameters:
                kwargs.setdefault(name, self.grid[name][0])
        optimizer = optax.inject_hyperparams(ctor)(**kwargs)
        # validate against what inject_hyperparams actually made sweepable
        # (numeric ctor args become state; masks/dtypes/flags do not)
        probe = optimizer.init({"w": jnp.zeros((1,))})
        sweepable = set(probe.hyperparams)
        unknown = set(self.grid) - sweepable
        if unknown:
            raise ValueError(
                f"Optimizer {spec.optimizer!r} has no sweepable "
                f"hyperparameter(s) {sorted(unknown)}; "
                f"sweepable: {sorted(sweepable)}"
            )
        self.trainer = FleetTrainer(
            spec,
            lookahead=lookahead,
            mesh=mesh,
            optimizer=optimizer,
            broadcast_data=True,
        )

    def _inject(self, opt_state: Any) -> Any:
        """
        Overwrite the stacked state's hyperparams with the (padded) grid.
        Grid names were validated against the state in ``__init__``.
        """
        hyperparams = dict(opt_state.hyperparams)
        for name, values in self.grid.items():
            padded = list(values) + [values[-1]] * (self.n_padded - len(values))
            hyperparams[name] = jnp.asarray(padded, dtype=jnp.float32)
        return opt_state._replace(hyperparams=hyperparams)

    def fit(
        self,
        X: np.ndarray,
        y: Optional[np.ndarray] = None,
        epochs: int = 10,
        batch_size: int = 128,
        seed: int = 0,
    ) -> "SweepResult":
        """
        Train every variant on the same (X, y). Returns a SweepResult with
        per-variant losses and stacked params, best-first ranking included.
        """
        y = y if y is not None else X.copy()
        # ONE device copy of the data, shared by every variant
        data = StackedData.from_ragged([np.asarray(X)], [np.asarray(y)])
        # Every variant trains from the key a STANDALONE single-machine
        # fit with this seed would use — one shared init/shuffle/dropout
        # stream, so variants differ ONLY in their hyperparameters and a
        # sweep trial is exactly "a plain fit at those hyperparameters".
        # Deriving per-variant keys with split(seed_key, n_variants) broke
        # that parity (~12% loss drift): threefry's split lays keys out by
        # the TOTAL count, so variant 0's key — and with it the init and
        # the shared data's shuffle order — changed with the sweep WIDTH.
        solo_key = np.asarray(self.trainer.machine_keys(1, seed=seed))[0]
        keys = np.broadcast_to(
            solo_key, (self.n_padded,) + solo_key.shape
        ).copy()
        params = self.trainer.init_params(keys, data.X.shape[-1])
        opt_state = self._inject(self.trainer.init_opt_state(params))
        params, losses = self.trainer.fit(
            data,
            keys,
            epochs=epochs,
            batch_size=batch_size,
            params=params,
            opt_state=opt_state,
        )
        return SweepResult(
            grid=self.grid, params=params, losses=losses[:, : self.n_variants]
        )


class SweepResult:
    """Per-variant training outcome of a HyperparamSweep."""

    def __init__(self, grid: Dict[str, List[float]], params: Any, losses: np.ndarray):
        self.grid = grid
        self.params = params
        self.losses = losses  # (epochs, n_variants)

    @property
    def final_losses(self) -> np.ndarray:
        return self.losses[-1]

    @property
    def best_index(self) -> int:
        return int(np.argmin(self.final_losses))

    @property
    def best_hyperparams(self) -> Dict[str, float]:
        return {k: v[self.best_index] for k, v in self.grid.items()}

    def best_params(self) -> Any:
        """The winning variant's (unstacked) parameter pytree."""
        return FleetTrainer.unstack_params(self.params, self.best_index)

    def ranking(self) -> List[Tuple[Dict[str, float], float]]:
        """(hyperparams, final loss) pairs, best first."""
        order = np.argsort(self.final_losses)
        return [
            ({k: v[i] for k, v in self.grid.items()}, float(self.final_losses[i]))
            for i in order
        ]
