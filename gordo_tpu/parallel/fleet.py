"""
FleetTrainer: train a whole bucket of same-architecture Machines as ONE
compiled XLA program.

This is the framework's performance core — the TPU-native replacement for
the reference's one-pod-per-model Argo fan-out (SURVEY.md §2.10, §7 stage 6):

- Machines' parameters are stacked on a leading ``fleet`` axis via
  ``vmap``-ed init; training vmaps a single-machine epoch over that axis.
- All stacked tensors (params, opt state, data, PRNG keys) are sharded over
  a ``jax.sharding.Mesh`` fleet axis with ``NamedSharding`` — XLA places
  each machine's slice on a device; no collectives are needed between
  machines (they are independent), so the program scales linearly over ICI.
- Ragged fleets (different data lengths) are handled by padding to a common
  grid and per-sample weight masks; ragged *epochs* by loss masking; CV
  folds are just more masks (train-range masks), so the threshold
  calibration runs as extra fleet fits, not per-machine loops.
- The fleet size is padded to a multiple of the mesh size with zero-weight
  dummy machines so shardings stay even.

Within one machine the epoch runs exactly like the single-model path
(gordo_tpu.models.core): in-jit shuffle, ``lax.scan`` over fixed-size
minibatches, windowed gathers for sequence models.
"""

import collections
import contextlib
import dataclasses
import functools
import logging
import math
import time
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from gordo_tpu.models.specs import (
    ModelSpec,
    masked_per_sample_loss,
    per_sample_loss,
)
from gordo_tpu.observability import (
    emit_event,
    get_registry,
    tracing,
)
from gordo_tpu.parallel import transfer
from gordo_tpu.parallel.mesh import fleet_sharding, pad_to_multiple, replicated_sharding
from gordo_tpu.programs import ProgramCache
from gordo_tpu.robustness import faults as _faults

logger = logging.getLogger(__name__)


@jax.jit
def _keep_better(mask, new_tree, old_tree):
    """
    Per-machine select over the stacked params' leading axis.

    Module-level and jitted ONCE: it used to be redefined inside every
    ``fit`` call, so each fit re-traced it; the jit cache is keyed on
    tree structure/shapes, so all fits sharing a geometry now reuse one
    compiled select (``restore_best_weights``' per-machine snapshot).
    """

    def select(new_leaf, old_leaf):
        shape = (mask.shape[0],) + (1,) * (new_leaf.ndim - 1)
        return jnp.where(mask.reshape(shape), new_leaf, old_leaf)

    return jax.tree_util.tree_map(select, new_tree, old_tree)


def _sliding_window_min(w: jnp.ndarray, window: int) -> jnp.ndarray:
    """
    The minimum over every length-``window`` run of the 1-D ``w`` — bit
    for bit what ``lax.reduce_window(w, inf, min, (window,), (1,),
    "valid")`` returns (min is exact and idempotent), built from
    O(log window) shifted minimums. The TPU compiler spends ~125 s on that
    reduce_window at 16,384 timesteps x window 64 (the flagship epoch
    program compiled in 142 s, 17 s without it); this form costs it
    nothing measurable.
    """
    span = 1  # invariant: m[i] == min(w[i : i + span])
    m = w
    while span * 2 <= window:
        m = jnp.minimum(m[: m.shape[0] - span], m[span:])
        span *= 2
    if span < window:
        # two overlapping spans cover the window: [i, i+span) and
        # [i+window-span, i+window)
        shift = window - span
        m = jnp.minimum(m[: m.shape[0] - shift], m[shift:])
    return m


def _put_fleet_arr(x, mesh: Optional[Mesh]):
    """Small per-machine (M,)-shaped array onto the fleet sharding (or
    the default device when unmeshed) — the flag/state arrays the gated
    programs take (``active``/``healthy``/injection masks)."""
    arr = jnp.asarray(x)
    if mesh is not None:
        arr = jax.device_put(arr, fleet_sharding(mesh))
    return arr


def host_fetch(x):
    """
    device -> host for arrays that may span multiple PROCESSES (multi-host
    meshes from parallel.distributed): ``jax.device_get`` refuses global
    arrays with non-addressable shards, so those go through
    ``process_allgather`` (every host receives the full global value —
    exactly what the fleet's loss/param fetches need, since every process
    runs the same control flow on them).
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return multihost_utils.process_allgather(x, tiled=True)
    return jax.device_get(x)


@functools.partial(jax.jit, static_argnames=("lookback", "lookahead"))
def _weight_facts(w, lookback: Optional[int] = None, lookahead: int = 0):
    """
    What a fit must know of its effective ``(M, n)`` sample weights, as two
    ``(M,)`` int32 vectors computed where the weights are: the REAL rows a
    machine (``w > 0``) and its VALID samples — the same count without a
    ``lookback``; with one, a sample counts iff its whole window and its
    target row at ``lookahead`` are real. Exact for any weight pattern.
    """
    real = w > 0
    rows = real.sum(axis=1, dtype=jnp.int32)
    if lookback is None:
        return rows, rows
    n_samples = w.shape[1] - lookback + 1 - lookahead
    c = jnp.pad(jnp.cumsum(real, axis=1, dtype=jnp.int32), ((0, 0), (1, 0)))
    window_real = (c[:, lookback:] - c[:, :-lookback]) == lookback
    target = lookback - 1 + lookahead
    valid = window_real[:, :n_samples] & real[:, target : target + n_samples]
    return rows, valid.sum(axis=1, dtype=jnp.int32)


def _read_by_products_alone(fn, x, *rest) -> bool:
    """
    Whether ``fn(x, *rest)`` reads the array ``x`` only as an operand of
    matrix products at the default precision (``x`` and ``rest`` may be
    abstract). On a TPU such a product computes in bfloat16
    (``jax.lax.Precision.DEFAULT``), so ``x`` rounded to bfloat16 gives ``fn``
    the same bits: what XLA's own bfloat16 propagation stores for such an
    operand where it makes it.
    """
    if jax.config.jax_default_matmul_precision is not None:
        return False
    closed = jax.make_jaxpr(fn)(x, *rest)
    return _products_alone_read(closed.jaxpr, closed.jaxpr.invars[0])


def _products_alone_read(jaxpr, var) -> bool:
    if any(out is var for out in jaxpr.outvars):
        return False
    default = jax.lax.Precision.DEFAULT
    for eqn in jaxpr.eqns:
        for k, arg in enumerate(eqn.invars):
            if arg is not var:
                continue
            if eqn.primitive is jax.lax.dot_general_p:
                if eqn.params["precision"] not in (None, (default, default)):
                    return False
                continue
            # a call (jit, a custom rule's body): what its body does with it
            inner = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            inner = getattr(inner, "jaxpr", inner)
            if inner is None or not _products_alone_read(inner, inner.invars[k]):
                return False
    return True


@jax.jit
def _split_masks(w, train_cut, n_train):
    """``validation_split``'s two ``(M, n)`` float32 masks from the ``(M,)``
    cuts: the bare train-cut indicator, and the holdout tail carrying the
    effective weights ``w`` (it is used standalone as the eval weight)."""
    t = jnp.arange(w.shape[1], dtype=jnp.int32)[None, :]
    train_mask = (t < train_cut[:, None]).astype(jnp.float32)
    val_mask = (t >= n_train[:, None]).astype(jnp.float32) * w
    return train_mask, val_mask


class _FitPhases:
    """
    What one fit measures at its own host-side boundaries, tracing on or
    off: ``time.perf_counter`` pairs summed per phase (``prepare_s``,
    ``decide_s``, ``checkpoint_s``, ``collect_s``, ``report_s``; each
    stands beside the ``train.*`` span of the same phase) and the count
    and bytes of its device->host fetches, booked where each happens.
    """

    def __init__(self):
        self.seconds = collections.defaultdict(float)
        self.n_host_syncs = 0
        self.host_fetch_bytes = 0

    @contextlib.contextmanager
    def timed(self, key: str):
        start = time.perf_counter()
        try:
            yield
        finally:
            self.seconds[key] += time.perf_counter() - start

    def fetched(self, tree):
        """Book one ``host_fetch`` by what it brought; returns ``tree``."""
        self.n_host_syncs += 1
        self.host_fetch_bytes += sum(
            np.asarray(leaf).nbytes for leaf in jax.tree.leaves(tree)
        )
        return tree


def _under_fit_span(fit):
    """Run ``FleetTrainer.fit`` under its root span, ``train.fit``."""

    @functools.wraps(fit)
    def traced_fit(self, data, keys, epochs=1, batch_size=32, *args, **kwargs):
        with tracing.start_span(
            "train.fit", n_machines=len(keys), epochs=epochs,
            batch_size=batch_size,
        ):
            return fit(self, data, keys, epochs, batch_size, *args, **kwargs)

    return traced_fit


@dataclasses.dataclass
class StackedData:
    """
    A fleet bucket's training data, stacked and padded to a common grid.

    X: (M, n, f) float32; y: (M, n, f_out); sample_weight: (M, n) in {0,1}
    marking real (vs padding) rows per machine. ``feature_out_weight``
    ((M, f_out) in {0,1}) marks real (vs pad) OUTPUT columns per machine
    — set only by padded-policy buckets whose machines have ragged
    feature widths (docs/parallelism.md "Bucketing compiler"); None
    means every column is real and training takes the historical
    unmasked path bit-identically.
    """

    X: jnp.ndarray
    y: jnp.ndarray
    sample_weight: jnp.ndarray
    feature_out_weight: Optional[jnp.ndarray] = None

    @classmethod
    def from_ragged(
        cls,
        Xs: List[np.ndarray],
        ys: List[np.ndarray],
        n_machines_padded: Optional[int] = None,
        n_timesteps: Optional[int] = None,
        n_features: Optional[int] = None,
        n_features_out: Optional[int] = None,
        prefetch_depth: int = 0,
    ) -> "StackedData":
        """
        Stack per-machine (n_i, f_i) arrays, zero-padding rows up to the
        longest machine (or an explicit ``n_timesteps`` grid, so slightly
        ragged buckets share one compiled program geometry) and optionally
        padding the fleet axis with dummy machines (all-zero weights).

        ``n_features`` / ``n_features_out`` widen the feature axes to a
        padded program width (the padded bucket policy): narrower
        machines get zero pad COLUMNS — inert on input (zero activations,
        zero gradients) and masked out of the loss via the returned
        ``feature_out_weight`` on output. Defaults keep the historical
        contract: machine 0's widths, every column real, no mask.

        ``prefetch_depth`` > 0 pipelines the host->device transfer of
        the big stacked tensors as sliced ``device_put`` calls
        (parallel/transfer.py) so later slices stream while the first
        feeds the device; 0 (the default) is the historical single
        ``jnp.asarray`` — same bits either way, the slicing moves
        bytes, not math.
        """
        assert len(Xs) == len(ys) and len(Xs) > 0
        f = max(n_features or 0, max(x.shape[1] for x in Xs))
        f_out = max(n_features_out or 0, max(y_.shape[1] for y_ in ys))
        n_max = max(max(len(x) for x in Xs), n_timesteps or 0)
        m_total = n_machines_padded or len(Xs)
        X = np.zeros((m_total, n_max, f), dtype=np.float32)
        y = np.zeros((m_total, n_max, f_out), dtype=np.float32)
        w = np.zeros((m_total, n_max), dtype=np.float32)
        fw = np.zeros((m_total, f_out), dtype=np.float32)
        ragged_out = False
        for i, (xi, yi) in enumerate(zip(Xs, ys)):
            X[i, : len(xi), : xi.shape[1]] = xi
            y[i, : len(yi), : yi.shape[1]] = yi
            w[i, : len(xi)] = 1.0
            fw[i, : yi.shape[1]] = 1.0
            ragged_out = ragged_out or yi.shape[1] != f_out
        # pad machines on the fleet axis carry an all-real column mask:
        # their sample weights are already zero, and a zero fw row would
        # needlessly special-case the masked loss's normalizer
        fw[len(Xs):] = 1.0
        if prefetch_depth > 0:
            return cls(
                transfer.device_put_sliced(X, prefetch_depth, plane="build"),
                transfer.device_put_sliced(y, prefetch_depth, plane="build"),
                transfer.device_put_sliced(w, prefetch_depth, plane="build"),
                feature_out_weight=(
                    jnp.asarray(fw) if ragged_out else None
                ),
            )
        return cls(
            jnp.asarray(X),
            jnp.asarray(y),
            jnp.asarray(w),
            feature_out_weight=jnp.asarray(fw) if ragged_out else None,
        )

    @property
    def n_machines(self) -> int:
        return self.X.shape[0]

    @property
    def n_timesteps(self) -> int:
        return self.X.shape[1]


class FleetTrainer:
    """
    Train/predict a fleet of identical-architecture models in one program.

    Parameters
    ----------
    spec
        The shared architecture (a factory's ModelSpec).
    lookahead
        Target offset for windowed (sequence) models.
    mesh
        Device mesh; None trains unsharded on the default device.
    donate
        Donate param/opt buffers across epoch calls (halves HBM traffic).
    optimizer
        Optional optax optimizer overriding ``spec.make_optimizer()`` —
        e.g. an ``optax.inject_hyperparams``-wrapped one whose state
        carries per-machine hyperparameters (parallel.sweep).
    broadcast_data
        When True, all machines train on ONE shared (n, f) dataset
        (hyperparameter sweeps): ``fit`` takes a single-machine
        StackedData and the epoch vmaps with ``in_axes=None`` for the
        data, so device memory holds one copy instead of M.
    quarantine_nonfinite
        In-program non-finite guard (docs/robustness.md): a per-machine
        ``healthy`` flag rides the compiled program, and a machine whose
        epoch loss or updated params go non-finite is QUARANTINED — its
        params roll back to the last finite epoch's values via the same
        masked select early stopping uses, and it stops updating while
        the rest of the fleet trains on. The quarantine mask comes back
        through the existing history fetches (``self.healthy_`` /
        ``self.quarantine_epoch_``) at zero additional host syncs. For
        finite-loss machines the guard's selects are identity, so
        results are bit-identical to running without it.
    """

    def __init__(
        self,
        spec: ModelSpec,
        lookahead: int = 0,
        mesh: Optional[Mesh] = None,
        donate: bool = True,
        optimizer: Optional[Any] = None,
        broadcast_data: bool = False,
        quarantine_nonfinite: bool = True,
        fault_sites: Tuple[str, ...] = ("train",),
    ):
        self.spec = spec
        self.lookahead = int(lookahead) if spec.windowed else 0
        self.mesh = mesh
        self.donate = donate
        self.broadcast_data = broadcast_data
        self.quarantine_nonfinite = bool(quarantine_nonfinite)
        #: GORDO_FAULT_INJECT sites whose nan-mode specs poison this
        #: trainer's fits ("train" everywhere; lifecycle warm-start
        #: refits add "refit" so refit:nan targets refit builds only)
        self.fault_sites = tuple(fault_sites)
        self._optimizer = optimizer if optimizer is not None else spec.make_optimizer()
        #: the optimizer is the spec's own (what the fused step loop reads
        #: its arguments from), not one passed in
        self._spec_optimizer = optimizer is None
        #: _inputs_in_products' answers, by batch shape and precision
        self._inputs_rounded: dict = {}
        # ALL compiled program handles (epoch, val, predict, opt_init)
        # live in the one ProgramCache (docs/performance.md "AOT
        # executable cache") — LRU + HBM-aware bounded, hit/miss/evict
        # telemetry for free, and no per-site ad-hoc dicts
        self._programs = ProgramCache("trainer")

    # -- setup -----------------------------------------------------------
    def machine_keys(self, n_machines: int, seed: int = 0) -> jnp.ndarray:
        """(M,) stacked PRNG keys — one independent stream per machine."""
        return jax.random.split(jax.random.PRNGKey(seed), n_machines)

    def init_params(self, keys: jnp.ndarray, n_features: int) -> Any:
        """vmap-ed init -> param pytree with leading fleet axis."""
        lb = self.spec.lookback_window if self.spec.windowed else 1
        if self.spec.windowed:
            example = jnp.zeros((1, lb, n_features), dtype=jnp.float32)
        else:
            example = jnp.zeros((1, n_features), dtype=jnp.float32)
        init_one = lambda k: self.spec.module.init(k, example)
        params = jax.vmap(init_one)(keys)
        return self._shard(params)

    def init_opt_state(self, params: Any) -> Any:
        """Fresh stacked optimizer state, made in ONE dispatch: the vmapped
        ``init`` under ``jax.jit`` (one compiled program per param-tree
        shape) instead of an eager dispatch per leaf."""
        init = self._programs.get_or_build(
            ("opt_init",), lambda: jax.jit(jax.vmap(self._optimizer.init))
        )
        return self._shard(init(params))

    def _shard(self, tree: Any) -> Any:
        if self.mesh is None:
            return tree
        sharding = fleet_sharding(self.mesh)
        return jax.device_put(tree, sharding)

    def shard_data(self, data: StackedData) -> StackedData:
        if self.mesh is None:
            return data
        # broadcast mode: the one shared dataset is replicated, not split
        sharding = (
            replicated_sharding(self.mesh)
            if self.broadcast_data
            else fleet_sharding(self.mesh)
        )
        return StackedData(
            X=jax.device_put(data.X, sharding),
            y=jax.device_put(data.y, sharding),
            sample_weight=jax.device_put(data.sample_weight, sharding),
            feature_out_weight=(
                jax.device_put(data.feature_out_weight, fleet_sharding(self.mesh))
                if data.feature_out_weight is not None
                else None
            ),
        )

    def _n_samples(self, n: int) -> int:
        """Grid sample count for ``n`` timesteps (windows for sequence
        models), failing loudly when the grid cannot fit one window."""
        lb = self.spec.lookback_window if self.spec.windowed else 1
        la = self.lookahead
        n_samples = (n - lb + 1 - la) if self.spec.windowed else n
        if n_samples <= 0:
            raise ValueError(
                f"Not enough timesteps ({n}) for lookback={lb}, lookahead={la}"
            )
        return n_samples

    def _fit_facts(self, w):
        """
        ``(rows, valid)`` of the effective ``(M, n)`` weights ``w``, on the
        device (:func:`_weight_facts` under this trainer's window). ``fit``
        fetches the two ``(M,)`` vectors: ``rows`` is its timestep
        accounting, and the fleet-wide ``max(valid)`` is the scan-length cap
        that keeps each machine's optimizer-step count at the solo path's
        ``ceil(n_train / batch_size)`` instead of the padded grid's.
        """
        self._n_samples(w.shape[1])  # fails loudly when the grid fits no window
        lookback = self.spec.lookback_window if self.spec.windowed else None
        return _weight_facts(w, lookback=lookback, lookahead=self.lookahead)

    # -- the compiled epoch ---------------------------------------------
    def _choose_row_fetch(
        self, data: StackedData, batch_size: int, sample_cap: Optional[int]
    ) -> str:
        """
        How this fit's steps get their rows, from what the trainer can see
        (docs/observability.md has the table): ``"permute_epoch"`` — every
        machine's rows permuted into batch order once an epoch from on-chip
        memory (``ops/row_permute.py``) — where each row is read once an
        epoch (no windows), every machine has rows of its own on ONE chip,
        the chip is a TPU, the rows are float32 and the kernel's buffers fit
        its vector and scalar memory (``row_permute.serves``); ``"gather"``,
        the per-step row gathers, everywhere else.
        """
        if (
            self.spec.windowed
            or self.broadcast_data
            or self.mesh is not None
            or jax.default_backend() != "tpu"
        ):
            return "gather"
        # only a fit that may use the kernel pays for importing Pallas
        from gordo_tpu.ops import row_permute

        n_batches = self._n_batches(data.n_timesteps, batch_size, sample_cap)
        if row_permute.serves(data.X, data.y, n_batches * batch_size):
            return "permute_epoch"
        return "gather"

    def _choose_step_loop(
        self, data: StackedData, batch_size: int, sample_cap: Optional[int],
        row_fetch: str,
    ) -> str:
        """
        How this fit's epochs run their optimizer steps, from what the
        trainer can see (docs/observability.md has the table): ``"fused"``
        — every machine's steps in ONE kernel call an epoch, each machine
        block's parameters and Adam moments held in vector memory
        (``ops/fleet_dense.py``) — where the rows come by the permuting
        fetch on a TPU, the spec is a float32 ``FeedForwardNet`` with
        activations and a loss the kernel knows, its own optimizer is Adam
        with constant arguments, no matrix precision is set, a batch is
        whole 128-row tiles and a block of machines fits vector memory
        (``fleet_dense.plan`` and ``serves``); ``"scan"``, the per-step
        ``lax.scan``, everywhere else.
        """
        if (
            row_fetch != "permute_epoch"
            or not self._spec_optimizer
            or jax.default_backend() != "tpu"
        ):
            return "scan"
        from gordo_tpu.ops import fleet_dense

        stack = fleet_dense.plan(self.spec, data.X.shape[-1])
        if stack is None:
            return "scan"
        n_batches = self._n_batches(data.n_timesteps, batch_size, sample_cap)
        # the input slab as the permuting fetch stores it
        rounded = self._inputs_in_products(
            jax.ShapeDtypeStruct((batch_size,) + data.X.shape[2:], data.X.dtype)
        )
        x_itemsize = 2 if rounded else data.X.dtype.itemsize
        if not fleet_dense.serves(
            stack, data.n_machines, batch_size, n_batches, x_itemsize
        ):
            return "scan"
        return "fused"

    def _inputs_in_products(self, xb: jax.ShapeDtypeStruct) -> bool:
        """
        Whether the model's forward pass reads a batch ``xb`` of input rows
        only as operands of default-precision matrix products
        (:func:`_read_by_products_alone`), so that the permuting fetch may
        store them rounded to bfloat16 on a TPU. Traced once a batch shape
        and matrix precision.
        """
        seen = (xb.shape, xb.dtype, jax.config.jax_default_matmul_precision)
        if seen not in self._inputs_rounded:
            module = self.spec.module
            # shapes alone: the forward pass is traced, nothing is drawn
            abstract_rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
            params = jax.eval_shape(module.init, abstract_rng, xb)
            self._inputs_rounded[seen] = _read_by_products_alone(
                lambda x, p, k: module.apply(
                    p, x, deterministic=False, rngs={"dropout": k}
                ),
                xb, params, abstract_rng,
            )
        return self._inputs_rounded[seen]

    def _n_batches(
        self, n: int, batch_size: int, sample_cap: Optional[int]
    ) -> int:
        """Optimizer steps per epoch for a geometry: ``ceil(cap /
        batch_size)``. The cap reaches the compiled program only through
        this count, so caps rounding to the same batch count share one
        compiled epoch."""
        n_samples = self._n_samples(n)
        cap = n_samples if sample_cap is None else max(1, min(sample_cap, n_samples))
        return max(1, math.ceil(cap / batch_size))

    def _epoch_fn(
        self,
        n: int,
        batch_size: int,
        shuffle: bool,
        gated: bool = False,
        sample_cap: Optional[int] = None,
        quarantine: bool = False,
        inject: bool = False,
        masked: bool = False,
        row_fetch: str = "gather",
        step_loop: str = "scan",
    ):
        """
        Build (and cache) the jitted fleet-epoch function for a given
        (timesteps, batch_size) geometry. One compiled program per geometry,
        reused across the whole fleet and all epochs/folds.

        ``gated`` variants take a per-machine ``active`` flag (early
        stopping); the ungated program skips ITS full-tree select so
        ordinary fits don't pay for early stopping.

        ``quarantine`` variants take (and return) a per-machine
        ``healthy`` flag: a machine whose loss or updated params go
        non-finite keeps its entering params (the non-finite guard,
        docs/robustness.md). This is the one feature that IS paid for
        by default (``quarantine_nonfinite=True``): one isfinite
        reduction over the updated params and one fused masked select
        per machine per epoch — element-wise work, a rounding error
        next to the epoch's matmuls, bought deliberately so a silent
        NaN can never poison a fleet that didn't opt in to a guard.
        ``inject`` variants additionally take a per-machine NaN-poison
        flag — the fault-injection seam, traced into the program ONLY
        when a ``train:nan`` fault is configured, so fault-free
        programs stay byte-identical to injection-off builds.

        ``sample_cap`` bounds the scan at ``ceil(cap / batch_size)``
        optimizer steps — the fleet-wide maximum of REAL samples, computed
        by ``fit`` from the effective weights. Without it, timestep-grid
        padding would inflate the step count: each batch's loss is
        normalized by its own real-weight sum, so every extra batch is a
        full-magnitude optimizer step and a 288-row machine on a 512-row
        grid would silently train ~1.8x the steps the solo path
        (models/core.py: ceil(n_train / batch_size), Keras semantics)
        takes. Real samples are packed into the leading batches per
        machine (masked argsort), and a step whose batch holds no real
        samples leaves params and optimizer state untouched.

        ``masked`` variants take a per-machine (f_out,) feature-column
        weight (padded-policy buckets with ragged widths): the loss
        means over REAL output columns only, so pad columns never move
        params or stopping decisions. Unmasked programs carry no trace
        of the feature, keeping exact-policy fits bit-identical.

        ``row_fetch`` is how a step's rows reach it (``_choose_row_fetch``,
        which ``fit`` asks once per fit): the default traces the per-step
        gathers. ``step_loop`` is how an epoch runs its steps
        (``_choose_step_loop``): the default traces the ``lax.scan``.
        """
        n_batches = self._n_batches(n, batch_size, sample_cap)
        cache_key = (
            n, batch_size, shuffle, gated, n_batches, quarantine, inject,
            masked, row_fetch, step_loop,
        )

        def build():
            fleet_epoch = self._build_epoch_callable(
                n, batch_size, shuffle, gated, n_batches,
                quarantine=quarantine, inject=inject, masked=masked,
                row_fetch=row_fetch, step_loop=step_loop,
            )
            n_args = 6 + int(gated) + int(quarantine) + int(inject) + int(masked)
            jit_kwargs: dict = {}
            if self.mesh is not None:
                fs = fleet_sharding(self.mesh)
                rs = replicated_sharding(self.mesh)
                data_sh = rs if self.broadcast_data else fs
                jit_kwargs["in_shardings"] = tuple(
                    data_sh if i in (3, 4, 5) else fs for i in range(n_args)
                )
                jit_kwargs["out_shardings"] = (fs,) * (4 if quarantine else 3)
            if self.donate:
                jit_kwargs["donate_argnums"] = (0, 1)
            return jax.jit(fleet_epoch, **jit_kwargs)

        return self._programs.get_or_build(cache_key, build)

    def _build_epoch_callable(
        self,
        n: int,
        batch_size: int,
        shuffle: bool,
        gated: bool,
        n_batches: int,
        quarantine: bool = False,
        inject: bool = False,
        masked: bool = False,
        row_fetch: str = "gather",
        step_loop: str = "scan",
    ):
        """
        The vmapped fleet-epoch callable for a geometry, un-jitted
        (:meth:`_epoch_fn` jits and caches it).

        Per-machine extras ride after the data args in a fixed order:
        ``active`` (``gated``), ``healthy`` (``quarantine``), the
        NaN-poison flag (``inject``), and the (f_out,) feature-column
        weight (``masked``); quarantine variants return the updated
        ``healthy`` as a fourth output.

        What the scope ``fleet.gather`` holds depends on ``row_fetch``.
        ``"gather"``: inside every step, the three row gathers ``Xi[sel]``,
        ``yi[sel]``, ``wb_all[sel]`` (for a windowed spec, the gather of
        the step's windows). ``"permute_epoch"``: once an epoch, before the
        step loop, every machine's rows permuted into batch order by ONE
        kernel call (``ops/row_permute.py``: it reads the tables as they
        lie and writes the per-step slabs, the input slab in bfloat16
        where the step reads it only through default-precision products);
        a step then takes its batch as the loop's own slice, and its
        weights from the sort in ``fleet.order`` that made the order.

        ``step_loop`` ``"fused"`` (the permuting fetch only) runs the
        epoch's steps for the whole fleet in ONE kernel call
        (``ops/fleet_dense.py``) under ``fleet.loss_grad``, Adam's update
        with them: the scope ``fleet.optimizer`` is then empty. The
        kernel's reference, and the path of an un-vmapped call, is the
        scan itself.
        """
        n_samples = self._n_samples(n)
        spec = self.spec
        optimizer = self._optimizer
        lb = spec.lookback_window if spec.windowed else 1
        la = self.lookahead
        n_pad = n_batches * batch_size

        # scan-tail overflow (n_pad may exceed the grid's sample count by
        # up to batch_size - 1): overflow slots repeat sample 0 with a
        # static zero mask
        n_take = min(n_pad, n_samples)
        pad_mask = np.zeros(n_pad, dtype=np.float32)
        pad_mask[:n_take] = 1.0
        pm_all_np = pad_mask.reshape(n_batches, batch_size)

        loss_name = spec.loss
        module = spec.module
        windowed = spec.windowed
        permute = row_fetch == "permute_epoch"
        if permute:
            if windowed or self.broadcast_data:
                raise ValueError(
                    "the permuting row fetch is for stacked, non-windowed data"
                )
            from gordo_tpu.ops import row_permute
        fused = step_loop == "fused"
        if fused:
            if not permute:
                raise ValueError("the fused step loop reads the permuting fetch's slabs")
            from gordo_tpu.ops import fleet_dense

        def sample_weights(wi):
            """Per-sample effective weight for every grid sample: a window
            is as real as its least-real row times its target row."""
            if not windowed:
                return wi
            win_min = _sliding_window_min(wi, lb)[:n_samples]
            return win_min * jax.lax.dynamic_slice(wi, (lb - 1 + la,), (n_samples,))

        def gather(Xi, yi, sel):
            # Xi: (n, f); sel: (batch,) window starts / row ids
            if windowed:
                rows = sel[:, None] + jnp.arange(lb, dtype=jnp.int32)[None, :]
                xb = Xi[rows]                      # (batch, lb, f)
                yb = yi[sel + (lb - 1 + la)]
            else:
                xb = Xi[sel]
                yb = yi[sel]
            return xb, yb

        def machine_epoch(params, opt_state, key, Xi, yi, wi, *extras):
            """
            One epoch for ONE machine; vmapped over the fleet axis.

            ``active`` (scalar 0/1, gated variants only) gates the state
            transition: an inactive (early-stopped) machine's params and
            optimizer state come out EXACTLY as they went in —
            zero-weighting alone would still let regularization-penalty
            gradients, optimizer momentum, and weight decay drift the
            params.

            ``healthy`` (scalar bool, quarantine variants) gates the
            same way, and flips False — permanently, for this fit —
            when the machine's epoch loss or updated params go
            non-finite: the faulted epoch's update is discarded, so the
            machine freezes at its last finite params (the quarantine
            guard, docs/robustness.md).
            """
            _extras = list(extras)
            active = _extras.pop(0) if gated else None
            healthy = _extras.pop(0) if quarantine else None
            inj_flag = _extras.pop(0) if inject else None
            fm = _extras.pop(0) if masked else None  # (f_out,) column mask
            # The trainer's own work carries stable scope names
            # (jax.named_scope: metadata only, no arithmetic), so that a
            # device trace can sum it by what it is and not by fusion.237:
            # fleet.order, fleet.step > fleet.gather, fleet.loss_grad,
            # fleet.optimizer, then fleet.guard (docs/observability.md;
            # chipbench/scopes.json)
            with jax.named_scope("fleet.order"):
                wb_all = sample_weights(wi)            # (n_samples,)
                real = wb_all > 0
                if shuffle:
                    noise = jax.random.uniform(key, (n_samples,))
                    sort_key = jnp.where(real, noise, 2.0 + noise)
                else:
                    # stable: real samples keep their time order up front.
                    # int32 keys: float32 arange collides above 2^24
                    # samples, which could misplace a real sample past the
                    # scan cap.
                    ar = jnp.arange(n_samples, dtype=jnp.int32)
                    sort_key = jnp.where(real, ar, n_samples + ar)
                if permute:
                    # the weights ride the sort: ONE stable sort gives the
                    # order argsort gives and the weights already in it
                    _, order, w_sorted = jax.lax.sort(
                        (sort_key, jnp.arange(n_samples, dtype=jnp.int32), wb_all),
                        num_keys=1, is_stable=True,
                    )
                else:
                    order = jnp.argsort(sort_key).astype(jnp.int32)
                if n_pad > n_samples:
                    order = jnp.concatenate(
                        [order, jnp.zeros(n_pad - n_samples, dtype=jnp.int32)]
                    )
                sel_all = order[:n_pad].reshape(n_batches, batch_size)
                pm_all = jnp.asarray(pm_all_np)
                if permute:
                    # overflow slots fetch sample 0 and weigh nothing
                    w_all = jnp.pad(w_sorted, (0, max(0, n_pad - n_samples)))
                    w_all = w_all[:n_pad].reshape(n_batches, batch_size)

            def loss_fn(p, xb, yb, wb, dropout_key, fm):
                out, penalty = module.apply(
                    p, xb, deterministic=False, rngs={"dropout": dropout_key}
                )
                per = (
                    masked_per_sample_loss(loss_name, out, yb, fm)
                    if masked
                    else per_sample_loss(loss_name, out, yb)
                )
                total_w = jnp.maximum(jnp.sum(wb), 1.0)
                return jnp.sum(per * wb) / total_w + penalty, jnp.sum(per * wb)

            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

            if permute:
                # a step reads its input rows only through the model (the
                # loss reads the targets), and autodiff's products of them
                # keep the forward's precision: a forward that reads them
                # only through default-precision products lets the kernel
                # store them rounded
                rounded = self._inputs_in_products(
                    jax.ShapeDtypeStruct((batch_size,) + Xi.shape[1:], Xi.dtype)
                )
                with jax.named_scope("fleet.gather"):
                    fetch_epoch = row_permute.epoch_batches(
                        n_batches, input_in_products=rounded
                    )
                    xb_all, yb_all = fetch_epoch(Xi, yi, order[:n_pad])

            def step(key, fm, carry, batch):
                p, o = carry
                if permute:
                    xb, yb, wb, idx = batch
                    xb = xb.astype(Xi.dtype)  # a no-op where stored float32
                else:
                    sel, pm, idx = batch
                    with jax.named_scope("fleet.gather"):
                        xb, yb = gather(Xi, yi, sel)
                        wb = wb_all[sel] * pm
                dkey = jax.random.fold_in(key, idx)
                # the model's own Flax scopes nest under this one, the
                # backward pass under transpose(jvp(...))
                with jax.named_scope("fleet.loss_grad"):
                    (_, loss_sum), grads = grad_fn(p, xb, yb, wb, dkey, fm)
                with jax.named_scope("fleet.optimizer"):
                    updates, new_o = optimizer.update(grads, o, p)
                    new_p = jax.tree.map(lambda a, u: a + u, p, updates)
                    # an all-padding batch must be a no-op, not a
                    # zero-gradient optimizer step (momentum decay / penalty
                    # gradients would still move the params)
                    has_real = jnp.sum(wb) > 0
                    p = jax.tree.map(
                        lambda new, old: jnp.where(has_real, new, old), new_p, p
                    )
                    o = jax.tree.map(
                        lambda new, old: jnp.where(has_real, new, old), new_o, o
                    )
                return (p, o), (loss_sum, jnp.sum(wb))

            step_ids = jnp.arange(n_batches, dtype=jnp.int32)

            def scan_steps(params, opt_state, key, batches, fm):
                # every per-machine value an argument, none closed over:
                # the fused step loop's rule sees the fleet's
                (p, o), (loss_sums, w_sums) = jax.lax.scan(
                    functools.partial(step, key, fm),
                    (params, opt_state),
                    batches + (step_ids,),
                )
                return p, o, loss_sums, w_sums

            # fleet.step holds what the step loop runs beside the named
            # parts of a step: the loop's own plumbing and the buffers XLA
            # makes for it (a fill of a scan's stacked outputs would carry
            # this path: specs.lstm_time_scan allocates and never fills)
            with jax.named_scope("fleet.step"):
                batches = (xb_all, yb_all, w_all) if permute else (sel_all, pm_all)
                if fused:
                    stack = fleet_dense.plan(spec, Xi.shape[-1])
                    if stack is None:
                        raise ValueError("the fused step loop does not serve this spec")
                    scan_steps = fleet_dense.epoch_steps(stack, scan_steps)
                new_params, new_opt, loss_sums, w_sums = scan_steps(
                    params, opt_state, key, batches, fm
                )
            with jax.named_scope("fleet.guard"):
                epoch_loss = jnp.sum(loss_sums) / jnp.maximum(jnp.sum(w_sums), 1.0)
                if inject:
                    # the train:nan fault seam: poison this machine's epoch
                    # loss so the guard below sees exactly what a real
                    # divergence produces
                    epoch_loss = jnp.where(inj_flag, jnp.nan, epoch_loss)
                keep = active > 0.5 if gated else None
                healthy_out = None
                if quarantine:
                    finite = jnp.isfinite(epoch_loss)
                    for leaf in jax.tree.leaves(new_params):
                        finite = finite & jnp.all(jnp.isfinite(leaf))
                    healthy_out = healthy & finite
                    keep = healthy_out if keep is None else keep & healthy_out
                if keep is not None:
                    params = jax.tree.map(
                        lambda new, old: jnp.where(keep, new, old),
                        new_params,
                        params,
                    )
                    opt_state = jax.tree.map(
                        lambda new, old: jnp.where(keep, new, old),
                        new_opt,
                        opt_state,
                    )
                else:
                    params, opt_state = new_params, new_opt
            if quarantine:
                return params, opt_state, epoch_loss, healthy_out
            return params, opt_state, epoch_loss

        n_args = 6 + int(gated) + int(quarantine) + int(inject) + int(masked)
        if self.broadcast_data:
            # one shared dataset; only params/opt/keys (and the
            # per-machine flags) carry the fleet axis
            in_axes = tuple(None if i in (3, 4, 5) else 0 for i in range(n_args))
            fleet_epoch = jax.vmap(machine_epoch, in_axes=in_axes)
        else:
            fleet_epoch = jax.vmap(machine_epoch, in_axes=(0,) * n_args)

        return fleet_epoch

    def _val_fn(
        self, n: int, batch_size: int, lo: int = 0, masked: bool = False
    ):
        """
        Build (and cache) the jitted per-machine validation loss over the
        fleet (:meth:`_build_val_callable`).
        """
        cache_key = ("val", n, batch_size, lo, masked)

        def build():
            fleet_val = self._build_val_callable(n, batch_size, lo, masked)
            jit_kwargs: dict = {}
            if self.mesh is not None:
                fs = fleet_sharding(self.mesh)
                rs = replicated_sharding(self.mesh)
                data_sh = rs if self.broadcast_data else fs
                shardings = (fs, data_sh, data_sh, data_sh)
                if masked:
                    shardings = shardings + (fs,)
                jit_kwargs["in_shardings"] = shardings
                jit_kwargs["out_shardings"] = fs
            return jax.jit(fleet_val, **jit_kwargs)

        return self._programs.get_or_build(cache_key, build)

    def _build_val_callable(
        self, n: int, batch_size: int, lo: int = 0, masked: bool = False
    ):
        """
        The vmapped per-machine validation loss, un-jitted: deterministic
        forward, per-sample loss weighted by a (M, n) validation mask —
        chunked like the training scan so the windowed gather never
        materializes more than (batch, lb, f) at once (mirrors the solo
        path's chunked val loss, models/core.py:334-356).

        ``lo`` skips samples below the fleet-wide first validation index:
        the eval walks only the holdout tail instead of zero-weighting the
        whole training prefix every epoch. ``masked`` variants take the
        same per-machine (f_out,) feature-column weight the training
        epoch does, so a padded machine's val loss ignores pad columns.
        """
        spec = self.spec
        lb = spec.lookback_window if spec.windowed else 1
        la = self.lookahead
        n_samples = self._n_samples(n)
        n_eval = max(1, n_samples - lo)
        n_batches = max(1, math.ceil(n_eval / batch_size))
        n_pad = n_batches * batch_size
        sample_ids = np.zeros(n_pad, dtype=np.int32)
        sample_ids[:n_eval] = lo + np.arange(n_eval, dtype=np.int32)
        pad_mask = np.zeros(n_pad, dtype=np.float32)
        pad_mask[:n_eval] = 1.0
        sel_all = jnp.asarray(sample_ids.reshape(n_batches, batch_size))
        pm_all = jnp.asarray(pad_mask.reshape(n_batches, batch_size))

        loss_name = spec.loss
        module = spec.module
        windowed = spec.windowed

        def machine_val(params, Xi, yi, vi, *extras):
            fm = extras[0] if masked else None  # (f_out,) column mask

            def one_chunk(args):
                sel, pm = args
                with jax.named_scope("fleet.gather"):
                    if windowed:
                        rows = (
                            sel[:, None] + jnp.arange(lb, dtype=jnp.int32)[None, :]
                        )
                        xb = Xi[rows]
                        tgt = sel + (lb - 1 + la)
                        yb = yi[tgt]
                        wb = jnp.min(vi[rows], axis=1) * vi[tgt]
                    else:
                        xb = Xi[sel]
                        yb = yi[sel]
                        wb = vi[sel]
                    wb = wb * pm
                with jax.named_scope("fleet.val_loss"):
                    out, _ = module.apply(params, xb)
                    per = (
                        masked_per_sample_loss(loss_name, out, yb, fm)
                        if masked
                        else per_sample_loss(loss_name, out, yb)
                    )
                    return jnp.sum(per * wb), jnp.sum(wb)

            sums, ws = jax.lax.map(one_chunk, (sel_all, pm_all))
            return jnp.sum(sums) / jnp.maximum(jnp.sum(ws), 1.0)

        if self.broadcast_data:
            in_axes: tuple = (0, None, None, None)
        else:
            in_axes = (0, 0, 0, 0)
        if masked:
            in_axes = in_axes + (0,)
        return jax.vmap(machine_val, in_axes=in_axes)

    def _validation_masks(
        self, w, rows: np.ndarray, validation_split: float
    ) -> Tuple[jnp.ndarray, jnp.ndarray, np.ndarray, int]:
        """
        Per-machine Keras ``validation_split`` semantics as timestep masks:
        the LAST fraction of each machine's samples (windows, for sequence
        models) is held out, before any shuffling (models/core.py:264-272).

        For contiguous prefix data the window -> max-row mapping is
        monotonic, so per-timestep masks express the sample split EXACTLY:
        a window s trains iff s < n_train (all its rows fall before the
        train cut) and validates iff s >= n_train with its whole window
        inside the real region.

        ``w`` is the effective (M, n) weights on the device and ``rows``
        their fetched real-row counts (``_fit_facts``): the per-machine
        cuts are float64/int64 host arithmetic on the (M,) counts, and the
        masks are built on the device from the uploaded cuts.

        Returns (train_mask, val_mask, has_val, val_lo): the (M, n) float32
        masks (sharded), a (M,) bool marking machines whose split actually
        yields validation samples (a machine too small for ``n_val >= 1``
        has none — its monitored metric must fall back to the training
        loss, like the solo path with ``n_val == 0``), and the smallest
        first-validation-sample index across machines (so the eval only
        walks the holdout tail, not the whole dataset).
        """
        lb = self.spec.lookback_window if self.spec.windowed else 1
        la = self.lookahead
        # count rows, not weight mass: fractional sample weights must not
        # shift the split boundary
        n_real = np.asarray(rows, dtype=np.int64)
        n_samples = np.maximum(n_real - lb + 1 - la, 0)
        n_val = (n_samples * validation_split).astype(np.int64)
        n_train = n_samples - n_val
        if np.any((n_samples > 0) & (n_train <= 0)):
            raise ValueError(
                f"validation_split={validation_split} leaves no training "
                "samples for at least one machine"
            )
        # last timestep a training window touches is s + lb - 1 + la for
        # s = n_train - 1, so the cut excludes exactly samples >= n_train.
        # train_mask is the bare cut indicator — the caller multiplies it
        # into the effective weights, so folding w in here would SQUARE
        # every non-binary weight; val_mask is used standalone as the eval
        # weight, so it does carry the effective weights (once)
        train_cut = n_train + lb - 1 + la
        train_mask, val_mask = _split_masks(
            w, train_cut.astype(np.int32), n_train.astype(np.int32)
        )
        has_val = n_val > 0
        val_lo = int(n_train[has_val].min()) if has_val.any() else 0
        return self._shard(train_mask), self._shard(val_mask), has_val, val_lo

    # -- public API ------------------------------------------------------
    @_under_fit_span
    def fit(
        self,
        data: StackedData,
        keys: jnp.ndarray,
        epochs: int = 1,
        batch_size: int = 32,
        shuffle: Optional[bool] = None,
        params: Any = None,
        opt_state: Any = None,
        extra_weight: Optional[jnp.ndarray] = None,
        checkpointer: Optional[Any] = None,
        checkpoint_every: int = 1,
        early_stopping_patience: Optional[int] = None,
        early_stopping_min_delta: float = 0.0,
        early_stopping_start_from_epoch: int = 0,
        restore_best_weights: bool = False,
        validation_split: float = 0.0,
        early_stopping_on_val: Optional[bool] = None,
        machine_names: Optional[List[str]] = None,
    ) -> Tuple[Any, np.ndarray]:
        """
        Train the fleet. Returns (stacked params, losses (epochs, M)).

        With ``quarantine_nonfinite`` (the default), a machine whose
        epoch loss or updated params go non-finite is quarantined
        in-program: its params roll back to the last finite epoch and
        freeze while the rest of the fleet trains on. The mask comes
        back with the history fetches — ``self.healthy_`` (final (M,)
        mask), ``self.quarantine_epoch_`` ((M,) first faulted epoch, -1
        for healthy) and ``self.healthy_history_`` — at zero additional
        host syncs. ``machine_names`` (optional, fleet order) names the
        casualties in ``machine_quarantined`` events and lets
        ``GORDO_FAULT_INJECT`` train faults target machines by name.

        ``opt_state`` lets callers pre-build/modify the stacked optimizer
        state (e.g. per-machine hyperparameters via inject_hyperparams);
        None initializes it fresh from ``params``.

        ``extra_weight`` ((M, n), e.g. a CV-fold train mask) multiplies the
        base sample weights — this is how fold training reuses the same
        compiled program.

        ``checkpointer`` (a parallel.checkpoint.FleetCheckpointer) saves
        (params, opt_state) every ``checkpoint_every`` epochs and, when the
        directory already holds checkpoints, resumes from the last
        completed epoch — preemption-safe long fleet builds.

        ``early_stopping_patience`` enables PER-MACHINE early stopping by
        loss masking (SURVEY.md §7.6): a machine whose epoch loss hasn't
        improved by ``early_stopping_min_delta`` for that many epochs gets
        zero sample weights from then on — its params freeze while the
        rest of the fleet trains — and the loop ends early once every
        machine has stopped. This syncs the (M,) losses to host each
        epoch (the cost of the decision). Stopped machines still ride
        along in the compiled program (gated, not compacted). Monitored
        metric is the training loss.

        ``restore_best_weights`` (early stopping only) keeps a device-side
        per-machine snapshot of the params at each machine's best epoch —
        one masked tree-select per improving epoch, costing one extra copy
        of the stacked params in device memory — and returns those instead
        of the final params, matching Keras
        ``EarlyStopping(restore_best_weights=True)`` per machine.

        ``validation_split`` holds out the LAST fraction of each machine's
        samples (per-machine, counted over its real rows — Keras
        semantics, models/core.py:264-272): held-out samples get zero
        training weight, and a per-machine validation loss is computed
        every epoch (fetch it from ``self.val_losses_`` after ``fit``,
        shape (epochs, M)). With early stopping, the monitored metric
        defaults to the validation loss when a split is configured
        (``early_stopping_on_val=None``); pass False to monitor the
        training loss regardless (Keras ``monitor="loss"``).
        """
        fit_start = time.perf_counter()
        phases = _FitPhases()
        with phases.timed("prepare_s"), tracing.start_span("train.prepare"):
            if shuffle is None:
                shuffle = not self.spec.windowed
            if not 0.0 <= float(validation_split) < 1.0:
                raise ValueError(
                    f"validation_split must be in [0, 1), got {validation_split}"
                )
            data = self.shard_data(data)
            w = data.sample_weight
            # padded-policy buckets carry a per-machine output-column mask;
            # None (every exact-policy fit) keeps the historical unmasked
            # programs bit-identically
            fmask = data.feature_out_weight
            masked = fmask is not None
            if masked and self.broadcast_data:
                raise ValueError(
                    "broadcast_data fleets share one dataset and cannot take "
                    "per-machine feature_out_weight masks"
                )
            if extra_weight is not None:
                w = w * self._shard(jnp.asarray(extra_weight))
            # what the fit must know of its weights is counted where they
            # are; only the two (M,) count vectors cross to the host
            rows, valid = phases.fetched(host_fetch(self._fit_facts(w)))

            val_w = None
            has_val = None
            val_lo = 0
            self.val_losses_: Optional[np.ndarray] = None
            if validation_split > 0.0:
                # computed from the EFFECTIVE weights so a CV fold's extra
                # mask shrinks the split's base, exactly like a solo fold fit
                # on that fold's rows would
                train_mask, val_w, has_val, val_lo = self._validation_masks(
                    w, rows, float(validation_split)
                )
                w = w * train_mask
                rows, valid = phases.fetched(host_fetch(self._fit_facts(w)))
            monitor_val = (
                val_w is not None
                if early_stopping_on_val is None
                else bool(early_stopping_on_val) and val_w is not None
            )

            if params is None:
                params = self.init_params(keys, data.X.shape[-1])
            if opt_state is None:
                opt_state = self.init_opt_state(params)
            keys = self._shard(jnp.asarray(keys))

            early_stopping = early_stopping_patience is not None
            m = len(keys)  # the fleet axis (== data.n_machines unless broadcast)
            quarantine = self.quarantine_nonfinite
            # the train:nan fault seam, resolved ONCE per fit: None unless a
            # matching GORDO_FAULT_INJECT spec targets this fleet (and then
            # an ((M,) mask, epoch) pair baked into a distinct program)
            inj = _faults.train_nan_injection(machine_names, m, sites=self.fault_sites)
            healthy_np = np.ones(m, dtype=bool)
            self.healthy_: Optional[np.ndarray] = None
            self.quarantine_epoch_: Optional[np.ndarray] = None
            self.healthy_history_: Optional[np.ndarray] = None
            if has_val is not None and has_val.shape[0] != m:
                # broadcast_data: masks are per weight ROW (the one shared
                # dataset), but monitored metrics and val columns are per
                # MACHINE — expand so boolean indexing lines up
                has_val = np.repeat(has_val, m)
            if early_stopping:
                es_state = {
                    "best": np.full(m, np.inf, dtype=np.float64),
                    "wait": np.zeros(m, dtype=np.int64),
                    "active": np.ones(m, dtype=bool),
                    "last_loss": np.zeros(m, dtype=np.float64),
                }
                es_stop_at = max(int(early_stopping_patience), 1)
                es_delta = abs(float(early_stopping_min_delta))

            start_epoch = 0
            if checkpointer is not None and checkpointer.latest_epoch() is not None:
                extra_template: dict = {}
                if quarantine:
                    extra_template["healthy"] = healthy_np
                if early_stopping:
                    extra_template.update(es_state)
                if extra_template:
                    params, opt_state, done, restored_extra = (
                        checkpointer.restore_with_extra(
                            params, opt_state, extra_template,
                            # a pre-quarantine ES checkpoint lacks "healthy";
                            # its ES state must still restore
                            optional_extra_keys=("healthy",),
                        )
                    )
                    if restored_extra is not None:
                        restored_extra = {
                            k: np.asarray(v) for k, v in restored_extra.items()
                        }
                        restored_healthy = restored_extra.pop("healthy", None)
                        if quarantine and restored_healthy is not None:
                            healthy_np = restored_healthy.astype(bool)
                    if early_stopping and restored_extra and "active" in restored_extra:
                        es_state = restored_extra
                        es_state["active"] = es_state["active"].astype(bool)
                    elif early_stopping:
                        # no (or healthy-only) extra: a checkpoint from a
                        # plain fit or an older layout
                        logger.warning(
                            "Resuming an early-stopping fleet fit without saved "
                            "early-stop state (older checkpoint?): stopped "
                            "machines will briefly reactivate"
                        )
                else:
                    params, opt_state, done = checkpointer.restore(params, opt_state)
                start_epoch = done + 1
                logger.info("Resuming fleet fit at epoch %d/%d", start_epoch, epochs)
                emit_event(
                    "fit_resume", path="fleet", start_epoch=start_epoch, epochs=epochs
                )

            if self.broadcast_data:
                if data.n_machines != 1:
                    raise ValueError(
                        "broadcast_data expects a single-machine StackedData "
                        f"(shared by all fleet members), got M={data.n_machines}"
                    )
                if w.shape[0] != 1:
                    # e.g. a per-machine (M, n) extra_weight: the shared-data
                    # epoch takes ONE weight row; silently using row 0 would
                    # train every member with machine 0's mask
                    raise ValueError(
                        "broadcast_data cannot take per-machine weights "
                        f"(got weight shape {w.shape}); weights must be (1, n)"
                    )
                X_arg, y_arg, w_arg = data.X[0], data.y[0], w[0]
                val_arg = val_w[0] if val_w is not None else None
            else:
                X_arg, y_arg, w_arg = data.X, data.y, w
                val_arg = val_w

            if self.broadcast_data:
                # every fleet member trains on the one shared dataset
                rows_per_machine = np.full(m, int(rows.sum()), dtype=np.int64)
            else:
                rows_per_machine = np.asarray(rows, dtype=np.int64)
            sample_cap = max(1, int(valid.max()))
            track_best = early_stopping and restore_best_weights
            row_fetch = self._choose_row_fetch(data, batch_size, sample_cap)
            step_loop = self._choose_step_loop(
                data, batch_size, sample_cap, row_fetch
            )

            epoch_fn = self._epoch_fn(
                data.n_timesteps,
                batch_size,
                shuffle,
                gated=early_stopping,
                sample_cap=sample_cap,
                quarantine=quarantine,
                inject=inj is not None,
                masked=masked,
                row_fetch=row_fetch,
                step_loop=step_loop,
            )
            val_fn = (
                self._val_fn(
                    data.n_timesteps, batch_size, lo=val_lo, masked=masked
                )
                if val_w is not None
                else None
            )

        best_params = None  # set at the first monitored improvement

        healthy_entry = healthy_np.copy()
        healthy_dev = _put_fleet_arr(healthy_np, self.mesh) if quarantine else None
        healthy_rows: list = []

        losses = []
        val_losses: list = []
        # -- telemetry: the first dispatched epoch is synced ONCE so
        # compile+first-step cost separates from the steady state; later
        # epochs keep the async dispatch pipeline intact (their cost is
        # recovered from the loop total at the end-of-fit sync)
        first_epoch_s: Optional[float] = None
        epochs_run = 0
        timesteps_trained = 0
        early_stop_epoch: Optional[int] = None
        dispatch_times: list = []
        loop_start = time.perf_counter()
        for epoch in range(start_epoch, epochs):
            epoch_start = time.perf_counter()
            # the host's share of one epoch: key fold-in, the per-machine
            # flags and the enqueue of the epoch program (the device work
            # is asynchronous and not inside)
            with tracing.start_span("train.dispatch", epoch=epoch):
                epoch_keys = jax.vmap(
                    lambda k: jax.random.fold_in(k, epoch)
                )(keys)
                extras = []
                if early_stopping:
                    extras.append(
                        _put_fleet_arr(
                            es_state["active"].astype(np.float32), self.mesh
                        )
                    )
                if quarantine:
                    extras.append(healthy_dev)
                if inj is not None:
                    # poison only at the configured epoch
                    extras.append(
                        _put_fleet_arr(inj[0] & (epoch == inj[1]), self.mesh)
                    )
                if masked:
                    extras.append(fmask)
                result = epoch_fn(
                    params, opt_state, epoch_keys, X_arg, y_arg, w_arg,
                    *extras
                )
            if quarantine:
                params, opt_state, epoch_loss, healthy_dev = result
            else:
                params, opt_state, epoch_loss = result
            # host-side cost of issuing this epoch (key vmap + dispatch);
            # the async device work itself is not included
            dispatch_times.append(time.perf_counter() - epoch_start)
            epochs_run += 1
            # active ENTERING this epoch (the gate the program just ran)
            timesteps_trained += int(
                rows_per_machine[es_state["active"]].sum()
                if early_stopping
                else rows_per_machine.sum()
            )
            if first_epoch_s is None:
                # guarded to run ONCE per fit (compile-cost telemetry),
                # not per iteration — the sync budget accounts for it
                with tracing.start_span("train.first_sync", epoch=epoch):
                    jax.block_until_ready(epoch_loss)  # lint: disable=host-sync
                first_epoch_s = time.perf_counter() - epoch_start
            if val_fn is not None:
                val_losses.append(
                    val_fn(params, X_arg, y_arg, val_arg, fmask)
                    if masked
                    else val_fn(params, X_arg, y_arg, val_arg)
                )
            # keep the loss on device: a host fetch here would sync every
            # epoch and stall the dispatch pipeline (costly over DCN
            # links); all losses are pulled in one transfer after the loop
            # (except under early stopping, whose per-epoch decision IS a
            # sync)
            if quarantine and not early_stopping:
                # device-resident history row; the end-of-fit bulk fetch
                # pulls it with the losses (no extra sync)
                healthy_rows.append(healthy_dev)
            if early_stopping:
                with phases.timed("decide_s"), tracing.start_span(
                    "train.decide", epoch=epoch
                ):
                    if quarantine:
                        # healthy rides the SAME per-epoch decision sync the
                        # ES path already pays — one call, one transfer
                        step_fetch = phases.fetched(
                            host_fetch(
                                {"loss": epoch_loss, "healthy": healthy_dev}
                            )
                        )
                        loss_np = np.asarray(step_fetch["loss"], dtype=np.float64)
                        healthy_np = np.asarray(step_fetch["healthy"], dtype=bool)
                        healthy_rows.append(healthy_np)
                    else:
                        loss_np = np.asarray(
                            phases.fetched(host_fetch(epoch_loss)),
                            dtype=np.float64,
                        )
                    # a stopped machine's computed loss reflects a discarded
                    # would-be update; report its last active loss instead
                    report = np.where(
                        es_state["active"], loss_np, es_state["last_loss"]
                    )
                    losses.append(report)
                    es_state["last_loss"] = report
                    if monitor_val:
                        val_np = np.asarray(
                            phases.fetched(host_fetch(val_losses[-1])),
                            dtype=np.float64,
                        )
                        # keep the host copy: the end-of-fit stack must not
                        # re-transfer a history already fetched epoch by epoch
                        val_losses[-1] = val_np
                        # a machine too small for any validation samples falls
                        # back to its training loss (solo path: n_val == 0
                        # skips val_loss and EarlyStopping monitors loss) —
                        # monitoring its constant-0.0 val loss would spuriously
                        # stop it at epoch 0
                        monitored = np.where(has_val, val_np, loss_np)
                    else:
                        monitored = loss_np
                    if epoch >= int(early_stopping_start_from_epoch):
                        # the improvement test runs in float32, the losses'
                        # own precision (the state itself stays float64 for
                        # checkpoint-format stability; the values are exact
                        # float32s either way)
                        improved = es_state["active"] & (
                            monitored.astype(np.float32)
                            < es_state["best"].astype(np.float32)
                            - np.float32(es_delta)
                        )
                        es_state["best"] = np.where(
                            improved, monitored, es_state["best"]
                        )
                        es_state["wait"] = np.where(
                            improved, 0, es_state["wait"] + 1
                        )
                        es_state["active"] = es_state["active"] & (
                            es_state["wait"] < es_stop_at
                        )
                        if track_best and improved.any():
                            mask = _put_fleet_arr(improved, self.mesh)
                            best_params = _keep_better(
                                mask,
                                params,
                                params if best_params is None else best_params,
                            )
            else:
                losses.append(epoch_loss)
            epoch_fields: dict = {"path": "fleet", "epoch": epoch}
            if early_stopping:
                # only the early-stopping path syncs losses per epoch;
                # elsewhere the epoch event records dispatch, not results
                epoch_fields.update(
                    mean_loss=float(np.mean(report)),
                    n_active=int(es_state["active"].sum()),
                )
            emit_event("epoch", **epoch_fields)
            if checkpointer is not None and (epoch + 1) % max(
                1, checkpoint_every
            ) == 0:
                with phases.timed("checkpoint_s"), tracing.start_span(
                    "train.checkpoint", epoch=epoch
                ):
                    extra: Optional[dict] = None
                    if quarantine or early_stopping:
                        extra = {}
                        if quarantine:
                            if not early_stopping:
                                # plain fits keep healthy on device; the
                                # checkpoint write is already a sync point
                                healthy_np = np.asarray(
                                    phases.fetched(host_fetch(healthy_dev)),
                                    dtype=bool,
                                )
                            extra["healthy"] = healthy_np
                        if early_stopping:
                            extra.update(es_state)
                    checkpointer.save(epoch, params, opt_state, extra=extra)
            if early_stopping and not es_state["active"].any():
                logger.info(
                    "Fleet early stop: all %d machines stopped at epoch "
                    "%d/%d",
                    m,
                    epoch,
                    epochs,
                )
                early_stop_epoch = epoch
                emit_event(
                    "early_stop", path="fleet", epoch=epoch, n_machines=m
                )
                break
        if checkpointer is not None:
            with phases.timed("checkpoint_s"), tracing.start_span(
                "train.checkpoint"
            ):
                checkpointer.wait()
        if track_best and best_params is not None:
            # each machine leaves with the params of its best epoch; a
            # machine that never hit a monitored epoch (epochs <=
            # start_from_epoch) was never snapshotted and keeps its final
            # params via the first keep_better call's fallback
            params = best_params
        losses_out, n_quarantined = self._collect_fit(
            phases, losses=losses, val_losses=val_losses,
            healthy_rows=healthy_rows, has_val=has_val,
            healthy_entry=healthy_entry, start_epoch=start_epoch,
            machine_names=machine_names, m=m,
        )
        # loop time is read AFTER the bulk fetch above — that fetch is the
        # sync that makes the async epochs' wall-clock real
        self._report_fit(
            phases,
            wall_time_s=time.perf_counter() - fit_start,
            loop_time_s=time.perf_counter() - loop_start,
            first_epoch_s=first_epoch_s,
            epochs_run=epochs_run,
            epochs_configured=epochs,
            start_epoch=start_epoch,
            timesteps_trained=timesteps_trained,
            n_machines=m,
            early_stopping=early_stopping,
            early_stop_epoch=early_stop_epoch,
            n_stopped=(
                int((~es_state["active"]).sum()) if early_stopping else 0
            ),
            dispatch_times=dispatch_times,
            n_quarantined=n_quarantined,
            row_fetch=row_fetch,
            step_loop=step_loop,
        )
        return params, losses_out

    def _collect_fit(
        self,
        phases: "_FitPhases",
        *,
        losses: list,
        val_losses: list,
        healthy_rows: list,
        has_val: Optional[np.ndarray],
        healthy_entry: np.ndarray,
        start_epoch: int,
        machine_names: Optional[List[str]],
        m: int,
    ) -> Tuple[np.ndarray, int]:
        """
        The end of a fit's loop, under ``train.collect``. Whatever history
        is still on the device — a plain fit's whole loss / val / healthy
        history — comes back in ONE bulk transfer. Early stopping already
        host-materialized its history (its decision IS the sync), and
        fetching that again would make ``process_allgather`` treat the
        replicated host copy as per-process data. The histories (one (M,)
        row an epoch) are stacked, and the quarantine bookkeeping runs.
        Sets ``val_losses_``; returns (losses (epochs, M), how many
        machines ended quarantined).
        """

        def stacked(rows, dtype=None):
            return np.stack([np.asarray(r, dtype=dtype) for r in rows])

        with phases.timed("collect_s"), tracing.start_span("train.collect"):
            history = {"loss": losses, "val": val_losses, "healthy": healthy_rows}
            pending = {
                name: rows for name, rows in history.items()
                if rows and not isinstance(rows[0], np.ndarray)
            }
            if pending:
                history.update(phases.fetched(host_fetch(pending)))
            if history["val"]:
                val = stacked(history["val"], np.float64)
                # machines with no validation samples have no val loss
                # (their computed 0.0 is an artifact of the empty weight sum)
                if has_val is not None and not has_val.all():
                    val[:, ~has_val] = np.nan
                self.val_losses_ = val
            losses_out = (
                stacked(history["loss"]) if history["loss"] else np.zeros((0, m))
            )
            n_quarantined = 0
            if self.quarantine_nonfinite:
                n_quarantined = self._finish_quarantine(
                    history["healthy"], healthy_entry, start_epoch,
                    machine_names, m,
                )
        return losses_out, n_quarantined

    def _report_fit(self, phases: "_FitPhases", **telemetry) -> None:
        """A fit's last phase, under ``train.report``: publish its telemetry
        (:meth:`_record_fit_telemetry`). What the publishing itself took can
        only be added once it is over."""
        with phases.timed("report_s"), tracing.start_span("train.report"):
            self._record_fit_telemetry(phases=phases, **telemetry)
        self.fit_telemetry_["report_s"] = phases.seconds["report_s"]

    def _finish_quarantine(
        self,
        healthy_rows: list,
        healthy_entry: np.ndarray,
        start_epoch: int,
        machine_names: Optional[List[str]],
        m: int,
    ) -> int:
        """
        Post-fit quarantine bookkeeping from the already-fetched healthy
        history (one (M,) row an epoch): sets
        ``healthy_`` / ``quarantine_epoch_`` / ``healthy_history_``,
        emits one ``machine_quarantined`` event per casualty, and
        returns how many machines ended the fit quarantined.
        """
        if healthy_rows:
            hist = np.stack([np.asarray(r, dtype=bool) for r in healthy_rows])
        else:
            hist = np.ones((0, m), dtype=bool)
        self.healthy_history_ = hist
        final = hist[-1] if len(hist) else healthy_entry.copy()
        self.healthy_ = final
        quarantine_epoch = np.full(m, -1, dtype=np.int64)
        prev = healthy_entry
        for j in range(len(hist)):
            newly = prev & ~hist[j]
            for i in np.flatnonzero(newly):
                epoch = start_epoch + j
                quarantine_epoch[i] = epoch
                name = (
                    machine_names[i]
                    if machine_names is not None and i < len(machine_names)
                    else None
                )
                logger.warning(
                    "Fleet quarantine: machine %s went non-finite at epoch "
                    "%d; params rolled back to last finite epoch and frozen",
                    name if name is not None else f"index {i}",
                    epoch,
                )
                emit_event(
                    "machine_quarantined",
                    path="fleet",
                    machine_index=int(i),
                    machine=name,
                    epoch=int(epoch),
                )
            prev = hist[j]
        self.quarantine_epoch_ = quarantine_epoch
        return int((~final).sum())

    def _record_fit_telemetry(
        self,
        *,
        wall_time_s: float,
        loop_time_s: float,
        first_epoch_s: Optional[float],
        epochs_run: int,
        epochs_configured: int,
        start_epoch: int,
        timesteps_trained: int,
        n_machines: int,
        early_stopping: bool,
        early_stop_epoch: Optional[int],
        n_stopped: int,
        phases: "_FitPhases",
        dispatch_times: list,
        n_quarantined: int,
        row_fetch: str,
        step_loop: str,
    ) -> None:
        """
        Derive and publish one fit's telemetry: ``self.fit_telemetry_``
        (the builder copies it into bucket reports), the process metrics
        registry, and a ``fit_finished`` event.

        Compile time is estimated as (the first epoch, synced) - (one
        steady-state epoch): the first dispatch is the only one that pays
        XLA compilation (per geometry), and all later ones reuse the
        program. When nothing ran after the first epoch there is no steady
        state to subtract, so ``compile_time_s`` degrades to the whole
        first epoch's cost (an upper bound).

        ``dispatch_times`` are the HOST-side seconds spent issuing each
        epoch (key derivation + program submission, not the device work),
        one dispatch an epoch: their steady-state mean is
        ``dispatch_gap_s_mean``. The first dispatch is excluded (it carries
        tracing and compile time). ``epochs_per_sync`` is how many epochs
        each device->host round-trip bought.

        ``phases`` carries what the fit measured at its own boundaries
        (:class:`_FitPhases`): the seconds of each host phase outside the
        dispatches and the bytes and count of its device->host fetches.
        """
        n_host_syncs = phases.n_host_syncs
        steady = None
        if epochs_run > 1 and first_epoch_s is not None:
            steady = max(0.0, (loop_time_s - first_epoch_s) / (epochs_run - 1))
        compile_s = None
        if first_epoch_s is not None:
            compile_s = (
                max(0.0, first_epoch_s - steady)
                if steady is not None
                else first_epoch_s
            )
        throughput = (
            timesteps_trained / loop_time_s if loop_time_s > 0 else None
        )
        # compile-free rate: what the fit would sustain if it ran forever
        # (the whole-loop rate above amortizes the one-off compile)
        steady_throughput = None
        if steady and epochs_run > 0:
            steady_throughput = (timesteps_trained / epochs_run) / steady
        steady_dispatches = dispatch_times[1:]
        dispatch_gap = (
            sum(steady_dispatches) / len(steady_dispatches)
            if steady_dispatches
            else None
        )
        dispatch_overhead = sum(dispatch_times) or None
        epochs_per_sync = (
            epochs_run / n_host_syncs if n_host_syncs else None
        )
        self.fit_telemetry_ = {
            "path": "fleet",
            "wall_time_s": wall_time_s,
            "epoch_loop_s": loop_time_s,
            "first_epoch_s": first_epoch_s,
            "first_dispatch_s": first_epoch_s,
            "steady_state_epoch_s": steady,
            "compile_time_s": compile_s,
            "epochs_configured": epochs_configured,
            "epochs_run": epochs_run,
            "resumed_from_epoch": start_epoch if start_epoch else None,
            "n_machines": n_machines,
            "sensor_timesteps_trained": timesteps_trained,
            "sensor_timesteps_per_s": throughput,
            "steady_state_sensor_timesteps_per_s": steady_throughput,
            "early_stopping": early_stopping,
            "early_stop_epoch": early_stop_epoch,
            "n_machines_early_stopped": n_stopped,
            "n_machines_quarantined": n_quarantined,
            # how the steps' rows reached them (_choose_row_fetch), and the
            # epochs run on that path
            "row_fetch": {"path": row_fetch, "epochs": epochs_run},
            # how their optimizer steps ran (_choose_step_loop), and the
            # share of this call's epochs whose steps ran in the kernel (a
            # float: what a benchmark's call log keeps)
            "step_loop": {"path": step_loop, "epochs": epochs_run},
            "fused_step_share": float(step_loop == "fused") if epochs_run else 0.0,
            # one dispatch an epoch
            "n_dispatches": epochs_run,
            "n_host_syncs": n_host_syncs,
            "epochs_per_sync": epochs_per_sync,
            "dispatch_overhead_s": dispatch_overhead,
            "dispatch_gap_s_mean": dispatch_gap,
            # the host's phases outside the dispatches (the first sync is
            # first_dispatch_s above; report_s is added when it is over)
            "prepare_s": phases.seconds["prepare_s"],
            "decide_s": phases.seconds["decide_s"],
            "checkpoint_s": phases.seconds["checkpoint_s"],
            "collect_s": phases.seconds["collect_s"],
            "host_fetch_bytes": phases.host_fetch_bytes,
        }
        reg = get_registry()
        reg.histogram(
            "gordo_train_fit_seconds", "Fleet fit wall time", ("path",)
        ).observe(wall_time_s, path="fleet")
        if compile_s is not None:
            reg.histogram(
                "gordo_train_compile_seconds",
                "Compile + first-step time of a fit's first epoch",
                ("path",),
            ).observe(compile_s, path="fleet")
        if steady is not None:
            reg.histogram(
                "gordo_train_epoch_seconds",
                "Steady-state (post-compile) epoch wall time",
                ("path",),
            ).observe(steady, path="fleet")
        reg.counter(
            "gordo_train_epochs_total", "Training epochs executed", ("path",)
        ).inc(epochs_run, path="fleet")
        reg.counter(
            "gordo_train_sensor_timesteps_total",
            "Real sensor-timesteps trained over",
            ("path",),
        ).inc(timesteps_trained, path="fleet")
        if n_stopped:
            reg.counter(
                "gordo_train_early_stops_total",
                "Machines halted by per-machine early stopping",
                ("path",),
            ).inc(n_stopped, path="fleet")
        if self.quarantine_nonfinite:
            reg.gauge(
                "gordo_train_quarantined_machines",
                "Machines quarantined by the non-finite guard (last fit)",
                ("path",),
            ).set(n_quarantined, path="fleet")
        reg.counter(
            "gordo_train_host_syncs_total",
            "Device->host synchronizations paid by fits",
            ("path",),
        ).inc(n_host_syncs, path="fleet")
        reg.counter(
            "gordo_train_host_fetch_bytes_total",
            "Bytes fits brought from the device to the host",
            ("path",),
        ).inc(phases.host_fetch_bytes, path="fleet")
        if epochs_per_sync is not None:
            reg.gauge(
                "gordo_train_epochs_per_sync",
                "Epochs bought per device->host round-trip (last fit)",
                ("path",),
            ).set(epochs_per_sync, path="fleet")
        if dispatch_overhead is not None:
            reg.histogram(
                "gordo_train_dispatch_seconds",
                "Host-side dispatch overhead of one whole fit",
                ("path",),
            ).observe(dispatch_overhead, path="fleet")
        emit_event(
            "fit_finished",
            path="fleet",
            epochs_run=epochs_run,
            n_machines=n_machines,
            wall_time_s=round(wall_time_s, 4),
            sensor_timesteps_per_s=throughput,
        )

    def predict(self, params: Any, X: jnp.ndarray, batch_size: int = 8192) -> np.ndarray:
        """
        Fleet forward pass. X: (M, n, f) ->
        (M, n_out, f_out) where n_out = n - lookback + 1 - lookahead for
        windowed models, else n.

        ``batch_size`` bounds the windows one DEVICE materializes at a
        time, across the machines it holds: a windowed fleet with more
        than that is scored in chunks inside the program (``lax.map``),
        so the gather and the activations behind it stay at
        (batch_size, lookback, ...) per device however wide the bucket.
        A per-machine bound let the footprint grow with M — the flagship
        bucket (8 machines x 16,384 rows, lookback 64) asked for 16.06 GB
        of a v5e's 15.75 GB and was refused at compile.
        """
        X = jnp.asarray(X)
        fn = self._predict_fn(
            X.shape[1], self._predict_chunk(X.shape[0], batch_size)
        )
        return np.asarray(fn(params, X))

    def _predict_chunk(self, n_machines: int, batch_size: int) -> int:
        """Windows per machine per in-program chunk such that one device
        (holding its share of ``n_machines``) materializes at most
        ``batch_size`` windows at a time."""
        n_devices = self.mesh.devices.size if self.mesh is not None else 1
        return max(1, batch_size // math.ceil(n_machines / n_devices))

    def _predict_fn(self, n: int, batch_size: int):
        """Build (and cache) the jitted fleet forward for a geometry."""
        from gordo_tpu.ops.windowing import num_windows, window_sample_indices

        spec = self.spec
        lb = spec.lookback_window if spec.windowed else 1
        la = self.lookahead
        if batch_size < 1:
            raise ValueError(f"batch_size must be >= 1, got {batch_size}")
        # the direct (un-chunked) program is independent of batch_size, so
        # all large-enough batch_sizes share one cache entry
        chunked = spec.windowed and num_windows(n, lb, la) > batch_size
        cache_key = ("predict", n, batch_size if chunked else None)
        return self._programs.get_or_build(
            cache_key,
            lambda: self._build_predict_fn(n, batch_size, chunked),
        )

    def _build_predict_fn(self, n: int, batch_size: int, chunked: bool):
        """The uncached body of :meth:`_predict_fn`."""
        from gordo_tpu.ops.windowing import window_sample_indices

        spec = self.spec
        lb = spec.lookback_window if spec.windowed else 1
        la = self.lookahead
        if spec.windowed:
            rows_np = window_sample_indices(n, lb, la)  # (n_out, lb)
            n_out = len(rows_np)
            if not chunked:
                rows = jnp.asarray(rows_np)

                def one(p, Xi):
                    out, _ = spec.module.apply(p, Xi[rows])  # (n_out, lb, f)
                    return out

            else:
                offs = jnp.arange(lb, dtype=jnp.int32)[None, :]
                n_chunks = math.ceil(n_out / batch_size)
                n_pad = n_chunks * batch_size
                starts = np.zeros(n_pad, dtype=np.int32)
                starts[:n_out] = np.arange(n_out, dtype=np.int32)
                chunked_starts = jnp.asarray(
                    starts.reshape(n_chunks, batch_size)
                )

                def one(p, Xi):
                    def do_chunk(sel):
                        out, _ = spec.module.apply(p, Xi[sel[:, None] + offs])
                        return out

                    outs = jax.lax.map(do_chunk, chunked_starts)
                    return outs.reshape(n_pad, *outs.shape[2:])[:n_out]

        else:
            def one(p, Xi):
                out, _ = spec.module.apply(p, Xi)
                return out

        fleet_apply = jax.vmap(one)
        if self.mesh is not None:
            fs = fleet_sharding(self.mesh)
            fleet_apply = jax.jit(
                fleet_apply, in_shardings=(fs, fs), out_shardings=fs
            )
        else:
            fleet_apply = jax.jit(fleet_apply)
        return fleet_apply

    @staticmethod
    def unstack_params(params: Any, index: int) -> Any:
        """Extract machine ``index``'s param pytree from the stacked fleet."""
        return jax.tree.map(lambda a: np.asarray(a[index]), params)

    @staticmethod
    def unstack_all(params: Any, n: int) -> List[Any]:
        """
        Host-materialize the stacked fleet params with ONE device->host
        transfer and slice per machine on host. Per-machine
        ``unstack_params`` pays a separate transfer per machine per leaf
        (~2,800 roundtrips for a 200-machine fleet); this is the bulk
        path the builder uses instead.
        """
        host = host_fetch(params)
        # explicit copy per slice: a view would pin the whole padded stack
        # in memory for as long as any single machine's params live
        # (ascontiguousarray is a no-op on contiguous slices)
        return [
            jax.tree.map(lambda a: np.asarray(a[i]).copy(), host)
            for i in range(n)
        ]

    @staticmethod
    def pad_fleet_size(n_machines: int, mesh: Optional[Mesh]) -> int:
        if mesh is None:
            return n_machines
        return pad_to_multiple(n_machines, mesh.devices.size)
