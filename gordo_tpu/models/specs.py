"""
Model architecture specs: what a factory returns and the Flax modules
implementing the reference's network shapes.

Where the reference's factories return *compiled Keras models*
(gordo/machine/model/factories/*.py), ours return a :class:`ModelSpec` —
a Flax module plus optimizer/loss config — which the estimator compiles
under ``jax.jit``. Modules return ``(output, activity_penalty)`` so l1
activity regularization (reference: feedforward_autoencoder.py:82) folds
into the jitted loss without Keras-style layer-attached losses.

TPU notes: Dense/LSTM matmuls run through the MXU; ``dtype="bfloat16"``
switches compute (not params) to bf16, the MXU-native format. Params stay
float32 for stable optimizer math.
"""

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from gordo_tpu.ops.activations import resolve_activation

_DTYPES = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float64": jnp.float64,
}


def resolve_dtype(dtype) -> Any:
    if dtype is None:
        return jnp.float32
    if isinstance(dtype, str):
        try:
            return _DTYPES[dtype]
        except KeyError:
            raise ValueError(f"Unknown dtype {dtype!r}") from None
    return dtype


_OPTIMIZERS: Dict[str, Callable[..., optax.GradientTransformation]] = {
    "adam": optax.adam,
    "adamw": optax.adamw,
    "sgd": optax.sgd,
    "rmsprop": optax.rmsprop,
    "adagrad": optax.adagrad,
    "adadelta": optax.adadelta,
    "adamax": optax.adamax,
    "nadam": optax.nadam,
    "lamb": optax.lamb,
    "lion": optax.lion,
}

# Keras optimizer-kwarg spellings -> optax spellings
_OPT_KWARG_ALIASES = {"lr": "learning_rate", "decay": "weight_decay"}


def resolve_optimizer(
    name: str, optimizer_kwargs: Optional[Dict[str, Any]] = None
) -> Tuple[Callable[..., optax.GradientTransformation], Dict[str, Any]]:
    """
    (constructor, normalized kwargs) for a Keras-style optimizer config —
    alias translation (lr -> learning_rate, ...) and the default learning
    rate applied. Shared by make_optimizer and the hyperparameter sweep.
    """
    kwargs = dict(optimizer_kwargs or {})
    for old, new in _OPT_KWARG_ALIASES.items():
        if old in kwargs:
            kwargs[new] = kwargs.pop(old)
    kwargs.setdefault("learning_rate", 1e-3)
    try:
        ctor = _OPTIMIZERS[name.lower()]
    except KeyError:
        raise ValueError(
            f"Unknown optimizer {name!r}; available: {sorted(_OPTIMIZERS)}"
        ) from None
    return ctor, kwargs


def make_optimizer(
    name: str, optimizer_kwargs: Optional[Dict[str, Any]] = None
) -> optax.GradientTransformation:
    """Build an optax optimizer from a Keras-style name + kwargs."""
    ctor, kwargs = resolve_optimizer(name, optimizer_kwargs)
    return ctor(**kwargs)


_LOSSES = {
    "mse": lambda err: err ** 2,
    "mean_squared_error": lambda err: err ** 2,
    "mae": lambda err: jnp.abs(err),
    "mean_absolute_error": lambda err: jnp.abs(err),
    "huber": lambda err: optax.losses.huber_loss(err, jnp.zeros_like(err)),
}


def per_sample_loss(loss: str, y_pred: jnp.ndarray, y_true: jnp.ndarray) -> jnp.ndarray:
    """(batch, features) prediction error -> (batch,) per-sample loss."""
    try:
        elementwise = _LOSSES[loss]
    except KeyError:
        raise ValueError(f"Unknown loss {loss!r}; available: {sorted(_LOSSES)}") from None
    return jnp.mean(elementwise(y_pred - y_true), axis=-1)


def masked_per_sample_loss(
    loss: str,
    y_pred: jnp.ndarray,
    y_true: jnp.ndarray,
    feature_weight: jnp.ndarray,
) -> jnp.ndarray:
    """
    :func:`per_sample_loss` with a {0,1} feature mask: the mean runs
    over the REAL output columns only, so a padded-bucket machine's
    loss (and the gradients, early stopping and quarantine decisions
    derived from it) ignores inert pad columns entirely. Zeroing the
    error before the elementwise loss is exact for every registered
    loss (they all map 0 -> 0), and with an all-ones mask this reduces
    to :func:`per_sample_loss` exactly.
    """
    try:
        elementwise = _LOSSES[loss]
    except KeyError:
        raise ValueError(f"Unknown loss {loss!r}; available: {sorted(_LOSSES)}") from None
    err = (y_pred - y_true) * feature_weight
    n_real = jnp.maximum(jnp.sum(feature_weight), 1.0)
    return jnp.sum(elementwise(err), axis=-1) / n_real


@dataclasses.dataclass
class ModelSpec:
    """What a factory returns: architecture + training configuration."""

    module: nn.Module
    optimizer: str = "Adam"
    optimizer_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    loss: str = "mse"
    # sequence-model window geometry; windowed=False means samples are rows
    windowed: bool = False
    lookback_window: int = 1

    def make_optimizer(self) -> optax.GradientTransformation:
        return make_optimizer(self.optimizer, self.optimizer_kwargs)


class FeedForwardNet(nn.Module):
    """
    Dense encoder/decoder stack (reference shape:
    factories/feedforward_autoencoder.py:16-104). ``l1_flags[i]`` marks layers
    whose *activations* incur an l1 penalty — the reference applies it to all
    encoder layers except the first.
    """

    layer_dims: Tuple[int, ...]
    layer_funcs: Tuple[str, ...]
    l1_flags: Tuple[bool, ...]
    out_dim: int
    out_func: str = "linear"
    l1: float = 1e-4
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        penalty = jnp.asarray(0.0, dtype=jnp.float32)
        for dim, func, l1_flag in zip(self.layer_dims, self.layer_funcs, self.l1_flags):
            x = nn.Dense(dim, dtype=self.dtype)(x)
            x = resolve_activation(func)(x)
            if l1_flag:
                penalty = penalty + self.l1 * jnp.sum(
                    jnp.abs(x.astype(jnp.float32))
                ) / x.shape[0]
        x = nn.Dense(self.out_dim, dtype=self.dtype)(x)
        return resolve_activation(self.out_func)(x).astype(jnp.float32), penalty


def lstm_gates(h, z_t, w_h, b_h, dtype):
    """
    The four gates of one LSTM timestep before their activations, float32
    (batch, 4h) in the order [i, f, g, o], from pre-projected input
    ``z_t``: the recurrent matmul runs in ``dtype`` (MXU).
    """
    return (z_t + h.astype(dtype) @ w_h + b_h).astype(jnp.float32)


def lstm_cell_update(c, gates, act):
    """
    The elementwise half of a timestep, in float32: sigmoid on i, f and o,
    ``act`` on g and on the new cell state. Returns the new (c, h).
    """
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    i, f, o = nn.sigmoid(i), nn.sigmoid(f), nn.sigmoid(o)
    c = f * c + i * act(g)
    h = o * act(c)
    return c, h


def lstm_cell_update_transpose(c, gates, d_c, d_h, act):
    """
    The cotangents of ``c`` and ``gates`` in :func:`lstm_cell_update` from
    those of the new (c, h): autodiff's own, op for op, taken in two parts,
    the gates' activations and what makes (c, h) of them. The activations,
    the residuals of their derivatives, ``c`` and ``d_h`` pass through ONE
    ``optimization_barrier``, so each is made once and read once.
    Transposed whole, the update compiles for a TPU to fusions that each
    make the activations again and each read ``c`` and ``d_h`` again, rows
    the backward loop reads from HBM (docs/performance.md, "The backward
    step's transposed cell update is made once").
    """

    def activations(gates):
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        return nn.sigmoid(i), nn.sigmoid(f), act(g), nn.sigmoid(o)

    def update(c, activations):
        i, f, g, o = activations
        c = f * c + i * g
        return c, o * act(c)

    acts, acts_vjp = jax.vjp(activations, gates)
    acts, acts_vjp, c, d_h = jax.lax.optimization_barrier((acts, acts_vjp, c, d_h))
    _, update_vjp = jax.vjp(update, c, acts)
    d_c, d_acts = update_vjp((d_c, d_h))
    (d_gates,) = acts_vjp(d_acts)
    return d_c, d_gates


def lstm_cell_step(c, h, z_t, w_h, b_h, act, dtype):
    """
    One LSTM timestep from pre-projected input ``z_t`` (gate order
    [i, f, g, o], sigmoid gates, ``act`` on g and the cell output):
    matmul in ``dtype`` (MXU); gate math + cell state in float32, matching
    OptimizedLSTMCell's float32 (param_dtype) carry. The reference that
    :func:`lstm_time_scan` is held to: a ``jax.lax.scan`` of this step,
    its backward pass left to autodiff, computes the same numbers.
    """
    return lstm_cell_update(c, lstm_gates(h, z_t, w_h, b_h, dtype), act)


@functools.lru_cache(maxsize=None)
def _step_writer(n_stacked):
    """
    ``write(buffer, rows, start)``: ``rows`` (batch, width) as one step's
    rows of a row-flat stacked buffer (time*batch, width), from row
    ``start`` on; ``n_stacked`` axes stand in front of the row axis. It is
    its own rule under ``vmap``: one axis more in front, the same
    ``dynamic_update_slice`` on the row axis. JAX's own batching rule
    turns the update into a scatter, which on the chip reads the old rows
    and selects against them before it writes the new ones.
    """

    @jax.custom_batching.custom_vmap
    def write(buffer, rows, start):
        return jax.lax.dynamic_update_slice_in_dim(buffer, rows, start, n_stacked)

    @write.def_vmap
    def write_stacked(axis_size, in_batched, buffer, rows, start):
        if in_batched[2]:
            raise NotImplementedError("a time scan's step index is one for the stack")
        buffer, rows = (
            x if batched else jnp.broadcast_to(x, (axis_size, *x.shape))
            for x, batched in zip((buffer, rows), in_batched)
        )
        return _step_writer(n_stacked + 1)(buffer, rows, start), True

    return write


def _write_step(buffer, rows, t):
    return _step_writer(0)(buffer, rows, t * rows.shape[0])


def _read_step(buffer, t, batch):
    return jax.lax.dynamic_slice_in_dim(buffer, t * batch, batch, 0)


def _lstm_forward(act, dtype, unroll, keep_residuals, x, w_x, w_h, b_h):
    """
    The recurrence over time-major ``x`` (time, batch, f): a counted loop
    that multiplies step t's rows of ``x`` by the input kernel ``w_x``
    itself, beside the recurrent product of the same rows and columns, and
    writes step t's rows of each stacked buffer and nothing else of it.
    The buffers start uninitialised (``jax.lax.empty``: on a TPU an
    ``AllocateBuffer``, no fill), and every row is written before anything
    reads it. Returns the stacked hidden states and, with
    ``keep_residuals``, the cell states, which the backward loop reads
    besides. The gates are NOT stacked: the backward loop makes them again
    from the stacked ``x`` and hidden states, which it reads anyway
    (docs/performance.md, "The backward loop makes a step's gates again").
    Nor is the projected input ``x @ w_x``: it would be the widest buffer
    the forward pass moves, written whole and read back a step at a time
    with nothing else done to it ("The forward loop multiplies a step's
    input itself").

    Every stacked buffer, ``x`` among them, is ROW-FLAT, (time*batch,
    width) with step t at rows ``t*batch`` on, and has no time axis. XLA
    lays a buffer with the axis its loop indexes outermost, so under the
    fleet's ``vmap`` a (time, batch, width) buffer comes out time-major,
    machines inside, and is copied whole to turn it machine-major for
    whatever takes all its rows at once (PERF.md section 6, PR 33). A
    loop that indexes rows leaves the machines in front, and the reshapes
    at :func:`lstm_time_scan`'s edge compile to nothing.
    """
    n_steps, batch, h_dim = x.shape[0], x.shape[1], w_h.shape[0]
    x = x.reshape(n_steps * batch, -1)
    state = jnp.zeros((batch, h_dim), jnp.float32)
    stacked = [
        jax.lax.empty((n_steps * batch, h_dim), jnp.float32)
        for _ in range(2 if keep_residuals else 1)
    ]

    def body(t, carry):
        c, h, stacked = carry
        # the two products stay apart, each rounding its own operands, and
        # are summed in float32: [x_t, h] @ [w_x; w_h] would change the sums
        with jax.named_scope("lstm.fwd.gates"):
            gates = lstm_gates(h, _read_step(x, t, batch) @ w_x, w_h, b_h, dtype)
        with jax.named_scope("lstm.fwd.cell"):
            c, h = lstm_cell_update(c, gates, act)
        with jax.named_scope("lstm.fwd.cell"):
            written = [_write_step(b, row, t) for b, row in zip(stacked, [h, c])]
        return c, h, written

    _, _, stacked = jax.lax.fori_loop(
        0, n_steps, body, (state, state, stacked), unroll=unroll
    )
    return stacked


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def lstm_time_scan(act, dtype, unroll, x, w_x, w_h, b_h):
    """
    The hidden states (time, batch, h), float32, of one LSTM layer over its
    time-major input ``x`` (time, batch, f) and input kernel ``w_x`` (f,
    4h), both in ``dtype``: step for step :func:`lstm_cell_step` of ``x_t @
    w_x`` from a zero state.

    Why not ``jax.lax.scan`` under autodiff: ``scan`` starts every stacked
    output as a broadcast zero (jax 0.9.0, ``loops.py`` ``_empty_array``),
    autodiff makes 11-12 (time, batch, h) residuals a layer such outputs,
    and on the chip the fleet's step program wrote all of them whole every
    step before the scans overwrote them row by row: 6.5 GB a step in the
    50-tag plant (PERF.md section 6, PR 30). Here forward and backward are
    each one counted loop over buffers that are never filled, and what is
    kept from the forward pass is chosen: the cell states and the output
    itself. The time axis exists at this function's edge only: inside,
    every stacked buffer is (time*batch, width) rows (:func:`_lstm_forward`
    says why), so nothing is turned between one layer's loop and the
    next's. A new recurrent layer uses this shape of scan, not
    ``lax.scan`` under autodiff.
    """
    (hs,) = _lstm_forward(act, dtype, unroll, False, x, w_x, w_h, b_h)
    return hs.reshape(*x.shape[:2], -1)


def _lstm_time_scan_fwd(act, dtype, unroll, x, w_x, w_h, b_h):
    hs, cs = _lstm_forward(act, dtype, unroll, True, x, w_x, w_h, b_h)
    return hs.reshape(*x.shape[:2], -1), (hs, cs, x, w_x, w_h, b_h)


def _lstm_time_scan_bwd(act, dtype, unroll, residuals, d_hs):
    """
    The transposed recurrence, last step first. A step's gates are made
    again from its rows of ``x`` and the previous hidden state, the
    forward's own expression of the same operands. Kept, they were 4h wide,
    float32, written once and read once: at the widths of ``lstm50.fit``
    the forward's two products cost less than those bytes
    (docs/performance.md, "The backward loop makes a step's gates again").
    A step's elementwise half is transposed by
    :func:`lstm_cell_update_transpose` from the gates, the previous cell
    state and ``d_h`` with the step's rows of ``d_hs``: autodiff's own ops,
    each activation made once and each of those two rows read once. Its
    ``d_gates`` (batch, 4h) is the cotangent of both gate products, and the
    loop makes all four transposes of them while it is on the chip, each
    with the dimension numbers autodiff writes: against ``w_h`` into the
    previous hidden state, against the previous hidden state into ``d_w``,
    against ``w_x`` into step t's rows of the stacked ``d_x``, against step
    t's rows of ``x`` into ``d_w_x``; the weight gradients and the bias's
    sum are accumulated in the carry. ``d_gates`` is NOT stacked: as
    ``d_z``, the cotangent of ``x @ w_x``, it was 4h wide where ``d_x`` is
    f wide, written a step at a time and read whole twice by two products
    after the loop that waited for its bytes (docs/performance.md, "The
    backward loop multiplies a step's gate cotangent itself"). The
    residuals, ``d_hs`` and ``d_x`` are row-flat, as the forward loop's
    buffers are.
    """
    hs, cs, x, w_x, w_h, b_h = residuals
    n_steps, batch, h_dim = d_hs.shape
    d_hs = d_hs.reshape(n_steps * batch, h_dim)
    x = x.reshape(n_steps * batch, -1)
    zeros = jnp.zeros((batch, h_dim), jnp.float32)

    def previous(buffer, t):
        # step 0 started from the zero state
        rows = _read_step(buffer, jnp.maximum(t - 1, 0), batch)
        return jnp.where(t > 0, rows, 0.0)

    def body(k, carry):
        d_c, d_h, d_x, d_w_x, d_w, d_b = carry
        # what gives the step its gates: the forward's two products again
        with jax.named_scope("lstm.bwd.read"):
            t = n_steps - 1 - k
            step_gates = lstm_gates(
                previous(hs, t), _read_step(x, t, batch) @ w_x, w_h, b_h, dtype
            )
        with jax.named_scope("lstm.bwd.cell"):
            d_c, d_gates = lstm_cell_update_transpose(
                previous(cs, t), step_gates, d_c, d_h + _read_step(d_hs, t, batch), act
            )
            d_gates = d_gates.astype(dtype)
        with jax.named_scope("lstm.bwd.products"):
            d_w_x = d_w_x + jax.lax.dot_general(
                d_gates, _read_step(x, t, batch), (((0,), (0,)), ((), ()))
            ).T
            d_w = d_w + jax.lax.dot_general(
                d_gates, previous(hs, t).astype(dtype), (((0,), (0,)), ((), ()))
            ).T
            d_b = d_b + jax.lax.reduce_sum(d_gates, axes=(0,))
            d_x_t = jax.lax.dot_general(d_gates, w_x, (((1,), (1,)), ((), ())))
            d_h = jax.lax.dot_general(
                d_gates, w_h, (((1,), (1,)), ((), ()))
            ).astype(jnp.float32)
            d_x = _write_step(d_x, d_x_t, t)
        return d_c, d_h, d_x, d_w_x, d_w, d_b

    carry = (
        zeros,
        zeros,
        jax.lax.empty(x.shape, dtype),
        jnp.zeros_like(w_x),
        jnp.zeros_like(w_h),
        jnp.zeros((4 * h_dim,), dtype),
    )
    _, _, d_x, d_w_x, d_w, d_b = jax.lax.fori_loop(
        0, n_steps, body, carry, unroll=unroll
    )
    return d_x.reshape(n_steps, batch, -1), d_w_x, d_w, d_b


lstm_time_scan.defvjp(_lstm_time_scan_fwd, _lstm_time_scan_bwd)


def gru_cell_step(h, z_t, w_rz, w_n, b_n, act, dtype, h_dim):
    """
    One GRU timestep from pre-projected input ``z_t`` (r/z sigmoid gates,
    ``act`` on the candidate, reset gate applied to the PROJECTED hidden
    state, ``h' = (1-z)*n + z*h`` — GRUCell's convention); float32 gate
    math like lstm_cell_step. The step of :class:`FusedGRULayer`'s scan.
    """
    hd = h.astype(dtype)
    rz = (z_t[..., : 2 * h_dim] + hd @ w_rz).astype(jnp.float32)
    r, zg = jnp.split(nn.sigmoid(rz), 2, axis=-1)
    hn = (hd @ w_n).astype(jnp.float32) + b_n
    n = act(z_t[..., 2 * h_dim :].astype(jnp.float32) + r * hn)
    return (1.0 - zg) * n + zg * h


class _InputKernel(nn.Module):
    """
    The ``kernel`` of a bias-free ``nn.Dense`` without its product: under
    the name ``input_proj`` the parameter tree, the initializer and the
    key are those of the ``nn.Dense`` that :class:`FusedLSTMLayer` held
    while its projection was hoisted, so stored models load as they were.
    """

    features: int

    @nn.compact
    def __call__(self, in_features):
        return self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (in_features, self.features),
            jnp.float32,
        )


class FusedLSTMLayer(nn.Module):
    """
    LSTM layer as one time scan, :func:`lstm_time_scan`: forward and
    backward written out as counted loops over buffers that are allocated
    and never filled; the layer has no other scan. Same math as
    ``nn.RNN(OptimizedLSTMCell)`` (gate order [i, f, g, o], sigmoid gates,
    ``activation_fn`` on g and the cell output) with the four gates'
    kernels side by side: ``input_proj/kernel`` (f, 4h), bias-free (the
    recurrent bias covers it), and ``recurrent_kernel`` (h, 4h).

    The forward loop multiplies a step's rows of ``x`` by the input kernel
    itself, beside ``h @ w_h``. Hoisted out of the scan as one (time*batch,
    f) x (f, 4h) product, which is what XLA:CPU's gemm wants, the product
    is bound on a TPU by the write of its own output, which the loop then
    reads back a step at a time (docs/performance.md, "The forward loop
    multiplies a step's input itself", has the chip's numbers). The
    hoisting remains in :class:`FusedGRULayer` only. The backward loop
    likewise multiplies a step's gate cotangent by the input kernel and by
    the step's rows of ``x`` itself, beside the recurrent kernel's two
    transposes: what it stacks is ``d_x``, f wide, not the 4h-wide
    cotangent of ``x @ w_x`` for two products over all rows to read back
    ("The backward loop multiplies a step's gate cotangent itself"). And
    it makes a step's gates again from ``x_t`` and the previous ``h``,
    both of which it reads anyway, so the forward loop under a gradient
    stacks ``h`` and ``c`` and not the 4h-wide float32 gates, whose write
    and read cost more HBM time than the two products take on the MXU at
    the widths of ``lstm50.fit`` ("The backward loop makes a step's gates
    again").
    """

    features: int
    activation_fn: Any = jnp.tanh
    dtype: Any = jnp.float32
    # time-scan unroll factor (of the forward and of the backward loop):
    # XLA fuses gate math across consecutive steps, shrinking per-step
    # carry copies (the dominant non-matmul cost in the CPU fallback's
    # trace) and loop overhead; a pure schedule knob — the math is
    # step-for-step identical
    unroll: int = 1
    # time_major=True: x is (time, batch, f) and the output sequence comes
    # back (time, batch, h). Its rows, x.reshape(-1, f), are then in the
    # order of the scan's row-flat buffers (step t at rows t*batch on), so
    # the loops read and write those buffers as they lie and a stacked
    # time-major net turns nothing between one layer's loop and the
    # next's, whole or per layer (_lstm_forward; the round-4
    # CPU trace showed such copies out-costing the matmuls,
    # docs/performance.md). Param shapes are identical either way;
    # batch-major (default) keeps the original contract and pays a
    # swapaxes in and one out.
    time_major: bool = False

    @nn.compact
    def __call__(self, x):  # x: (batch, time, f) or time-major (time, batch, f)
        h_dim = self.features
        w_x = _InputKernel(4 * h_dim, name="input_proj")(x.shape[-1]).astype(self.dtype)
        w_h = self.param(
            "recurrent_kernel",
            nn.initializers.orthogonal(),
            (h_dim, 4 * h_dim),
            jnp.float32,
        ).astype(self.dtype)
        b_h = self.param(
            "recurrent_bias", nn.initializers.zeros_init(), (4 * h_dim,), jnp.float32
        ).astype(self.dtype)
        x = x.astype(self.dtype)
        # a stable name for "the time scan of this layer" on a device trace
        # (.../FusedLSTMLayer_k/scan/..., under transpose(jvp(...)) for the
        # backward pass), with the layout swaps that feed and drain it
        with jax.named_scope("scan"):
            hs = lstm_time_scan(
                self.activation_fn,
                self.dtype,
                max(1, int(self.unroll)),
                x if self.time_major else x.swapaxes(0, 1),
                w_x,
                w_h,
                b_h,
            )
            hs = hs if self.time_major else hs.swapaxes(0, 1)
        return hs.astype(self.dtype)


class FusedGRULayer(nn.Module):
    """
    GRU layer with the input projections hoisted OUT of the time scan:
    the x@W_[rzn] matmuls for the whole sequence run as one
    (batch*time, f) x (f, 3h) product (MXU-sized), and the scan carries
    only the recurrent h-projections. Same math as
    ``nn.RNN(GRUCell)`` — r/z sigmoid gates, ``activation_fn`` on the
    candidate, reset gate applied to the PROJECTED hidden state
    (``n = act(x_n + r * (h@W_hn + b_hn))``), ``h' = (1-z)*n + z*h`` —
    with the TPU-friendlier schedule of FusedLSTMLayer.
    """

    features: int
    activation_fn: Any = jnp.tanh
    dtype: Any = jnp.float32
    unroll: int = 1  # see FusedLSTMLayer.unroll
    time_major: bool = False  # see FusedLSTMLayer.time_major

    @nn.compact
    def __call__(self, x):  # x: (batch, time, f) or time-major (time, batch, f)
        h_dim = self.features
        # one big matmul over the full sequence; carries the input-side
        # biases for r/z/n (the recurrent r/z projections are bias-free,
        # as in GRUCell's summed-dense convention). The explicit 2D reshape
        # matters: a 3D dot_general's backward makes XLA:CPU materialize
        # 67MB transposes of the sequence to feed its gemm, while the 2D
        # form's dW = x^T @ dz lowers to a gemm with transpose flags (no
        # copies), measured in the round-5 HLO dump.
        lead = x.shape[:-1]
        z = nn.Dense(
            3 * h_dim, use_bias=True, dtype=self.dtype, name="input_proj"
        )(x.reshape(-1, x.shape[-1]))
        z = z.reshape(*lead, 3 * h_dim)
        w_rz = self.param(
            "recurrent_kernel_rz",
            nn.initializers.orthogonal(),
            (h_dim, 2 * h_dim),
            jnp.float32,
        ).astype(self.dtype)
        w_n = self.param(
            "recurrent_kernel_n",
            nn.initializers.orthogonal(),
            (h_dim, h_dim),
            jnp.float32,
        ).astype(self.dtype)
        b_n = self.param(
            "recurrent_bias_n", nn.initializers.zeros_init(), (h_dim,), jnp.float32
        )
        act = self.activation_fn

        def step(h, z_t):
            h = gru_cell_step(h, z_t, w_rz, w_n, b_n, act, self.dtype, h_dim)
            return h, h

        batch = x.shape[1] if self.time_major else x.shape[0]
        h0 = jnp.zeros((batch, h_dim), dtype=jnp.float32)
        with jax.named_scope("scan"):  # see FusedLSTMLayer
            _, hs = jax.lax.scan(
                step,
                h0,
                z if self.time_major else z.swapaxes(0, 1),
                unroll=max(1, int(self.unroll)),
            )
            hs = hs if self.time_major else hs.swapaxes(0, 1)
        return hs.astype(self.dtype)


class LSTMNet(nn.Module):
    """
    Stacked LSTM -> Dense head (reference shape:
    factories/lstm_autoencoder.py:17-103): every LSTM layer emits its full
    sequence to the next; the Dense head reads the final layer's last
    timestep — identical math to Keras' return_sequences=False on the last
    recurrent layer. ``fused=True`` swaps each layer for the cell's fused
    variant (FusedLSTMLayer / FusedGRULayer: the gates' kernels side by
    side and one scan a layer; different param tree, so choose it at
    model definition time).
    """

    layer_dims: Tuple[int, ...]
    layer_funcs: Tuple[str, ...]
    out_dim: int
    out_func: str = "linear"
    fused: bool = False
    cell: str = "lstm"  # "lstm" | "gru"
    time_unroll: int = 1  # fused layers' scan unroll (schedule-only knob)
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic: bool = True):  # x: (batch, time, features)
        if self.cell not in ("lstm", "gru"):
            raise ValueError(f"Unknown recurrent cell {self.cell!r}")
        if self.fused:
            # time-major through the whole stack: ONE transpose on entry,
            # none between layers, and none on exit (the head reads the
            # last timestep, hs[-1]). The round-4 CPU trace showed the
            # per-layer swapaxes copies out-costing the gate matmuls
            # (docs/performance.md); param shapes are layout-independent.
            x = x.swapaxes(0, 1)  # (time, batch, features)
            fused_layer = FusedGRULayer if self.cell == "gru" else FusedLSTMLayer
            for dim, func in zip(self.layer_dims, self.layer_funcs):
                x = fused_layer(
                    dim,
                    activation_fn=resolve_activation(func),
                    unroll=self.time_unroll,
                    time_major=True,
                    dtype=self.dtype,
                )(x)
            x = x[-1]  # last timestep: (batch, h)
        else:
            for dim, func in zip(self.layer_dims, self.layer_funcs):
                if self.cell == "gru":
                    cell = nn.GRUCell(
                        dim,
                        activation_fn=resolve_activation(func),
                        dtype=self.dtype,
                    )
                else:
                    cell = nn.OptimizedLSTMCell(
                        dim,
                        activation_fn=resolve_activation(func),
                        dtype=self.dtype,
                    )
                x = nn.RNN(cell)(x)
            x = x[:, -1, :]
        x = nn.Dense(self.out_dim, dtype=self.dtype)(x)
        return resolve_activation(self.out_func)(x).astype(jnp.float32), jnp.asarray(
            0.0, dtype=jnp.float32
        )


class SequentialNet(nn.Module):
    """
    Generic layer stack built from a raw layer-spec list — backing for
    RawModelRegressor (reference: models.py:332-388). Each entry:
    ``("dense", {units, activation})``, ``("lstm", {units, activation})``,
    ``("dropout", {rate})`` or ``("activation", {activation})``.
    """

    layers: Tuple[Tuple[str, Tuple[Tuple[str, Any], ...]], ...]
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        seen_recurrent = False
        for kind, frozen_kwargs in self.layers:
            kwargs = dict(frozen_kwargs)
            if kind == "dense":
                if x.ndim == 3 and not seen_recurrent:
                    pass  # dense over last axis of sequences is fine
                x = nn.Dense(int(kwargs["units"]), dtype=self.dtype)(x)
                x = resolve_activation(kwargs.get("activation", "linear"))(x)
            elif kind == "lstm":
                seen_recurrent = True
                cell = nn.OptimizedLSTMCell(
                    int(kwargs["units"]),
                    activation_fn=resolve_activation(kwargs.get("activation", "tanh")),
                    dtype=self.dtype,
                )
                x = nn.RNN(cell)(x)
                if not kwargs.get("return_sequences", False):
                    x = x[:, -1, :]
            elif kind == "dropout":
                x = nn.Dropout(rate=float(kwargs.get("rate", 0.5)))(
                    x, deterministic=deterministic
                )
            elif kind == "activation":
                x = resolve_activation(kwargs.get("activation", "linear"))(x)
            elif kind == "flatten":
                x = x.reshape((x.shape[0], -1))
            else:
                raise ValueError(f"Unknown raw layer type {kind!r}")
        return x.astype(jnp.float32), jnp.asarray(0.0, dtype=jnp.float32)
