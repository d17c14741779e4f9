"""
A feedforward fleet's epoch of optimizer steps, run by ONE Pallas TPU kernel.

:func:`epoch_steps` gives the trainer, once an epoch, every machine's
``n_batches`` Adam steps over the batches that ``row_permute.epoch_batches``
fetched. Under ``jax.vmap`` over a fleet the whole fleet goes through one
kernel call whose grid is ``(machine blocks, steps)``, the steps inner:

* A block's parameters and both Adam moments are output blocks whose index
  depends on the block alone: read in at the block's first step, held in
  vector memory through all of its steps, written back once an epoch.
* A step's input and target slabs, ``(n_batches, M, f, batch)`` as the
  fetch kernel writes them, come in by the pipeline's double-buffered
  DMAs, the next step's beside this one's work.
* Per machine and step, in a counted loop over the block's machines: the
  ``FeedForwardNet`` forward pass, the weighted loss, the l1 activity
  penalty, the backward pass (autodiff's, through :func:`jax.vjp`), Adam's
  update as ``optax.adam`` makes it, and the gate that makes a step with no
  real sample a no-op. Nothing a step makes leaves vector memory but the
  step's loss sum and weight sum.

The activations lie with the features on sublanes and the batch on lanes,
the slabs' own layout: a layer is ``W^T @ h`` of the ``(in, out)`` kernel,
held transposed, and the ``(in, batch)`` activation. Every product takes its
operands in the precision a default-precision float32 product takes on the
backend (bfloat16 on a TPU, float32 in the interpreter on a CPU), accumulates
in float32, and multiplies one machine's operands alone: no two machines
share a product, so a machine gone non-finite cannot reach another.

On the CPU backend (tests, rehearsals) the kernel runs in interpret mode; on
``tpu`` it compiles through Mosaic; any other backend is an error (the rule
of ``ops/flash_attention.py``).
"""

import functools
import numbers
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gordo_tpu.models.specs import _LOSSES, FeedForwardNet, resolve_optimizer
from gordo_tpu.ops.activations import resolve_activation

from .flash_attention import _LANES, _interpret_for_backend, _round_up

#: activations and losses the kernel's body is known to lower; a spec with
#: any other takes the trainer's scan
ACTIVATIONS = ("linear", "tanh", "relu", "sigmoid")
LOSSES = ("mse", "mean_squared_error", "mae", "mean_absolute_error")
#: Adam's arguments the kernel reproduces (numbers, not schedules)
_ADAM_KWARGS = ("learning_rate", "b1", "b2", "eps", "eps_root")
#: machines a block holds come in multiples of a sublane tile: the step
#: weights' block is ``(machines, batch)``
_SUBLANES = 8
#: what one kernel call may ask of the chip's vector memory (a v5e core has
#: 128 MiB), and room for the compiler's own
_VMEM_BUDGET_BYTES = 96 * 1024 * 1024
_VMEM_HEADROOM_BYTES = 8 * 1024 * 1024
#: a block's step losses and weight sums, and its bias corrections, in
#: scalar memory (a v5e core has 1 MiB)
_SMEM_BUDGET_BYTES = 512 * 1024


class DenseStack(NamedTuple):
    """What the kernel needs of a spec: the layers, the loss and Adam's
    arguments, all static."""

    widths: Tuple[int, ...]  # input width, then each layer's output width
    funcs: Tuple[str, ...]  # each layer's activation, the output layer last
    l1_flags: Tuple[bool, ...]  # per hidden layer
    l1: float
    loss: str
    learning_rate: float
    b1: float
    b2: float
    eps: float
    eps_root: float


def plan(spec, n_features: int) -> Optional[DenseStack]:
    """
    The kernel's view of ``spec`` for inputs of ``n_features`` tags, or
    ``None`` where the kernel does not serve it: a ``FeedForwardNet`` in
    float32 (it has no dropout), activations in :data:`ACTIVATIONS`, a loss
    in :data:`LOSSES`, Adam with constant numeric arguments, and the default
    matrix precision: the kernel's products take bfloat16 operands on a TPU,
    which is what a default-precision float32 product is there and less than
    ``jax.default_matmul_precision`` asks for where it is set.
    """
    if jax.config.jax_default_matmul_precision is not None:
        return None
    module = spec.module
    if type(module) is not FeedForwardNet or spec.windowed:
        return None
    if jnp.dtype(module.dtype) != jnp.float32:
        return None
    funcs = tuple(module.layer_funcs) + (module.out_func,)
    if not all(isinstance(f, str) and f in ACTIVATIONS for f in funcs):
        return None
    if spec.loss not in LOSSES:
        return None
    ctor, kwargs = resolve_optimizer(spec.optimizer, spec.optimizer_kwargs)
    if ctor is not optax.adam or set(kwargs) - set(_ADAM_KWARGS):
        return None
    if not all(isinstance(v, numbers.Real) for v in kwargs.values()):
        return None
    adam = {"b1": 0.9, "b2": 0.999, "eps": 1e-8, "eps_root": 0.0, **kwargs}
    return DenseStack(
        widths=(int(n_features),) + tuple(int(d) for d in module.layer_dims)
        + (int(module.out_dim),),
        funcs=funcs,
        l1_flags=tuple(bool(f) for f in module.l1_flags),
        l1=float(module.l1),
        loss=spec.loss,
        **{k: float(v) for k, v in adam.items()},
    )


def _tile_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """Bytes of a ``(rows, cols)`` array in vector memory's tiles."""
    sublanes = 8 * 4 // itemsize
    return _round_up(rows, sublanes) * _round_up(cols, _LANES) * itemsize


def vmem_bytes(
    stack: DenseStack, block: int, batch: int, n_batches: int, x_itemsize: int = 4
) -> int:
    """
    VMEM a call's buffers take for ``block`` machines: the parameters and
    both moments as input and output blocks, each double-buffered by the
    pipeline (a machine's transposed ``(out, in)`` kernels and ``(1, out)``
    biases); a step's input and target slabs and weights, double-buffered;
    and one machine's activations, cotangents and the products' residuals
    at a time.
    """
    widths = stack.widths
    per_machine_state = sum(
        _tile_bytes(o, i) + _tile_bytes(1, o) for i, o in zip(widths[:-1], widths[1:])
    )
    state = 3 * 2 * 2 * block * per_machine_state
    slabs = 2 * block * (
        _tile_bytes(widths[0], batch, x_itemsize) + _tile_bytes(widths[-1], batch)
    )
    weights = 2 * _tile_bytes(block, batch)
    # a layer's output, activation, cotangent and the product's operands
    working = 6 * sum(_tile_bytes(w, batch) for w in widths) + 4 * per_machine_state
    return state + slabs + weights + working


def smem_bytes(block: int, n_batches: int) -> int:
    """Scalar memory a block's bias corrections, loss sums and weight sums
    take, double-buffered."""
    return 2 * 4 * block * _round_up(4 * n_batches, _LANES)


def block_size(
    stack: DenseStack, n_machines: int, batch: int, n_batches: int, x_itemsize: int = 4
) -> int:
    """
    Machines a grid step holds: the most, in whole sublane tiles and no
    more than the fleet fills, whose buffers fit the kernel's share of
    vector and scalar memory (both grow by the same bytes with each tile of
    8 machines); a fleet of fewer than 8 machines is one block. 0 where
    even the least block does not fit.
    """
    vmem_room = _VMEM_BUDGET_BYTES - _VMEM_HEADROOM_BYTES

    def fits(block):
        return (
            vmem_bytes(stack, block, batch, n_batches, x_itemsize) <= vmem_room
            and smem_bytes(block, n_batches) <= _SMEM_BUDGET_BYTES
        )

    if n_machines < _SUBLANES:
        return n_machines if fits(n_machines) else 0
    fixed = vmem_bytes(stack, 0, batch, n_batches, x_itemsize)
    per_tile = vmem_bytes(stack, _SUBLANES, batch, n_batches, x_itemsize) - fixed
    tiles = min(
        _round_up(n_machines, _SUBLANES) // _SUBLANES,
        max(0, vmem_room - fixed) // per_tile,
        _SMEM_BUDGET_BYTES // smem_bytes(_SUBLANES, n_batches),
    )
    return tiles * _SUBLANES


def serves(
    stack: DenseStack, n_machines: int, batch: int, n_batches: int, x_itemsize: int
) -> bool:
    """
    Whether the kernel takes a fleet's epoch, its input slab
    ``x_itemsize`` bytes an element: batches of whole 128-row tiles, which
    the fetch kernel writes as the step kernel reads them (a narrower batch
    would reach it copied into slabs padded to 128 lanes: at 600 machines
    and batches of 32 that runs the chip out of HBM, compile, described
    v5e), and a block of machines that fits (:func:`block_size`).
    """
    return batch % _LANES == 0 and block_size(
        stack, n_machines, batch, n_batches, x_itemsize
    ) > 0


def _product(operand_dtype):
    """``W^T @ h`` of a transposed ``(out, in)`` kernel and an ``(in,
    batch)`` activation, and its derivative as XLA's autodiff makes it:
    each of the three products takes its operands rounded to
    ``operand_dtype`` and accumulates in float32. Where the activation is
    data, no cotangent of it is made (``data_input``)."""

    def dot(a, b, contract):
        return jax.lax.dot_general(
            a.astype(operand_dtype), b.astype(operand_dtype),
            (contract, ((), ())), preferred_element_type=jnp.float32,
        )

    def make(data_input):
        @jax.custom_vjp
        def product(w, h):
            return dot(w, h, ((1,), (0,)))

        def forward(w, h):
            return product(w, h), (w, h)

        def backward(residuals, ct):
            w, h = residuals
            d_w = dot(ct, h, ((1,), (1,)))
            d_h = None if data_input else dot(w, ct, ((0,), (0,)))
            return d_w, d_h

        product.defvjp(forward, backward)
        return product

    return make(True), make(False)


def _objective(stack: DenseStack, operand_dtype) -> Callable:
    """
    ``objective(layers, x, y, w, fm) -> (loss, loss_sum)`` of one machine's
    batch in the slabs' layout (``x`` ``(f, batch)``, ``y`` ``(f_out,
    batch)``, ``w`` ``(1, batch)``; ``fm`` the ``(f_out, 1)`` column mask or
    None; ``layers`` ``(kernel^T (out, in), bias (1, out))`` pairs): the
    trainer's ``loss_fn`` over ``FeedForwardNet``, op for op, with the
    features on sublanes. Values stay ``(1, 1)``, never rank 0.
    """
    first_product, product = _product(operand_dtype)
    elementwise = _LOSSES[stack.loss]
    acts = [resolve_activation(f) for f in stack.funcs]
    n_hidden = len(stack.l1_flags)

    def objective(layers, x, y, w, fm):
        batch = x.shape[1]
        penalty = jnp.zeros((1, 1), jnp.float32)
        h = x
        for k, ((kernel, bias), act) in enumerate(zip(layers, acts)):
            h = act((first_product if k == 0 else product)(kernel, h) + bias.T)
            if k < n_hidden and stack.l1_flags[k]:
                penalty = penalty + stack.l1 * jnp.sum(
                    jnp.abs(h), keepdims=True
                ) / batch
        err = h - y
        if fm is None:
            per = jnp.mean(elementwise(err), axis=0, keepdims=True)
        else:
            n_real = jnp.maximum(jnp.sum(fm, keepdims=True), 1.0)
            per = jnp.sum(elementwise(err * fm), axis=0, keepdims=True) / n_real
        total_w = jnp.maximum(jnp.sum(w, keepdims=True), 1.0)
        loss_sum = jnp.sum(per * w, keepdims=True)
        return loss_sum / total_w + penalty, loss_sum

    return objective


def _adam(stack: DenseStack, g, mu, nu, bc1, bc2):
    """``optax.adam``'s update of one leaf, op for op (``scale_by_adam``
    then ``scale_by_learning_rate``), its bias corrections ``1 - b**count``
    given."""
    b1, b2 = stack.b1, stack.b2
    mu = (1 - b1) * g + b1 * mu
    nu = (1 - b2) * (g ** 2) + b2 * nu
    update = (mu / bc1) / (jnp.sqrt(nu / bc2 + stack.eps_root) + stack.eps)
    return (-1 * stack.learning_rate) * update, mu, nu


def _steps_kernel(
    corr_ref, x_ref, y_ref, w_ref, *refs,
    stack, n_machines, block, n_layers, masked, operand_dtype,
):
    fm_ref = refs[0] if masked else None
    refs = refs[int(masked):]
    n_state = 3 * 2 * n_layers
    state_in, state, (loss_ref, wsum_ref) = (
        refs[:n_state], refs[n_state:2 * n_state], refs[2 * n_state:]
    )
    b, s = pl.program_id(0), pl.program_id(1)
    objective = _objective(stack, operand_dtype)

    @pl.when(s == 0)
    def _():
        for src, dst in zip(state_in, state):
            dst[...] = src[...]

    # (params, mu, nu), each (kernel, bias) per layer
    def leaves(group):
        group_refs = state[group * 2 * n_layers:(group + 1) * 2 * n_layers]
        return [group_refs[2 * k:2 * k + 2] for k in range(n_layers)]

    params_refs, mu_refs, nu_refs = leaves(0), leaves(1), leaves(2)

    def read(pairs, m):
        return [(kr[m], br[m]) for kr, br in pairs]

    def write(pairs, m, values):
        for (kr, br), (kv, bv) in zip(pairs, values):
            kr[m] = kv
            br[m] = bv

    def machine(m):
        layers = read(params_refs, m)
        x, y = x_ref[m], y_ref[m]
        w = w_ref[pl.ds(m, 1), :]
        fm = fm_ref[m].T if masked else None
        _, pull, loss_sum = jax.vjp(
            lambda p: objective(p, x, y, w, fm), layers, has_aux=True
        )
        (grads,) = pull(jnp.ones((1, 1), jnp.float32))
        total = jnp.sum(w, keepdims=True)
        has_real = total > 0
        bc1 = corr_ref[m, 2 * s]
        bc2 = corr_ref[m, 2 * s + 1]
        new_p, new_mu, new_nu = [], [], []
        for pair, g_pair, mu_pair, nu_pair in zip(
            layers, grads, read(mu_refs, m), read(nu_refs, m)
        ):
            out = [
                _adam(stack, g, mu, nu, bc1, bc2)
                for g, mu, nu in zip(g_pair, mu_pair, nu_pair)
            ]
            # a step with no real sample leaves the machine as it was
            new_p.append(tuple(
                jnp.where(has_real, p + u, p) for p, (u, _, _) in zip(pair, out)
            ))
            new_mu.append(tuple(
                jnp.where(has_real, mu, old) for old, (_, mu, _) in zip(mu_pair, out)
            ))
            new_nu.append(tuple(
                jnp.where(has_real, nu, old) for old, (_, _, nu) in zip(nu_pair, out)
            ))
        write(params_refs, m, new_p)
        write(mu_refs, m, new_mu)
        write(nu_refs, m, new_nu)
        loss_ref[m, s] = jnp.sum(loss_sum)
        wsum_ref[m, s] = jnp.sum(total)

    def machines(m, carry):
        machine(m)
        return carry

    # the block's machines that are in the fleet: a counted loop, never
    # written out (what is written out is traced and lowered again by every
    # process that builds the program)
    jax.lax.fori_loop(0, jnp.minimum(block, n_machines - b * block), machines, 0)


def _layers(tree, n_layers):
    """``[(kernel, bias), ...]`` of a FeedForwardNet's parameter tree."""
    inner = tree["params"]
    return [
        (inner[f"Dense_{k}"]["kernel"], inner[f"Dense_{k}"]["bias"])
        for k in range(n_layers)
    ]


def _with_layers(tree, layers):
    """``tree`` with each ``Dense_<k>`` layer's kernel and bias taken from
    ``layers[k]``, its own structure kept."""

    def leaf(path, _):
        layer, name = (getattr(key, "key", None) for key in path[-2:])
        return layers[int(layer.split("_")[1])][name == "bias"]

    return jax.tree_util.tree_map_with_path(leaf, tree)


def _fleet_steps(stack, params, opt_state, xs, ys, w, fm, interpret):
    """
    Every machine's epoch of steps in one kernel call: ``xs``/``ys``
    ``(n_batches, M, f, batch)`` slabs, ``w`` ``(M, n_batches, batch)``,
    ``fm`` ``(M, f_out)`` or None. Returns ``(params, opt_state,
    loss_sums, w_sums)`` as the scan does, the sums ``(M, n_batches)``.

    The kernel takes each layer's kernel transposed, ``(M, out, in)``, its
    bias as ``(M, 1, out)`` and the weights step-major, a machine's tile
    at a time; XLA lays a fleet's leaves with the machines on lanes, so
    the state is laid anew on its way in and out, once an epoch.
    """
    n_batches, n_machines, fx, batch = xs.shape
    fy = ys.shape[2]
    n_layers = len(stack.widths) - 1
    adam, *rest_state = opt_state
    layers = _layers(params, n_layers)
    mu_layers, nu_layers = _layers(adam.mu, n_layers), _layers(adam.nu, n_layers)
    block = block_size(stack, n_machines, batch, n_batches, xs.dtype.itemsize)
    if not block:
        raise ValueError("the fleet's step kernel does not fit vector memory")
    n_blocks = pl.cdiv(n_machines, block)

    # optax's count and bias corrections, step by step: a step with a real
    # sample counts (safe_increment: the count saturates at int32's max)
    real = (jnp.sum(w, axis=-1) > 0).astype(jnp.int32)
    count = adam.count.astype(jnp.int32)
    room = jnp.iinfo(jnp.int32).max - count
    count_inc = count[:, None] + jnp.minimum(jnp.cumsum(real, axis=1), room[:, None])
    corr = jnp.stack(
        [1 - stack.b1 ** count_inc, 1 - stack.b2 ** count_inc], axis=-1
    ).astype(jnp.float32).reshape(n_machines, 2 * n_batches)
    new_count = count + jnp.minimum(jnp.sum(real, axis=1), room)

    # the state is laid into a machine's tiles once the slabs are made:
    # laid before, its copies would hold HBM beside the sort's and the
    # fetch's buffers
    pairs, xs, ys = jax.lax.optimization_barrier(
        ((*layers, *mu_layers, *nu_layers), xs, ys)
    )
    state = [
        leaf
        for kernel, bias in pairs
        for leaf in (kernel.transpose(0, 2, 1), bias[:, None, :])
    ]
    # a machine's weights as they lie, each step's a run of ``batch``
    w = w.reshape(n_machines, n_batches * batch)

    def by_block(a):
        tail = a.shape[1:]
        return pl.BlockSpec((block,) + tail, lambda i, s: (i,) + (0,) * len(tail))

    def slab_spec(f):
        return pl.BlockSpec((None, block, f, batch), lambda i, s: (s, i, 0, 0))

    def smem_spec():
        return pl.BlockSpec(
            (block, 2 * n_batches), lambda i, s: (i, 0), memory_space=pltpu.SMEM
        )

    def sums_spec():
        return pl.BlockSpec(
            (block, n_batches), lambda i, s: (i, 0), memory_space=pltpu.SMEM
        )

    operands = [corr, xs, ys, w]
    in_specs = [
        smem_spec(), slab_spec(fx), slab_spec(fy),
        pl.BlockSpec((block, batch), lambda i, s: (i, s)),
    ]
    if fm is not None:
        operands.append(fm.reshape(n_machines, 1, fy).astype(jnp.float32))
        in_specs.append(by_block(operands[-1]))
    first_state = len(operands)
    operands += state
    in_specs += [by_block(a) for a in state]
    operand_dtype = jnp.float32 if interpret else jnp.bfloat16
    call = pl.pallas_call(
        functools.partial(
            _steps_kernel, stack=stack, n_machines=n_machines, block=block,
            n_layers=n_layers, masked=fm is not None, operand_dtype=operand_dtype,
        ),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in state] + [
            jax.ShapeDtypeStruct((n_machines, n_batches), jnp.float32)
        ] * 2,
        grid_spec=pl.GridSpec(
            grid=(n_blocks, n_batches),
            in_specs=in_specs,
            out_specs=[by_block(a) for a in state] + [sums_spec(), sums_spec()],
        ),
        input_output_aliases={first_state + i: i for i in range(len(state))},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=(
                vmem_bytes(stack, block, batch, n_batches, xs.dtype.itemsize)
                + _VMEM_HEADROOM_BYTES
            ),
        ),
        interpret=interpret,
        name="fleet_steps",
    )
    with jax.named_scope("fleet.loss_grad"):
        outs = call(*operands)
    new_state, (loss_sums, w_sums) = outs[:-2], outs[-2:]
    pairs = [
        (new_state[i].transpose(0, 2, 1), new_state[i + 1][:, 0, :])
        for i in range(0, len(new_state), 2)
    ]
    new_params = _with_layers(params, pairs[:n_layers])
    new_adam = adam._replace(
        count=new_count.astype(adam.count.dtype),
        mu=_with_layers(adam.mu, pairs[n_layers:2 * n_layers]),
        nu=_with_layers(adam.nu, pairs[2 * n_layers:]),
    )
    return new_params, (new_adam, *rest_state), loss_sums, w_sums


def epoch_steps(stack: DenseStack, scan_steps: Callable, interpret: Optional[bool] = None):
    """
    ``run(params, opt_state, key, (xb_all, yb_all, w_all), fm) -> (params,
    opt_state, loss_sums, w_sums)`` for ONE machine (``fm`` its column mask
    or None): ``scan_steps``
    itself, the trainer's scan over the epoch's batches. Under ``jax.vmap``
    over a fleet (how the trainer calls it) the whole fleet's epoch of
    steps goes through ONE kernel call instead (the rule below), the
    batches read as the fetch kernel wrote them.

    ``interpret=None`` selects from the backend: compiled Mosaic kernel on
    ``tpu``, interpreter on ``cpu``, ValueError on anything else.
    """
    if interpret is None:
        interpret = _interpret_for_backend(jax.default_backend())

    @jax.custom_batching.custom_vmap
    def run(params, opt_state, key, batches, fm):
        return scan_steps(params, opt_state, key, batches, fm)

    @run.def_vmap
    def run_fleet(axis_size, in_batched, params, opt_state, key, batches, fm):
        params, opt_state, (xb_all, yb_all, w_all), fm = jax.tree.map(
            lambda a, batched: a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape),
            (params, opt_state, batches, fm), tuple(in_batched[:2]) + tuple(in_batched[3:]),
        )
        # (M, n_batches, batch, f) back to the slabs the fetch kernel wrote,
        # (n_batches, M, f, batch): the two transposes meet and cancel
        xs, ys = (a.transpose(1, 0, 3, 2) for a in (xb_all, yb_all))
        out = _fleet_steps(stack, params, opt_state, xs, ys, w_all, fm, interpret)
        return out, jax.tree.map(lambda _: True, out)

    return run
