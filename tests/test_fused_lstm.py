"""
FusedLSTMLayer parity: the one-scan LSTM (four gates' kernels side by
side, the step's input product inside the time loop) must compute exactly
what nn.RNN(OptimizedLSTMCell) computes when given the same weights (gate
order [i, f, g, o]), and train end-to-end through the standard estimator
machinery.
"""

import pickle
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models import LSTMAutoEncoder
from gordo_tpu.models.specs import (
    FusedLSTMLayer,
    LSTMNet,
    lstm_cell_step,
    lstm_cell_update,
    lstm_cell_update_transpose,
)

B, T, F, H = 3, 7, 5, 8


def _map_cell_params_to_fused(cell_params):
    """OptimizedLSTMCell's i/f/g/o denses -> fused concatenated layout."""
    p = cell_params
    input_kernel = jnp.concatenate(
        [p["ii"]["kernel"], p["if"]["kernel"], p["ig"]["kernel"], p["io"]["kernel"]],
        axis=1,
    )
    recurrent_kernel = jnp.concatenate(
        [p["hi"]["kernel"], p["hf"]["kernel"], p["hg"]["kernel"], p["ho"]["kernel"]],
        axis=1,
    )
    recurrent_bias = jnp.concatenate(
        [p["hi"]["bias"], p["hf"]["bias"], p["hg"]["bias"], p["ho"]["bias"]]
    )
    return input_kernel, recurrent_kernel, recurrent_bias


def test_fused_layer_matches_optimized_cell():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, T, F)), jnp.float32)

    plain = LSTMNet(layer_dims=(H,), layer_funcs=("tanh",), out_dim=F)
    fused = LSTMNet(layer_dims=(H,), layer_funcs=("tanh",), out_dim=F, fused=True)

    plain_params = plain.init(jax.random.PRNGKey(0), x)
    fused_params = fused.init(jax.random.PRNGKey(0), x)

    # copy the cell's weights into the fused layout (+ shared head)
    cell_params = plain_params["params"]["OptimizedLSTMCell_0"]
    ik, rk, rb = _map_cell_params_to_fused(cell_params)
    fused_params = jax.tree_util.tree_map(lambda a: a, fused_params)  # copy
    fp = fused_params["params"]
    fp["FusedLSTMLayer_0"]["input_proj"]["kernel"] = ik
    fp["FusedLSTMLayer_0"]["recurrent_kernel"] = rk
    fp["FusedLSTMLayer_0"]["recurrent_bias"] = rb
    fp["Dense_0"] = plain_params["params"]["Dense_0"]

    out_plain, _ = plain.apply(plain_params, x)
    out_fused, _ = fused.apply(fused_params, x)
    np.testing.assert_allclose(out_fused, out_plain, rtol=1e-5, atol=1e-6)


def test_fused_stacked_layers_match():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(B, T, F)), jnp.float32)
    dims, funcs = (H, 4), ("tanh", "relu")

    plain = LSTMNet(layer_dims=dims, layer_funcs=funcs, out_dim=2)
    fused = LSTMNet(layer_dims=dims, layer_funcs=funcs, out_dim=2, fused=True)
    plain_params = plain.init(jax.random.PRNGKey(0), x)
    fused_params = fused.init(jax.random.PRNGKey(0), x)

    fp = fused_params["params"]
    for i in range(len(dims)):
        cell = plain_params["params"][f"OptimizedLSTMCell_{i}"]
        ik, rk, rb = _map_cell_params_to_fused(cell)
        fp[f"FusedLSTMLayer_{i}"]["input_proj"]["kernel"] = ik
        fp[f"FusedLSTMLayer_{i}"]["recurrent_kernel"] = rk
        fp[f"FusedLSTMLayer_{i}"]["recurrent_bias"] = rb
    fp["Dense_0"] = plain_params["params"]["Dense_0"]

    out_plain, _ = plain.apply(plain_params, x)
    out_fused, _ = fused.apply(fused_params, x)
    np.testing.assert_allclose(out_fused, out_plain, rtol=1e-5, atol=1e-6)


def _map_layer_fused_to_stacked(layer_params, stacked_params, cell="lstm"):
    """Per-layer fused params -> the stacked one-scan schedule's layout."""
    sp = jax.tree_util.tree_map(lambda a: a, stacked_params)["params"]
    lname = "FusedLSTMLayer" if cell == "lstm" else "FusedGRULayer"
    layer = 0
    while f"{lname}_{layer}" in layer_params["params"]:
        lp = layer_params["params"][f"{lname}_{layer}"]
        if layer == 0:
            sp["input_proj_0"]["kernel"] = lp["input_proj"]["kernel"]
            if cell == "gru":
                sp["input_proj_0"]["bias"] = lp["input_proj"]["bias"]
        else:
            sp[f"input_kernel_{layer}"] = lp["input_proj"]["kernel"]
            if cell == "gru":
                sp[f"input_bias_{layer}"] = lp["input_proj"]["bias"]
        if cell == "lstm":
            sp[f"recurrent_kernel_{layer}"] = lp["recurrent_kernel"]
            sp[f"recurrent_bias_{layer}"] = lp["recurrent_bias"]
        else:
            sp[f"recurrent_kernel_rz_{layer}"] = lp["recurrent_kernel_rz"]
            sp[f"recurrent_kernel_n_{layer}"] = lp["recurrent_kernel_n"]
            sp[f"recurrent_bias_n_{layer}"] = lp["recurrent_bias_n"]
        layer += 1
    sp["Dense_0"] = layer_params["params"]["Dense_0"]
    return {"params": sp}


def test_stacked_schedule_matches_layer_schedule():
    """schedule="stacked" (one streaming time scan for all layers — the
    XLA:CPU-friendly layout) must compute exactly what the per-layer
    fused schedule computes given the same weights, for both cells."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(B, T, F)), jnp.float32)
    dims, funcs = (H, 4, H), ("tanh", "relu", "tanh")

    for cell in ("lstm", "gru"):
        layer_net = LSTMNet(
            layer_dims=dims, layer_funcs=funcs, out_dim=2, fused=True, cell=cell
        )
        stacked_net = LSTMNet(
            layer_dims=dims, layer_funcs=funcs, out_dim=2, fused=True,
            cell=cell, schedule="stacked",
        )
        layer_params = layer_net.init(jax.random.PRNGKey(0), x)
        stacked_params = stacked_net.init(jax.random.PRNGKey(1), x)
        stacked_params = _map_layer_fused_to_stacked(
            layer_params, stacked_params, cell
        )
        out_layer, _ = layer_net.apply(layer_params, x)
        out_stacked, _ = stacked_net.apply(stacked_params, x)
        np.testing.assert_allclose(out_stacked, out_layer, rtol=1e-5, atol=1e-6)


def test_stacked_estimator_trains_and_predicts():
    rng = np.random.default_rng(5)
    X = rng.random((80, F)).astype("float32")
    model = LSTMAutoEncoder(
        kind="lstm_model",
        lookback_window=6,
        encoding_dim=(8,),
        encoding_func=("tanh",),
        decoding_dim=(8,),
        decoding_func=("tanh",),
        fused=True,
        schedule="stacked",
        epochs=2,
    )
    model.fit(X, X)
    assert model.predict(X).shape == (80 - 6 + 1, F)


def test_fused_estimator_trains_and_pickles():
    rng = np.random.default_rng(2)
    X = rng.random((80, F)).astype("float32")
    model = LSTMAutoEncoder(
        kind="lstm_model",
        lookback_window=6,
        encoding_dim=(8,),
        encoding_func=("tanh",),
        decoding_dim=(8,),
        decoding_func=("tanh",),
        fused=True,
        epochs=2,
    )
    model.fit(X, X)
    out = model.predict(X)
    assert out.shape == (80 - 6 + 1, F)
    clone = pickle.loads(pickle.dumps(model))
    np.testing.assert_allclose(clone.predict(X), out, rtol=1e-5)


def test_time_unroll_is_pure_schedule():
    """``time_unroll`` must not change the math — unrolled and rolled
    scans produce identical outputs for identical params."""
    from gordo_tpu.models.factories.lstm import lstm_model

    rng = np.random.default_rng(3)
    x = rng.random((4, 10, F)).astype("float32")
    rolled = lstm_model(
        n_features=F, lookback_window=10, encoding_dim=(8,),
        encoding_func=("tanh",), decoding_dim=(8,), decoding_func=("tanh",),
        fused=True, time_unroll=1,
    )
    unrolled = lstm_model(
        n_features=F, lookback_window=10, encoding_dim=(8,),
        encoding_func=("tanh",), decoding_dim=(8,), decoding_func=("tanh",),
        fused=True, time_unroll=4,
    )
    import jax

    params = rolled.module.init(jax.random.PRNGKey(0), x)
    out_rolled, _ = rolled.module.apply(params, x)
    out_unrolled, _ = unrolled.module.apply(params, x)
    np.testing.assert_allclose(out_unrolled, out_rolled, rtol=1e-6, atol=1e-7)


# -- the time scan against lax.scan under autodiff ---------------------------


class AutodiffScanLSTMLayer(nn.Module):
    """FusedLSTMLayer as it was before its time scan became
    ``lstm_time_scan``: the input projection hoisted out of the scan as
    one ``nn.Dense`` over all rows, then ``jax.lax.scan`` over
    ``lstm_cell_step``, its backward pass left to autodiff. Same parameter
    tree: the reference the hand-written scan is held to."""

    features: int
    activation_fn: Any = jnp.tanh
    dtype: Any = jnp.float32
    unroll: int = 1
    time_major: bool = False

    @nn.compact
    def __call__(self, x):
        h_dim = self.features
        lead = x.shape[:-1]
        z = nn.Dense(
            4 * h_dim, use_bias=False, dtype=self.dtype, name="input_proj"
        )(x.reshape(-1, x.shape[-1]))
        z = z.reshape(*lead, 4 * h_dim)
        w_h = self.param(
            "recurrent_kernel", nn.initializers.orthogonal(),
            (h_dim, 4 * h_dim), jnp.float32,
        ).astype(self.dtype)
        b_h = self.param(
            "recurrent_bias", nn.initializers.zeros_init(), (4 * h_dim,), jnp.float32
        ).astype(self.dtype)

        def step(carry, z_t):
            c, h = lstm_cell_step(
                *carry, z_t, w_h, b_h, self.activation_fn, self.dtype
            )
            return (c, h), h

        batch = x.shape[1] if self.time_major else x.shape[0]
        zeros = jnp.zeros((batch, h_dim), jnp.float32)
        _, hs = jax.lax.scan(
            step, (zeros, zeros), z if self.time_major else z.swapaxes(0, 1),
            unroll=self.unroll,
        )
        hs = hs if self.time_major else hs.swapaxes(0, 1)
        return hs.astype(self.dtype)


N_MACHINES, N_STEPS = 3, 2


def machine_step(layer):
    """One machine's step: the layer's outputs and the gradients of a probed
    sum of them with respect to every parameter and the input."""

    def machine(p, x, w):
        def loss(p, x):
            hs = layer.apply(p, x)
            return jnp.sum(hs.astype(jnp.float32) * w), hs

        (_, hs), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, x)
        return hs, grads

    return machine


def fleet_steps(layer, params, xs, probe):
    """What the fleet's epoch program does with a layer: a ``lax.scan``
    over steps, each the ``vmap`` over machines of ``machine_step``."""
    machines = jax.vmap(machine_step(layer))
    return jax.lax.scan(lambda _, x: (None, machines(params, x, probe)), None, xs)[1]


def unbatched_steps(layer, params, xs, probe):
    """``fleet_steps`` with no ``vmap`` and no ``lax.scan``: one jitted call
    a step and a machine, stacked afterwards."""
    machine = jax.jit(machine_step(layer))
    outs = [
        machine(jax.tree.map(lambda leaf: leaf[m], params), x[m], probe[m])
        for x in xs
        for m in range(N_MACHINES)
    ]
    return jax.tree.map(
        lambda *leaves: jnp.stack(leaves).reshape(N_STEPS, N_MACHINES, *leaves[0].shape),
        *outs,
    )


def fleet_inputs(rng, layer, n_time, width, time_major, batch=B, n_features=F):
    shape = (
        (n_time, batch, n_features) if time_major else (batch, n_time, n_features)
    )
    xs = jnp.asarray(rng.normal(size=(N_STEPS, N_MACHINES, *shape)), jnp.float32)
    out = shape[:-1] + (width,)
    probe = jnp.asarray(rng.normal(size=(N_MACHINES, *out)), jnp.float32)
    params = jax.vmap(lambda k: layer.init(k, xs[0, 0]))(
        jax.random.split(jax.random.PRNGKey(7), N_MACHINES)
    )
    # the bias starts at zero: give every gate one
    params["params"]["recurrent_bias"] = jnp.asarray(
        rng.normal(size=(N_MACHINES, 4 * width)) * 0.1, jnp.float32
    )
    return params, xs, probe


def assert_same_tree(got, want, dtype, exact=False):
    """float32 to 1e-6 of the reference's largest entry per leaf; bfloat16 to
    a few of its roundings (the two backward passes sum in another order);
    ``exact``: bit for bit.

    Why float32 is not bit for bit against the reference: the scan
    multiplies a step's ``batch`` rows of ``x`` by the input kernel inside
    its loop, the reference all ``time*batch`` rows at once, and XLA:CPU's
    gemm does not give a row block the rows of the whole product at every
    shape (here: the same bits at 3 and 5 rows a step, other last bits at 8
    and 16). The sums after the products are the reference's, in its order."""
    tol = 0.0 if exact else 1e-6 if dtype == jnp.float32 else 3e-2
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert g.shape == w.shape and g.dtype == w.dtype
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        np.testing.assert_allclose(g, w, rtol=0, atol=tol * max(np.abs(w).max(), 1e-3))


def scan_case(
    time_major, time_unroll, dtype, width, batch=B, unbatched=False, n_features=F
):
    name = "float32" if dtype == jnp.float32 else "bfloat16"
    layout = "time_major" if time_major else "batch_major"
    case = f"{layout}-{time_unroll}-{name}-{width}"
    if unbatched:
        case += f"-batch{batch}-unbatched_reference"
    elif (batch, n_features) != (B, F):
        case += f"-{batch}_rows-{n_features}_features"
    return pytest.param(
        time_major, time_unroll, dtype, width, batch, unbatched, n_features, id=case
    )


@pytest.mark.parametrize(
    "time_major,time_unroll,dtype,width,batch,unbatched,n_features",
    [
        scan_case(time_major, time_unroll, dtype, width)
        for time_major in (False, True)
        for time_unroll in (1, 4)
        for dtype in (jnp.float32, jnp.bfloat16)
        for width in (8, 16)
    ]
    # the stacked buffers are row-flat: step t's rows start at t*batch, which
    # at 5 rows a batch is aligned to nothing; and the machines' axis that
    # ``vmap`` puts in front of the rows must change no number of any machine
    + [
        scan_case(time_major, time_unroll, jnp.float32, 8, batch=5, unbatched=True)
        for time_major in (False, True)
        for time_unroll in (1, 4)
    ]
    # the input kernel's and ``x``'s cotangents are a product each of a step's
    # rows, inside the backward loop (the reference makes each as ONE product
    # over all rows): a first layer's input is 50 wide, a later one's a
    # multiple of 8; and at 8 rows a step XLA:CPU's gemm gives a row block
    # other last bits than the whole product's rows
    + [
        scan_case(time_major, 1, dtype, 8, batch=batch, n_features=n_features)
        for dtype in (jnp.float32, jnp.bfloat16)
        for time_major, batch, n_features in (
            (True, B, 50), (True, 8, F), (True, 8, 50), (False, 8, 50),
        )
    ],
)
def test_time_scan_matches_autodiff_scan(
    time_major, time_unroll, dtype, width, batch, unbatched, n_features
):
    """Outputs and the gradients of all three parameters (the input kernel
    among them) and of ``x``, as the fleet's epoch program takes them,
    against the autodiff scan over the hoisted ``x @ w_x`` under the same
    ``vmap``; the ``unbatched`` cases against that scan of one machine at a
    time, and bit for bit against the layer's own run of one machine at a
    time: the machines' axis changes no number."""
    kwargs = dict(unroll=time_unroll, time_major=time_major, dtype=dtype)
    layer = FusedLSTMLayer(width, **kwargs)
    reference = AutodiffScanLSTMLayer(width, **kwargs)
    params, xs, probe = fleet_inputs(
        np.random.default_rng(width + time_unroll), layer, T, width, time_major,
        batch, n_features,
    )
    got = fleet_steps(layer, params, xs, probe)
    want = (unbatched_steps if unbatched else fleet_steps)(reference, params, xs, probe)
    hs, (d_params, _) = got
    assert hs.shape == (N_STEPS, N_MACHINES) + probe.shape[1:]
    assert set(d_params["params"]) == {"input_proj", "recurrent_kernel", "recurrent_bias"}
    assert_same_tree(got, want, dtype)
    if unbatched:
        assert_same_tree(
            got, unbatched_steps(layer, params, xs, probe), dtype, exact=True
        )


@pytest.mark.parametrize("activation", ["tanh", "relu"])
def test_time_scan_of_one_step(activation):
    """A LOOKBACK of 1: the loops run once, from the zero state; and an
    activation that is not tanh (its derivative is taken from its input)."""
    from gordo_tpu.ops.activations import resolve_activation

    act = resolve_activation(activation)
    layer = FusedLSTMLayer(H, activation_fn=act, time_major=True)
    reference = AutodiffScanLSTMLayer(H, activation_fn=act, time_major=True)
    params, xs, probe = fleet_inputs(np.random.default_rng(1), layer, 1, H, True)
    got, want = (fleet_steps(m, params, xs, probe) for m in (layer, reference))
    assert_same_tree(got, want, jnp.float32)


@pytest.mark.parametrize("first_step", [False, True], ids=["later_step", "zero_state"])
@pytest.mark.parametrize("activation", ["tanh", "relu"])
def test_cell_update_transpose_is_autodiffs_bit_for_bit(activation, first_step):
    """The backward step's transpose of the cell update, taken in two parts
    with the activations behind a barrier, gives ``jax.vjp``'s cotangents of
    ``lstm_cell_update`` bit for bit in float32, under the fleet's ``vmap``:
    the previous cell state's and the gates'. At ``t = 0`` the previous cell
    state is the zero state; ``relu``'s derivative is taken from its input."""
    from gordo_tpu.ops.activations import resolve_activation

    act = resolve_activation(activation)
    rng = np.random.default_rng(3)

    def normal(width, scale=1.0):
        return jnp.asarray(rng.normal(size=(N_MACHINES, B, width)) * scale, jnp.float32)

    c = normal(H, 2.0) * (0.0 if first_step else 1.0)
    gates, d_c, d_h = normal(4 * H, 3.0), normal(H), normal(H)

    @jax.jit
    @jax.vmap
    def autodiff(c, gates, d_c, d_h):
        _, update_vjp = jax.vjp(lambda c, g: lstm_cell_update(c, g, act), c, gates)
        return update_vjp((d_c, d_h))

    @jax.jit
    @jax.vmap
    def transpose(c, gates, d_c, d_h):
        return lstm_cell_update_transpose(c, gates, d_c, d_h, act)

    want, got = autodiff(c, gates, d_c, d_h), transpose(c, gates, d_c, d_h)
    assert np.abs(want[1]).max() > 0
    assert_same_tree(got, want, jnp.float32, exact=True)


def lowered_text(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


def test_forward_only_call_stacks_one_buffer_a_layer():
    """Scoring, validation and streaming take no gradient: the scan then
    stacks the hidden states and nothing else: no ``z``, the step's input
    product is the loop's own. Under a gradient it stacks the cell states
    beside them and not the gates: the backward loop makes them again with
    the forward's two products. On the way back it stacks
    ``d_x``, f wide, if the input takes a cotangent: a first layer's does
    not (``x`` is data), and JAX drops that write and its product from the
    backward loop before XLA sees them."""
    x = jnp.zeros((T, B, F))
    layer = FusedLSTMLayer(H, time_major=True)
    params = layer.init(jax.random.PRNGKey(0), x)
    forward = lowered_text(layer.apply, params, x)
    assert forward.count("dynamic_update_slice") == 1
    # nothing (time*batch, 4h) exists without a gradient; with the projection
    # hoisted, ``z`` was one
    gates_buffer = f"tensor<{T * B}x{4 * H}xf32>"
    assert gates_buffer not in forward

    def loss(p, x):
        return jnp.sum(layer.apply(p, x))

    first_layer = lowered_text(jax.grad(loss), params, x)
    assert first_layer.count("dynamic_update_slice") == 2
    assert first_layer.count("dot_general") == 7
    later_layer = lowered_text(jax.grad(loss, argnums=(0, 1)), params, x)
    assert later_layer.count("dynamic_update_slice") == 3
    assert later_layer.count("dot_general") == 8
    # nor with one: the gates were the widest stacked buffer
    assert gates_buffer not in first_layer and gates_buffer not in later_layer


def sub_equations(eqns):
    """Every equation of ``eqns`` and of the jaxprs they hold."""
    for eqn in eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from sub_equations(sub.eqns)


#: the 50-tag plant's six layers at the LSTM factory's default widths,
#: (width, input features), and four layers far wider than any of them
LAYER_SHAPES = [(256, 50), (128, 256), (64, 128), (64, 64), (128, 64), (256, 128)] + [
    (8, 520), (256, 8), (256, 1000), (1024, 1000)
]


@pytest.mark.parametrize(
    "width,n_features,batch",
    [(8, 5, 3), (8, 32, 3), (16, 50, 8)] + [(h, f, B) for h, f in LAYER_SHAPES],
    ids=lambda v: str(v),
)
def test_backward_loop_stacks_no_gate_cotangent(width, n_features, batch):
    """The backward loop multiplies a step's ``d_gates`` by the input kernel
    and by the step's rows of ``x`` itself: the backward pass allocates and
    writes ONE stacked buffer, ``d_x``, f wide, its products are the loop's
    and take a step's rows, and no product follows the loop. ``d_z`` was a
    second buffer, (time*batch, 4h), which two products over all rows read
    after the loop. The second case has f = 4h: the widths alone do not
    tell ``d_x`` from ``d_z``, the count of buffers does. The loop also
    makes a step's gates again, the forward's two products beside the four
    transposes, so the forward loop stacks ``h`` and ``c`` and no
    (time*batch, 4h) gates: at the plant's six layer shapes and at layers
    far wider than gordo's factories make by default."""
    x = jnp.zeros((T, batch, n_features))
    layer = FusedLSTMLayer(width, time_major=True)
    params = layer.init(jax.random.PRNGKey(0), x)
    eqns = jax.make_jaxpr(
        jax.grad(lambda p, x: jnp.sum(layer.apply(p, x)), argnums=(0, 1))
    )(params, x).jaxpr.eqns
    forward_loop, backward_loop = (
        i for i, e in enumerate(eqns) if e.primitive.name == "scan"
    )
    rows = T * batch
    allocated = [
        (i > forward_loop, e.outvars[0].aval.shape)
        for i, e in enumerate(eqns) if e.primitive.name == "empty"
    ]
    assert allocated == [(False, (rows, width))] * 2 + [(True, (rows, n_features))]
    body = list(sub_equations(eqns[backward_loop].params["jaxpr"].eqns))
    written = [
        e.outvars[0].aval.shape for e in body
        if e.primitive.name == "dynamic_update_slice"
    ]
    assert written == [(rows, n_features)]
    products = [e for e in body if e.primitive.name == "dot_general"]
    assert len(products) == 6
    assert all(rows not in v.aval.shape for e in products for v in e.invars)
    assert not [
        e for e in sub_equations(eqns[backward_loop + 1 :])
        if e.primitive.name == "dot_general"
    ]


def test_input_cotangent_changes_no_parameter_gradient():
    """A first layer's input is data and takes no cotangent, a later
    layer's does: JAX drops ``d_x``'s product and write from the first
    one's backward loop, and what is left gives the same bits."""
    layer = FusedLSTMLayer(H, time_major=True)
    params, xs, probe = fleet_inputs(np.random.default_rng(2), layer, T, H, True)

    def loss(p, x):
        return jnp.sum(layer.apply(p, x) * probe[0])

    p = jax.tree.map(lambda leaf: leaf[0], params)
    first_layer = jax.jit(jax.grad(loss))
    later_layer = jax.jit(jax.grad(loss, argnums=(0, 1)))
    without = first_layer(p, xs[0, 0])
    with_d_x, d_x = later_layer(p, xs[0, 0])
    assert d_x.shape == xs[0, 0].shape and np.abs(d_x).max() > 0
    assert_same_tree(without, with_d_x, jnp.float32, exact=True)


def test_estimator_with_a_lookback_of_one_trains_and_pickles():
    rng = np.random.default_rng(6)
    X = rng.random((40, F)).astype("float32")
    model = LSTMAutoEncoder(
        kind="lstm_model", lookback_window=1,
        encoding_dim=(8,), encoding_func=("tanh",),
        decoding_dim=(8,), decoding_func=("tanh",),
        fused=True, epochs=2,
    )
    model.fit(X, X)
    out = model.predict(X)
    assert out.shape == (40, F) and np.isfinite(out).all()
    clone = pickle.loads(pickle.dumps(model))
    np.testing.assert_allclose(clone.predict(X), out, rtol=1e-5)
