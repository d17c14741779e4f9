"""
Transformer / TCN backend tests (new backends beyond the reference —
BASELINE.json config #5) plus the Pallas flash-attention kernel (interpret
mode on CPU; the same kernel code compiles via Mosaic on TPU).
"""

import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models import (
    TCNAutoEncoder,
    TCNForecast,
    TransformerAutoEncoder,
    TransformerForecast,
)
from gordo_tpu.models.anomaly import DiffBasedAnomalyDetector
from gordo_tpu.models.specs_seq import (
    dense_attention,
    default_dilations,
    receptive_field,
    sinusoidal_positions,
)
from gordo_tpu.ops.flash_attention import flash_attention

RNG = np.random.default_rng(7)


def make_data(n=200, f=4):
    X = RNG.random((n, f)).astype("float32")
    return X, X.copy()


SMALL_TRANSFORMER = dict(d_model=16, n_heads=2, n_layers=1, epochs=2, batch_size=16)
SMALL_TCN = dict(channels=(8, 8), kernel_size=3, epochs=2, batch_size=16)


@pytest.mark.parametrize(
    "cls,kind,kwargs,lookahead",
    [
        (TransformerAutoEncoder, "transformer_model", SMALL_TRANSFORMER, 0),
        (TransformerForecast, "transformer_model", SMALL_TRANSFORMER, 1),
        (TCNAutoEncoder, "tcn_model", SMALL_TCN, 0),
        (TCNForecast, "tcn_model", SMALL_TCN, 1),
    ],
)
def test_fit_predict_shapes(cls, kind, kwargs, lookahead):
    X, y = make_data()
    model = cls(kind=kind, lookback_window=12, **kwargs)
    assert model.lookahead == lookahead
    assert model.fit(X, y) is model
    out = model.predict(X)
    assert out.shape == (len(X) - 12 + 1 - lookahead, X.shape[1])
    assert np.isfinite(out).all()
    # training converged at least a little
    losses = model.history_["loss"]
    assert losses[-1] < losses[0]
    assert np.isfinite(model.score(X, y))


def test_transformer_pickle_roundtrip():
    X, y = make_data(150)
    model = TransformerAutoEncoder(
        kind="transformer_model", lookback_window=8, **SMALL_TRANSFORMER
    )
    model.fit(X, y)
    expected = model.predict(X)
    restored = pickle.loads(pickle.dumps(model))
    np.testing.assert_allclose(restored.predict(X), expected, rtol=1e-5)


def test_serializer_roundtrip():
    from gordo_tpu.serializer import from_definition, into_definition

    definition = {
        "gordo_tpu.models.TransformerAutoEncoder": {
            "kind": "transformer_model",
            "lookback_window": 8,
            "d_model": 16,
            "n_heads": 2,
            "n_layers": 1,
            "epochs": 1,
        }
    }
    model = from_definition(definition)
    assert isinstance(model, TransformerAutoEncoder)
    assert model.lookback_window == 8
    round_tripped = into_definition(model)
    rebuilt = from_definition(round_tripped)
    assert isinstance(rebuilt, TransformerAutoEncoder)
    assert rebuilt.kwargs["d_model"] == 16


def test_transformer_inside_anomaly_detector():
    X, y = make_data(240)
    detector = DiffBasedAnomalyDetector(
        base_estimator=TransformerAutoEncoder(
            kind="transformer_model", lookback_window=8, **SMALL_TRANSFORMER
        ),
        require_thresholds=False,
    )
    detector.fit(X, y)
    import pandas as pd

    index = pd.date_range("2020-01-01", periods=len(X), freq="10min", tz="UTC")
    anomalies = detector.anomaly(
        pd.DataFrame(X, index=index), pd.DataFrame(y, index=index)
    )
    assert "total-anomaly-scaled" in anomalies.columns.get_level_values(0)
    assert np.isfinite(
        anomalies["total-anomaly-scaled"].to_numpy(dtype=float)
    ).all()


def test_tcn_receptive_field_and_dilations():
    assert default_dilations(4) == (1, 2, 4, 8)
    # 2 convs per block: rf = 1 + 2*(k-1)*sum(d)
    assert receptive_field(3, (1, 2, 4)) == 1 + 2 * 2 * 7


def test_sinusoidal_positions_shape_and_range():
    enc = sinusoidal_positions(10, 16)
    assert enc.shape == (10, 16)
    assert float(jnp.abs(enc).max()) <= 1.0
    # rows are distinct (positions distinguishable)
    assert not np.allclose(np.asarray(enc[0]), np.asarray(enc[1]))


# -- flash attention kernel (interpret mode on CPU) -------------------------
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = (
        jnp.asarray(RNG.normal(size=(2, 37, 2, 16)), dtype=jnp.float32)
        for _ in range(3)
    )
    out_flash = flash_attention(q, k, v, causal=causal)
    out_dense = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out_flash, out_dense, atol=2e-3)


@pytest.mark.slow
def test_flash_unknown_backend_is_an_error(monkeypatch):
    """Interpret mode comes from the backend with ONLY ``cpu`` mapping to
    the interpreter: "not tpu, so interpret" let a chip under another
    platform name run the interpreter silently. An unknown backend
    raises; an explicit ``interpret=`` still decides for itself."""
    q = jnp.ones((1, 8, 1, 8), dtype=jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "somechip")
    with pytest.raises(ValueError, match="no kernel mode for backend 'somechip'"):
        flash_attention(q, q, q)
    assert flash_attention(q, q, q, interpret=True).shape == q.shape


def test_flash_gradients_match_dense():
    q, k, v = (
        jnp.asarray(RNG.normal(size=(1, 24, 2, 8)), dtype=jnp.float32)
        for _ in range(3)
    )

    def loss_flash(q_):
        return jnp.sum(flash_attention(q_, k, v, causal=True) ** 2)

    def loss_dense(q_):
        return jnp.sum(dense_attention(q_, k, v, causal=True) ** 2)

    np.testing.assert_allclose(
        jax.grad(loss_flash)(q), jax.grad(loss_dense)(q), atol=2e-3
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_full_gradients_match_dense(causal):
    """dq, dk AND dv from the blockwise backward kernels vs dense autodiff."""
    q, k, v = (
        jnp.asarray(RNG.normal(size=(2, 37, 2, 16)), dtype=jnp.float32)
        for _ in range(3)
    )

    def flash_loss(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, causal=causal) ** 2)

    def dense_loss(q_, k_, v_):
        return jnp.sum(dense_attention(q_, k_, v_, causal=causal) ** 2)

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, w, atol=2e-3, err_msg=f"d{name}")


def test_flash_training_memory_is_linear_in_seq():
    """
    Neither pass may materialize a (seq, seq) tensor NOR an O(block, seq)
    strip: both axes are tiled, so the largest score-shaped intermediate is
    (block_q, block_k). Pinned by inspecting the lowered HLO of the full
    value-and-grad program.
    """
    seq, d, block = 512, 8, 128
    q, k, v = (
        jnp.asarray(RNG.normal(size=(1, seq, 1, d)), dtype=jnp.float32)
        for _ in range(3)
    )

    def loss(q_, k_, v_):
        return jnp.sum(
            flash_attention(
                q_, k_, v_, causal=True, block_q=block, block_k=block
            )
            ** 2
        )

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).as_text()
    assert f"{seq},{seq}" not in hlo and f"{seq}x{seq}" not in hlo, (
        "backward materializes a (seq, seq) tensor"
    )
    # round-2 regression guard: the old kernels kept a (block, seq) strip
    # (whole-K in VMEM per grid cell), capping single-chip context length
    assert f"{block},{seq}" not in hlo and f"{block}x{seq}" not in hlo, (
        "a kernel materializes an O(block, seq) strip"
    )
    # the (block, block) tile IS expected — proves we checked the right
    # program, not an empty lowering
    assert f"{block},{block}" in hlo or f"{block}x{block}" in hlo


def test_flash_long_context_vmem_bounded():
    """
    The VERDICT-r2 ceiling case: at seq=16k the old kernels needed an
    ~8 MB strip + whole K/V in VMEM (past v5e VMEM); the tiled kernels'
    intermediates stay (block_q, block_k) regardless of seq. Asserted on
    the lowered HLO, then executed (forward) in interpret mode at a long
    sequence to prove the grid actually runs.
    """
    seq, d, block = 16384, 8, 512
    q = jax.ShapeDtypeStruct((1, seq, 1, d), jnp.float32)

    def loss(q_, k_, v_):
        return jnp.sum(
            flash_attention(
                q_, k_, v_, causal=True, block_q=block, block_k=block
            )
            ** 2
        )

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).as_text()
    for bad in (f"{seq},{seq}", f"{seq}x{seq}", f"{block},{seq}", f"{block}x{seq}"):
        assert bad not in hlo, f"unbounded intermediate {bad} in HLO"
    assert f"{block},{block}" in hlo or f"{block}x{block}" in hlo

    # execute forward at seq=4096 (16k in interpret mode is minutes on a
    # 1-core CI box; the 16k guarantee above is the lowering, which is
    # identical code): online-softmax result matches dense attention
    seq_run = 4096
    qr, kr, vr = (
        jnp.asarray(
            np.random.default_rng(i).normal(size=(1, seq_run, 1, d)),
            dtype=jnp.float32,
        )
        for i in range(3)
    )
    out = flash_attention(qr, kr, vr, causal=True, block_q=512, block_k=512)
    want = dense_attention(qr, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-3)


def test_flash_gradients_multi_block_seq():
    """Grad parity with dense autodiff when the grid is genuinely 2-D in
    both sequence axes (several q AND k blocks)."""
    seq = 1024
    q, k, v = (
        jnp.asarray(RNG.normal(size=(1, seq, 1, 8)), dtype=jnp.float32)
        for _ in range(3)
    )

    def flash_loss(q_, k_, v_):
        return jnp.sum(
            flash_attention(
                q_, k_, v_, causal=True, block_q=256, block_k=256
            )
            ** 2
        )

    def dense_loss(q_, k_, v_):
        return jnp.sum(dense_attention(q_, k_, v_, causal=True) ** 2)

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, w, atol=5e-3, err_msg=f"d{name}")


@pytest.mark.slow
def test_flash_attention_impl_in_estimator():
    X, y = make_data(120)
    model = TransformerAutoEncoder(
        kind="transformer_model",
        lookback_window=8,
        attention_impl="flash",
        **SMALL_TRANSFORMER,
    )
    model.fit(X, y)
    out = model.predict(X)
    assert np.isfinite(out).all()


def test_unknown_attention_impl_raises():
    with pytest.raises(ValueError, match="attention_impl"):
        model = TransformerAutoEncoder(
            kind="transformer_model", attention_impl="nope", **SMALL_TRANSFORMER
        )
        model.fit(*make_data(60))


def test_windowed_refit_serves_new_params():
    """A refit must invalidate the device-resident stacked-param cache:
    predictions after fit(X2) must come from the NEW params, not the
    first fit's (regression guard for _device_params_stacked)."""
    from gordo_tpu.models.models import LSTMAutoEncoder

    rng = np.random.default_rng(0)
    X1 = rng.random((60, 3)).astype("float32")
    X2 = (10.0 + rng.random((60, 3))).astype("float32")

    model = LSTMAutoEncoder(
        kind="lstm_model", lookback_window=5, encoding_dim=(4,),
        encoding_func=("tanh",), decoding_dim=(4,), decoding_func=("tanh",),
        epochs=2,
    )
    model.fit(X1, X1)
    out1 = model.predict(X1)
    model.fit(X2, X2)
    out2 = model.predict(X1)
    # params changed (X2's scale forces different weights); identical
    # outputs would mean the stale stacked cache served the old model
    assert not np.allclose(out1, out2)
