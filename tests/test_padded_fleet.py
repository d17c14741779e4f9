"""
The padded bucket policy end to end (docs/parallelism.md "Bucketing
compiler"): exact stays the bit-identical default, padded fuses ragged
widths into one program with per-machine parity inside the documented
tolerance, masking keeps pad columns out of training decisions, and the
serving/AOT layers pad-and-strip transparently.
"""

import numpy as np
import pytest

from gordo_tpu.builder import FleetModelBuilder
from gordo_tpu.builder.fleet_build import _find_jax_estimator
from gordo_tpu.machine import Machine


def make_machine(name, ntags=3, epochs=2, model=None, **model_kwargs):
    model = model or {
        "gordo_tpu.models.AutoEncoder": {
            "kind": "feedforward_hourglass",
            "epochs": epochs,
            **model_kwargs,
        }
    }
    return Machine(
        name=name,
        project_name="padded-test",
        model=model,
        dataset={
            "type": "RandomDataset",
            "train_start_date": "2017-12-25 06:00:00Z",
            "train_end_date": "2017-12-27 06:00:00Z",
            "tags": [[f"Tag {t}", None] for t in range(ntags)],
        },
    )


def machine_data(machine):
    from gordo_tpu.data import _get_dataset

    X, y = _get_dataset(machine.dataset.to_dict()).get_data()
    return np.asarray(X, dtype="float32"), np.asarray(y, dtype="float32")


def reconstruction_mae(model, machine) -> float:
    X, y = machine_data(machine)
    predicted = np.asarray(model.predict(X))
    return float(np.abs(predicted - y[-len(predicted):]).mean())


# -- exact is the pinned default ------------------------------------------


def test_exact_policy_bit_identical_to_default_build():
    """--bucket-policy exact must be a no-op: same params, same history,
    bit for bit, as a builder constructed without the argument."""
    default_pairs = FleetModelBuilder(
        [make_machine("m0"), make_machine("m1")]
    ).build()
    exact_pairs = FleetModelBuilder(
        [make_machine("m0"), make_machine("m1")], bucket_policy="exact"
    ).build()
    for (d_model, _), (e_model, _) in zip(default_pairs, exact_pairs):
        d_est, e_est = _find_jax_estimator(d_model), _find_jax_estimator(e_model)
        assert d_est.history_ == e_est.history_
        import jax

        d_leaves = jax.tree_util.tree_leaves(d_est.params_)
        e_leaves = jax.tree_util.tree_leaves(e_est.params_)
        for dl, el in zip(d_leaves, e_leaves):
            np.testing.assert_array_equal(np.asarray(dl), np.asarray(el))
        # exact artifacts carry no pad bookkeeping
        assert not hasattr(e_est, "n_active_features_")


# -- padded: fusion + parity ----------------------------------------------


def test_padded_build_fuses_and_holds_mae_parity():
    """
    Ragged widths (3, 4) fuse into ONE compiled program; at a converged
    epoch budget each machine's reconstruction MAE stays within the
    documented tolerance (25% relative — docs/parallelism.md: pad
    columns are masked out, so the residual delta is only the padded
    family's derived layer widths and init draws; measured ~12% here)
    of its exact-bucket build, and histories keep the exact build's
    shape. The width-4 machine compiles at its own dims either way, so
    its loss stream must agree to reduction-order ulps (the fused
    bucket's program computes the masked mean `sum(err*mask)/n`, the
    exact one `mean(err)` — same numbers, different reduction).
    """
    machines = [
        make_machine("w3", ntags=3, epochs=10),
        make_machine("w4", ntags=4, epochs=10),
    ]
    padded_builder = FleetModelBuilder(machines, bucket_policy="padded")
    padded = padded_builder.build()
    assert len(padded_builder.plan_) == 1  # one fused program
    exact = FleetModelBuilder(
        [
            make_machine("w3", ntags=3, epochs=10),
            make_machine("w4", ntags=4, epochs=10),
        ]
    ).build()

    for (p_model, p_machine), (e_model, e_machine) in zip(padded, exact):
        p_mae = reconstruction_mae(p_model, p_machine)
        e_mae = reconstruction_mae(e_model, e_machine)
        assert abs(p_mae - e_mae) <= 0.25 * e_mae, (p_machine.name, p_mae, e_mae)
        p_est, e_est = _find_jax_estimator(p_model), _find_jax_estimator(e_model)
        assert len(p_est.history_["loss"]) == len(e_est.history_["loss"])
        assert np.isfinite(p_est.history_["loss"]).all()
    # width 4 == its own bucket: the padded build matches the exact
    # build to reduction-order ulps (see docstring)
    np.testing.assert_allclose(
        np.asarray(_find_jax_estimator(padded[1][0]).history_["loss"]),
        np.asarray(_find_jax_estimator(exact[1][0]).history_["loss"]),
        rtol=1e-6,
    )

    # the padded artifacts record program vs active widths
    p3 = _find_jax_estimator(padded[0][0])
    assert (p3.n_features_, p3.n_active_features_) == (4, 3)
    assert (p3.n_features_out_, p3.n_active_features_out_) == (4, 3)
    # and predictions come back at the REAL width
    X3, _ = machine_data(padded[0][1])
    assert np.asarray(padded[0][0].predict(X3)).shape[1] == 3


def test_padded_masking_matches_isolated_build_for_full_width_machine():
    """
    The mask invariant, isolated: the 4-wide machine of a fused (3, 4)
    bucket trains EXACTLY like a padded bucket of itself alone (same
    program dims, no mask) — its loss stream must not see the 3-wide
    neighbor's pad columns at all.
    """
    fused = FleetModelBuilder(
        [make_machine("w3", ntags=3), make_machine("w4", ntags=4)],
        bucket_policy="padded",
    ).build()
    alone = FleetModelBuilder(
        [make_machine("w4", ntags=4)], bucket_policy="padded"
    ).build()
    fused_est = _find_jax_estimator(fused[1][0])
    alone_est = _find_jax_estimator(alone[0][0])
    np.testing.assert_allclose(
        fused_est.history_["loss"], alone_est.history_["loss"], rtol=1e-5
    )


@pytest.mark.slow
def test_padded_windowed_family_builds_and_predicts():
    """Sequence models (windowed gathers) take the same pad/mask path.
    LSTM fleet compiles are the dominant cost (~2 min on CPU), so this
    runs in the full suite; the fast gate still covers the windowed
    pad/strip through the benchmark-shaped serving tests and the
    feedforward masked paths."""
    machines = [
        make_machine(
            "l3",
            ntags=3,
            model={
                "gordo_tpu.models.LSTMAutoEncoder": {
                    "kind": "lstm_hourglass",
                    "lookback_window": 4,
                    "epochs": 1,
                }
            },
        ),
        make_machine(
            "l4",
            ntags=4,
            model={
                "gordo_tpu.models.LSTMAutoEncoder": {
                    "kind": "lstm_hourglass",
                    "lookback_window": 4,
                    "epochs": 1,
                }
            },
        ),
    ]
    builder = FleetModelBuilder(machines, bucket_policy="padded")
    results = builder.build()
    assert len(builder.plan_) == 1
    for (model, machine), width in zip(results, (3, 4)):
        X, _ = machine_data(machine)
        out = np.asarray(model.predict(X))
        assert out.shape == (len(X) - 4 + 1, width)
        assert np.isfinite(out).all()


def test_padded_with_early_stopping_and_validation():
    """The masked variants of the gated (early stopping) epoch program and
    of the validation program compile and converge, and stop decisions
    never see pad columns: the full-width machine of a fused (3, 4) bucket
    stops where a padded bucket of itself alone stops, on the same
    losses."""
    def mk(name, ntags):
        return make_machine(
            name,
            ntags=ntags,
            epochs=6,
            validation_split=0.2,
            callbacks=[
                {
                    "gordo_tpu.models.callbacks.EarlyStopping": {
                        "monitor": "val_loss",
                        "patience": 2,
                    }
                }
            ],
        )

    fused = FleetModelBuilder(
        [mk("c3", 3), mk("c4", 4)], bucket_policy="padded"
    ).build()
    alone = FleetModelBuilder([mk("c4", 4)], bucket_policy="padded").build()
    narrow, wide = (_find_jax_estimator(model) for model, _ in fused)
    wide_alone = _find_jax_estimator(alone[0][0])
    for key in ("loss", "val_loss"):
        assert np.isfinite(narrow.history_[key]).all()
        assert 1 <= len(narrow.history_[key]) <= 6
        np.testing.assert_allclose(
            wide.history_[key], wide_alone.history_[key], rtol=1e-5
        )


# -- serving + AOT --------------------------------------------------------


def test_padded_serving_fuses_groups_and_matches_solo_predict():
    from gordo_tpu.server.fleet_serving import fleet_scorer_from_models

    machines = [make_machine("s3", ntags=3), make_machine("s4", ntags=4)]
    results = FleetModelBuilder(machines, bucket_policy="padded").build()
    models = {machine.name: model for model, machine in results}
    scorer, _, fallback = fleet_scorer_from_models(models)
    assert not fallback
    assert scorer.n_groups == 1  # the serving stack fuses like the build
    rng = np.random.default_rng(0)
    inputs = {
        "s3": rng.random((12, 3)).astype("float32"),
        "s4": rng.random((12, 4)).astype("float32"),
    }
    outs = scorer.predict(inputs)
    for name, width in (("s3", 3), ("s4", 4)):
        assert outs[name].shape == (12, width)
        est = _find_jax_estimator(models[name])
        np.testing.assert_array_equal(outs[name], est.predict(inputs[name]))
    # a request at the WRONG width must fail loudly — zero-filling a
    # short frame up to the program width would feed untrained input
    # units and return confident garbage
    with np.testing.assert_raises_regex(ValueError, "expects 3 feature"):
        scorer.predict({"s3": rng.random((5, 2)).astype("float32")})
    with np.testing.assert_raises_regex(ValueError, "expects 3 feature"):
        # the padded program width is NOT an acceptable client width
        scorer.predict({"s3": rng.random((5, 4)).astype("float32")})


def test_padded_aot_store_round_trip_and_fallback_ladder(tmp_path):
    """A padded collection's AOT export stores ONE fused program family;
    a fresh scorer warm-loads it, serves identically to the traced path,
    and a corrupt payload degrades to retrace — never an error."""
    from gordo_tpu.programs import export_serving_programs, open_store
    from gordo_tpu.programs.cache import ProgramCache
    from gordo_tpu.server.fleet_serving import fleet_scorer_from_models

    machines = [make_machine("a3", ntags=3), make_machine("a4", ntags=4)]
    FleetModelBuilder(machines, bucket_policy="padded").build(
        output_dir_base=tmp_path
    )
    report = export_serving_programs(tmp_path)
    assert report["n_programs"] >= 1
    store = open_store(tmp_path)
    assert store is not None

    from gordo_tpu import serializer

    models = {m.name: serializer.load(tmp_path / m.name) for m in machines}
    ests = {n: _find_jax_estimator(m) for n, m in models.items()}
    from gordo_tpu.server.fleet_serving import FleetScorer

    scorer = FleetScorer(ests, store=store, cache=ProgramCache("serving-test"))
    assert scorer.warm_from_store() >= 1
    rng = np.random.default_rng(1)
    inputs = {
        "a3": rng.random((16, 3)).astype("float32"),
        "a4": rng.random((16, 4)).astype("float32"),
    }
    aot_outs = scorer.predict(inputs)
    traced = FleetScorer(ests, cache=ProgramCache("serving-test-traced"))
    traced_outs = traced.predict(inputs)
    for name in inputs:
        np.testing.assert_array_equal(aot_outs[name], traced_outs[name])

    # fallback ladder: corrupt every stored payload; a fresh scorer
    # still serves (retrace), outputs unchanged
    for prog in tmp_path.glob(".programs/*.xprog"):
        prog.write_bytes(b"torn" + prog.read_bytes()[4:])
    store2 = open_store(tmp_path)
    scorer2 = FleetScorer(
        ests, store=store2, cache=ProgramCache("serving-test-corrupt")
    )
    outs2 = scorer2.predict(inputs)
    for name in inputs:
        np.testing.assert_array_equal(outs2[name], traced_outs[name])
