"""
What ``FleetTrainer.fit`` says about itself at its own boundaries: the
``train.*`` spans of its host phases (in order, each under the root
``train.fit``), the seconds and fetched bytes it books in ``fit_telemetry_``
whether tracing is on or off, and the stable scope names its compiled
programs carry for a device trace (``jax.named_scope``: metadata only).
"""

import json
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gordo_tpu.models.factories.feedforward import feedforward_hourglass
from gordo_tpu.models.factories.gru import gru_model
from gordo_tpu.models.factories.lstm import lstm_model
from gordo_tpu.observability import get_registry, tracing
from gordo_tpu.observability.tracing import TRACE_LOG_ENV_VAR
from gordo_tpu.parallel import FleetTrainer, StackedData
from gordo_tpu.parallel.checkpoint import FleetCheckpointer

F, M, N, EPOCHS, BATCH = 3, 2, 40, 3, 8

SCOPES_FILE = Path(__file__).resolve().parents[1] / "chipbench" / "scopes.json"


def fleet_data():
    X = jnp.asarray(np.random.default_rng(0).random((M, N, F)), jnp.float32)
    return StackedData(X, X, jnp.ones((M, N), jnp.float32))


def recurrent_spec(factory=lstm_model, **kwargs):
    return factory(
        n_features=F, lookback_window=4, encoding_dim=(8,),
        encoding_func=("tanh",), decoding_dim=(8,), decoding_func=("tanh",),
        fused=True, **kwargs,
    )


def traced_fit(tmp_path, monkeypatch, trainer, **fit_kwargs):
    """One fit with the span log on: [(name, parent's name, attributes)]
    in the order the spans were opened."""
    log = tmp_path / "spans.jsonl"
    monkeypatch.setenv(TRACE_LOG_ENV_VAR, str(log))
    trainer.fit(
        fleet_data(), trainer.machine_keys(M), epochs=EPOCHS, batch_size=BATCH,
        **fit_kwargs,
    )
    spans = tracing.read_spans(str(log))
    assert len({s["trace_id"] for s in spans}) == 1
    names = {s["span_id"]: s["name"] for s in spans}
    opened = sorted(spans, key=lambda s: s["start_unix_ms"])
    return [
        (s["name"], names.get(s["parent_span_id"]), s["attributes"]) for s in opened
    ]


def test_fit_spans_in_order_under_one_root(tmp_path, monkeypatch):
    trainer = FleetTrainer(feedforward_hourglass(n_features=F))
    spans = traced_fit(tmp_path, monkeypatch, trainer)
    assert [name for name, _, _ in spans] == [
        "train.fit", "train.prepare", "train.dispatch", "train.first_sync",
        "train.dispatch", "train.dispatch", "train.collect", "train.report",
    ]
    root, *phases = spans
    assert root[1] is None
    assert root[2] == {"n_machines": M, "epochs": EPOCHS, "batch_size": BATCH}
    assert {parent for _, parent, _ in phases} == {"train.fit"}
    assert [a["epoch"] for n, _, a in phases if n == "train.dispatch"] == [0, 1, 2]


def test_early_stopping_and_checkpoint_phases(tmp_path, monkeypatch):
    """``train.decide`` and ``train.checkpoint`` exist only on their paths,
    and their seconds and fetches are booked."""
    trainer = FleetTrainer(feedforward_hourglass(n_features=F))
    spans = traced_fit(
        tmp_path, monkeypatch, trainer, early_stopping_patience=5,
        checkpointer=FleetCheckpointer(str(tmp_path / "ckpt")),
        checkpoint_every=2,
    )
    names = [name for name, _, _ in spans]
    assert names.count("train.decide") == EPOCHS
    # the save after epoch 1 and the wait at the end
    assert names.count("train.checkpoint") == 2
    telemetry = trainer.fit_telemetry_
    assert telemetry["decide_s"] > 0 and telemetry["checkpoint_s"] > 0
    # the weights' fetch and one per decision; nothing is left to collect
    assert telemetry["n_host_syncs"] == 1 + EPOCHS


def test_fit_telemetry_books_phases_with_tracing_off(monkeypatch):
    """The perf_counter pairs run whether tracing is on or off, and the bytes
    are those of the shapes that crossed: the two (M,) int32 count vectors
    the fit learns of its weights (real rows, valid samples; the weights
    themselves stay on the device), then the loss and healthy histories in
    the one bulk fetch."""
    monkeypatch.delenv(TRACE_LOG_ENV_VAR, raising=False)
    counter = get_registry().counter(
        "gordo_train_host_fetch_bytes_total",
        "Bytes fits brought from the device to the host", ("path",),
    )
    before = counter.value(path="fleet")
    trainer = FleetTrainer(feedforward_hourglass(n_features=F))
    trainer.fit(fleet_data(), trainer.machine_keys(M), epochs=EPOCHS, batch_size=BATCH)
    telemetry = trainer.fit_telemetry_
    for key in ("prepare_s", "collect_s", "report_s"):
        assert telemetry[key] > 0, key
    assert telemetry["decide_s"] == telemetry["checkpoint_s"] == 0.0
    counts = 2 * M * 4
    histories = EPOCHS * M * 4 + EPOCHS * M * 1  # float32 losses, bool healthy
    assert telemetry["host_fetch_bytes"] == counts + histories
    assert telemetry["n_host_syncs"] == 2
    assert counter.value(path="fleet") - before == counts + histories
    # the phases are parts of the call, not more than it
    parts = sum(
        telemetry[k] for k in ("prepare_s", "epoch_loop_s", "report_s")
    )
    assert parts <= telemetry["wall_time_s"] + telemetry["report_s"] + 1e-3


def test_validation_split_fetches_the_counts_twice():
    """A split's cuts are host arithmetic on the first counts, and the
    scan cap needs the counts after the cut: two (M,) pairs, no (M, n)."""
    trainer = FleetTrainer(feedforward_hourglass(n_features=F))
    trainer.fit(
        fleet_data(), trainer.machine_keys(M), epochs=EPOCHS, batch_size=BATCH,
        validation_split=0.25,
    )
    telemetry = trainer.fit_telemetry_
    histories = EPOCHS * M * (4 + 1 + 4)  # losses, healthy, val losses
    assert telemetry["host_fetch_bytes"] == 2 * (2 * M * 4) + histories
    assert telemetry["n_host_syncs"] == 3


# -- the optimizer's state, made in one dispatch ------------------------------


def counting_optimizer():
    """An Adam whose ``init`` counts how often it is traced or run."""
    import optax

    inner = optax.adam(1e-3)
    calls = []

    def init(params):
        calls.append(1)
        return inner.init(params)

    return optax.GradientTransformation(init, inner.update), inner, calls


@pytest.mark.parametrize("kind", ["feedforward", "lstm"])
def test_init_opt_state_is_the_vmapped_init_compiled_once(kind):
    spec = feedforward_hourglass(n_features=F) if kind == "feedforward" else recurrent_spec()
    optimizer, inner, calls = counting_optimizer()
    trainer = FleetTrainer(spec, optimizer=optimizer)
    params = trainer.init_params(trainer.machine_keys(M), F)
    want = jax.vmap(inner.init)(params)
    got = trainer.init_opt_state(params)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.weak_type == b.weak_type
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(calls) == 1
    # the same shapes again: the compiled program, no second trace
    trainer.init_opt_state(jax.tree.map(lambda leaf: leaf + 1, params))
    assert len(calls) == 1
    # another fleet size is another program
    trainer.init_opt_state(trainer.init_params(trainer.machine_keys(M + 1), F))
    assert len(calls) == 2


@pytest.mark.parametrize(
    "kind,kwargs",
    [
        ("lstm", {"shuffle": False}),
        ("feedforward-permuting", {"shuffle": True, "row_fetch": "permute_epoch"}),
    ],
)
def test_epoch_program_text_does_not_depend_on_how_the_state_was_made(kind, kwargs):
    """The epoch program lowers to the same text from the compiled
    ``init_opt_state`` as from the eager leaf-by-leaf init it replaced (a
    changed dtype or weak type on any moment would change it)."""
    spec = recurrent_spec() if kind == "lstm" else feedforward_hourglass(n_features=F)
    trainer = FleetTrainer(spec, lookahead=0)
    keys = trainer.machine_keys(M)
    params = trainer.init_params(keys, F)
    X = jnp.zeros((M, N, F))
    epoch_fn = trainer._epoch_fn(N, BATCH, quarantine=True, **kwargs)
    texts = [
        epoch_fn.lower(
            params, opt_state, keys, X, X, jnp.ones((M, N)), jnp.ones((M,), bool)
        ).as_text()
        for opt_state in (
            trainer.init_opt_state(params),
            jax.vmap(trainer._optimizer.init)(params),
        )
    ]
    assert texts[0] == texts[1]


# -- scope names inside the compiled programs ---------------------------------

FLEET_SCOPES = (
    "fleet.order", "fleet.step", "fleet.gather", "fleet.loss_grad",
    "fleet.optimizer", "fleet.guard",
)


def op_paths(lowered):
    """Every op_name path in a lowered program's debug text."""
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))


def lowered_epoch(spec, shuffle, **kwargs):
    trainer = FleetTrainer(spec, lookahead=0)
    keys = trainer.machine_keys(M)
    params = trainer.init_params(keys, F)
    X = jnp.zeros((M, N, F))
    return trainer._epoch_fn(N, BATCH, shuffle, quarantine=True, **kwargs).lower(
        params, trainer.init_opt_state(params), keys, X, X, jnp.ones((M, N)),
        jnp.ones((M,), bool),
    )


@pytest.fixture(scope="module")
def epoch_paths():
    return {
        "lstm": op_paths(lowered_epoch(recurrent_spec(), shuffle=False)),
        "feedforward": op_paths(
            lowered_epoch(feedforward_hourglass(n_features=F), shuffle=True)
        ),
        # the fetch a TPU gets (the kernel interpreted here, on the CPU)
        "feedforward-permuting": op_paths(lowered_epoch(
            feedforward_hourglass(n_features=F), shuffle=True,
            row_fetch="permute_epoch",
        )),
    }


@pytest.mark.parametrize("kind", ["lstm", "feedforward", "feedforward-permuting"])
def test_epoch_program_names_the_trainers_scopes(epoch_paths, kind):
    for scope in FLEET_SCOPES:
        assert any(scope in path for path in epoch_paths[kind]), scope


def test_permuting_fetch_lies_under_the_gather_scope_before_the_steps(epoch_paths):
    """Once an epoch: the kernel's one call, named ``epoch_fetch``, carries
    ``fleet.gather`` and not ``fleet.step``, no step gathers any more, and
    nothing of it could be taken for a model scan (``scopes.json`` asks for
    ``/scan/`` first)."""
    paths = epoch_paths["feedforward-permuting"]
    fetch = [p for p in paths if "fleet.gather" in p]
    assert any(p.endswith("fleet.gather)/epoch_fetch/pallas_call") for p in fetch)
    assert not any("fleet.step" in p or "/scan/" in p for p in fetch)
    # the interpreter's own gathers lie inside the kernel's call
    assert not any(
        p.endswith("/gather") and "/epoch_fetch/" not in p for p in paths
    )
    assert "fleet.gather/gather" in epoch_paths["feedforward"]


def test_fused_step_kernel_lies_under_the_loss_grad_scope():
    """On the fused step loop the kernel's one call, named ``fleet_steps``,
    carries ``fleet.loss_grad`` inside ``fleet.step`` (``scopes.json``
    counts it under ``fleet.loss_grad``, Adam's update with it), nothing is
    left under ``fleet.optimizer``, and nothing of it could be taken for a
    model scan."""
    paths = op_paths(lowered_epoch(
        feedforward_hourglass(n_features=F), shuffle=True,
        row_fetch="permute_epoch", step_loop="fused",
    ))
    steps = [p for p in paths if "/fleet_steps/" in p]
    assert any(
        p.endswith("fleet.step)/fleet.loss_grad/fleet_steps/pallas_call") for p in steps
    )
    assert all("fleet.loss_grad" in p for p in steps)
    assert not any("/scan/" in p for p in steps)
    assert not any("fleet.optimizer" in p for p in paths)


def test_windowed_epoch_program_is_untouched_by_the_row_fetch(monkeypatch):
    """A windowed spec never takes the permuting fetch: asked as on a TPU
    the chooser keeps the gather, the program it then gets is the text the
    default builds, and the permuting program cannot be asked for."""
    spec = recurrent_spec()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    X = jnp.zeros((M, N, F))
    data = StackedData(X, X, jnp.ones((M, N)))
    chosen = FleetTrainer(spec, lookahead=0)._choose_row_fetch(data, BATCH, None)
    assert chosen == "gather"
    monkeypatch.undo()
    default = lowered_epoch(spec, shuffle=False).as_text()
    assert lowered_epoch(spec, shuffle=False, row_fetch=chosen).as_text() == default
    with pytest.raises(ValueError, match="non-windowed"):
        lowered_epoch(spec, shuffle=False, row_fetch="permute_epoch")


def test_lstm_scan_is_a_path_forward_and_backward(epoch_paths):
    scans = [p for p in epoch_paths["lstm"] if "/scan/" in p]
    forward = [p for p in scans if "transpose(" not in p]
    backward = [p for p in scans if "transpose(" in p]
    assert forward and backward
    for layer in ("FusedLSTMLayer_0", "FusedLSTMLayer_1"):
        assert any(f"{layer}/scan/" in p for p in forward), layer
        assert any(f"{layer}/scan/" in p for p in backward), layer
    # and nests under the trainer's own scope
    assert all("fleet.loss_grad" in p for p in scans)
    assert not any("/scan/" in p for p in epoch_paths["feedforward"])


#: the parts of a time step ``lstm_time_scan``'s loops name, each read by
#: one metric of ``BENCHMARK.json`` (``chipbench/step_scopes.py``)
STEP_SCOPES = {
    "lstm.fwd.gates": "lstm_fwd_gates_ms.fit",
    "lstm.fwd.cell": "lstm_fwd_cell_ms.fit",
    "lstm.bwd.read": "lstm_bwd_read_ms.fit",
    "lstm.bwd.cell": "lstm_bwd_cell_ms.fit",
    "lstm.bwd.products": "lstm_bwd_products_ms.fit",
}


def test_every_op_of_a_time_step_carries_one_step_scope():
    """In the COMPILED program, where the loop body's ops carry their full
    path (in the lowered text they sit in a function of their own): each
    op of a time step holds exactly one step scope, forward ones outside
    ``transpose(`` and backward ones under it, in both layers; and no step
    scope can be taken for a fragment ``chipbench/scopes.json`` selects."""
    text = lowered_epoch(recurrent_spec(), shuffle=False).compile().as_text()
    step = [
        p for p in set(re.findall(r'op_name="([^"]+)"', text))
        if "/scan/while/body/closed_call/" in p
    ]
    assert step
    for path in step:
        held = [s for s in STEP_SCOPES if f"{s}/" in path]
        assert len(held) == 1, path
        assert held[0].startswith("lstm.bwd.") == ("transpose(" in path), path
    for layer in ("FusedLSTMLayer_0", "FusedLSTMLayer_1"):
        for scope in STEP_SCOPES:
            assert any(f"{layer}/scan/" in p and f"{scope}/" in p for p in step), (
                layer, scope,
            )
    for scope in STEP_SCOPES:
        assert not any(part in scope for part in ("fleet.", "/scan/", "transpose("))


_FWD = (
    "jit(machine_epoch)/vmap(fleet.step)/while/body/closed_call/fleet.loss_grad/"
    "jvp(LSTMNet)/FusedLSTMLayer_{}/scan/"
)
_BWD = _FWD.replace("jvp(LSTMNet)", "transpose(fleet.loss_grad)/jvp(LSTMNet)")
_STEP = "while/body/closed_call/"
#: self seconds by path of a traced stretch of 2 calls x 3 epochs
TRACED_PATHS = {
    _FWD.format(0) + _STEP + "lstm.fwd.gates/dot_general": 0.030,
    _FWD.format(1) + _STEP + "lstm.fwd.gates/dot_general": 0.012,
    _FWD.format(1) + _STEP + "lstm.fwd.cell/dynamic_update_slice": 0.009,
    _BWD.format(0) + _STEP + "lstm.bwd.read/gather": 0.006,
    _BWD.format(0) + _STEP + "lstm.bwd.cell/transpose(jvp())/mul": 0.0105,
    _BWD.format(1) + _STEP + "lstm.bwd.cell/transpose(jvp())/mul": 0.0045,
    _BWD.format(1) + _STEP + "lstm.bwd.products/dot_general": 0.024,
    _FWD.format(0) + "while": 0.003,
    "jit(machine_epoch)/vmap(fleet.step)/while/body/closed_call/fleet.gather/gather": 0.5,
}
#: the parent's program: the same loops with no step scope
UNNAMED_PATHS = {
    _FWD.format(0) + _STEP + "dot_general": 0.042,
    _BWD.format(0) + _STEP + "gather": 0.006,
}


@pytest.mark.parametrize(
    "metric,by_path,expected,layers",
    [
        ("lstm_fwd_gates_ms.fit", TRACED_PATHS, 7.0, (0, 1)),
        ("lstm_fwd_cell_ms.fit", TRACED_PATHS, 1.5, (1,)),
        ("lstm_bwd_read_ms.fit", TRACED_PATHS, 1.0, (0,)),
        ("lstm_bwd_cell_ms.fit", TRACED_PATHS, 2.5, (0, 1)),
        ("lstm_bwd_products_ms.fit", TRACED_PATHS, 4.0, (1,)),
        ("lstm_bwd_read_ms.fit", UNNAMED_PATHS, None, ()),
    ],
    ids=[*STEP_SCOPES, "absent"],
)
def test_step_scope_metric_reads_ms_an_epoch(capsys, metric, by_path, expected, layers):
    """A reader sums the traced paths that hold its scope over the traced
    epochs and tells each layer's share on stderr; where the program names
    no such scope (the parent's) it reads nothing and raises nothing."""
    from chipbench import loading

    ctx = {
        "scope_reduce": {"by_path": by_path},
        "traced": {"calls": [{}, {}], "epochs_per_call": 3},
    }
    value = loading.metric_reader("layer_metrics", metric)(ctx)
    err = capsys.readouterr().err
    if expected is None:
        assert value is None and not err
        return
    assert value == pytest.approx(expected)
    told = re.findall(r"FusedLSTMLayer_(\d+)", err)
    assert tuple(sorted(int(k) for k in told)) == layers


@pytest.mark.parametrize(
    "factory,kwargs,module",
    [(gru_model, {}, "FusedGRULayer_0"), (lstm_model, {"schedule": "stacked"}, "LSTMNet._stacked_scan")],
    ids=["gru", "lstm-stacked"],
)
def test_other_fused_scans_carry_the_scope(factory, kwargs, module):
    paths = op_paths(lowered_epoch(recurrent_spec(factory, **kwargs), shuffle=False))
    assert any(f"{module}/scan/" in p for p in paths)


def test_validation_program_names_its_gather():
    trainer = FleetTrainer(recurrent_spec(), lookahead=0)
    keys = trainer.machine_keys(M)
    params = trainer.init_params(keys, F)
    X = jnp.zeros((M, N, F))
    paths = op_paths(trainer._val_fn(N, BATCH).lower(params, X, X, jnp.ones((M, N))))
    for scope in ("fleet.gather", "fleet.val_loss"):
        assert any(scope in p for p in paths), scope


def test_scopes_change_no_arithmetic(monkeypatch):
    """The scopes are metadata: with ``jax.named_scope`` made a no-op the
    lowered epoch program is the same text but for its locations."""
    import contextlib

    def stripped(lowered):
        text = lowered.as_text()
        return re.sub(r"\s*loc\(.*?\)$", "", text, flags=re.M)

    with_scopes = stripped(lowered_epoch(recurrent_spec(), shuffle=False))
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    without = stripped(lowered_epoch(recurrent_spec(), shuffle=False))
    assert with_scopes == without


def test_every_scope_of_the_benchmark_is_one_the_program_names(epoch_paths):
    """``chipbench/scopes.json`` sums device time by path fragments; each has
    to be one that the lowered programs above really carry."""
    table = json.loads(SCOPES_FILE.read_text())
    every = set().union(*epoch_paths.values())
    for scope in table["scopes"]:
        holds, lacks = scope["holds"], scope.get("lacks", [])
        assert any(
            all(h in p for h in holds) and not any(l in p for l in lacks)
            for p in every
        ), scope["name"]
    assert {s["name"] for s in table["scopes"] if s["name"].startswith("fleet.")} == set(
        FLEET_SCOPES
    )
