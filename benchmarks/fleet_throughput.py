"""
Fleet-training throughput harness: models-trained/hour through the
stacked-vmap FleetModelBuilder vs the sequential per-machine ModelBuilder
loop — the BASELINE.json north-star axis ("1000-Machine batch build
vmap'd over v5e-16"), runnable at any size.

Prints one JSON object with both rates and the speedup.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gordo_tpu.utils import enable_compile_cache

enable_compile_cache()

CONFIG_TPL = """
  - name: fleet-m{i}
    dataset:
      type: RandomDataset
      tags: [{tags}]
      target_tag_list: [{tags}]
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-03T00:00:00+00:00'
      asset: gra
    model:
      gordo_tpu.models.anomaly.DiffBasedAnomalyDetector:
        base_estimator:
          gordo_tpu.models.{cls}:
            kind: {kind}
            epochs: {epochs}{extra}
"""

# BASELINE configs beyond the feedforward default: the LSTM family the
# reference ships, plus the Transformer/TCN backends (BASELINE.json
# config #5) so they are measured as WORKLOADS, not just factories.
KINDS = {
    "feedforward": ("AutoEncoder", "feedforward_hourglass", ""),
    "lstm": ("LSTMAutoEncoder", "lstm_hourglass", "\n            lookback_window: 12"),
    "gru": ("GRUAutoEncoder", "gru_hourglass", "\n            lookback_window: 12"),
    "transformer": (
        "TransformerAutoEncoder",
        "transformer_model",
        "\n            lookback_window: 12\n            d_model: 32\n            n_layers: 2",
    ),
    "tcn": (
        "TCNAutoEncoder",
        "tcn_model",
        "\n            lookback_window: 12\n            channels: [32, 32]",
    ),
}


def make_machines(n: int, epochs: int, buckets: int = 1, kind: str = "feedforward"):
    """n Machines spread over `buckets` architecture buckets (by tag count)."""
    import yaml

    from gordo_tpu.workflow.config_elements.normalized_config import NormalizedConfig

    cls, kind_name, extra = KINDS[kind]
    blocks = []
    for i in range(n):
        n_tags = 4 + (i % buckets)  # distinct n_features -> distinct bucket
        tags = ", ".join(f"tag-{t}" for t in range(n_tags))
        blocks.append(
            CONFIG_TPL.format(
                i=i, epochs=epochs, tags=tags, cls=cls, kind=kind_name, extra=extra
            )
        )
    config = yaml.safe_load("machines:" + "".join(blocks))
    return NormalizedConfig(config, project_name="bench").machines


def reconstruction_mae(model, machine) -> float:
    """Mean |y - reconstruction| of a built model on its own training data."""
    import numpy as np

    from gordo_tpu.data import _get_dataset

    X, y = _get_dataset(machine.dataset.to_dict()).get_data()
    predicted = model.predict(X)
    target = np.asarray(y)[-len(predicted):]
    return float(np.abs(np.asarray(predicted) - target).mean())


def _ms_summary(times):
    """mean/p50/p99 of a list of millisecond latencies."""
    ordered = sorted(times)
    return {
        "mean_ms": round(sum(ordered) / len(ordered), 3),
        "p50_ms": round(ordered[len(ordered) // 2], 3),
        "p99_ms": round(ordered[max(0, int(0.99 * len(ordered)) - 1)], 3),
    }


def precision_sweep(precisions, n_machines=8, epochs=5, rounds=30):
    """
    Build the SAME fleet once per precision mode (float32 always first —
    it is the parity baseline every other arm compares against) and
    report, per arm: build rate, the builder's own calibration decisions
    (n_bf16 / fallbacks / worst per-machine calibration MAE delta, from
    ``precision_decisions_`` — the numbers build_report.json persists),
    warm serving-dispatch latency through a :class:`FleetScorer`, and the
    worst per-machine SERVED MAE delta vs the float32 arm's outputs on a
    fixed input. Served outputs must come back float32 regardless of the
    arm (the in-program upcast contract); that is asserted, not assumed.

    On CPU the bf16 arm measures the dispatch/keying overhead only — XLA
    emulates bf16 math, so the wins this sweep exists to show (halved
    resident params, halved HBM traffic) are TPU-expected, and the MAE
    deltas are the honest number a CPU run CAN measure.
    """
    import numpy as np

    from gordo_tpu.builder.fleet_build import (
        FleetModelBuilder,
        _find_jax_estimator,
    )
    from gordo_tpu.server.fleet_serving import FleetScorer

    modes = [m for m in dict.fromkeys(precisions) if m != "float32"]
    modes.insert(0, "float32")

    machines = make_machines(n_machines, epochs)
    rng = np.random.default_rng(7)
    X = rng.random((64, 4)).astype("float32")

    arms = []
    baseline_outputs = None
    for mode in modes:
        start = time.perf_counter()
        builder = FleetModelBuilder(machines, precision=mode)
        results = builder.build()
        build_s = time.perf_counter() - start

        ests = {}
        for model, machine in results:
            est = _find_jax_estimator(model)
            if est is not None:
                ests[machine.name] = est
        inputs = {name: X for name in ests}
        scorer = FleetScorer(ests)
        outputs = scorer.predict(inputs)  # warm: trace+compile once
        times = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            outputs = scorer.predict(inputs)
            times.append((time.perf_counter() - t0) * 1000)
        assert all(
            np.asarray(v).dtype == np.float32 for v in outputs.values()
        ), "served outputs must be float32 (in-program upcast contract)"
        if baseline_outputs is None:
            baseline_outputs = outputs

        decisions = builder.precision_decisions_
        cal_deltas = [
            rec["mae_delta"]
            for rec in decisions.values()
            if rec.get("mae_delta") is not None
        ]
        served_deltas = [
            float(np.abs(np.asarray(v) - np.asarray(baseline_outputs[k])).mean())
            for k, v in outputs.items()
        ]
        arms.append(
            {
                "precision": mode,
                "fleet_build_s": round(build_s, 2),
                "fleet_models_per_hour": round(n_machines / build_s * 3600, 1),
                "n_machines_bf16": sum(
                    1 for r in decisions.values() if r["precision"] == "bf16"
                ),
                "n_machines_float32_fallback": sum(
                    1 for r in decisions.values() if r["precision"] == "float32"
                ),
                "calibration_worst_machine_mae_delta": (
                    float(f"{max(cal_deltas):.3g}") if cal_deltas else None
                ),
                "dispatch": {**_ms_summary(times), "rounds": rounds},
                "served_worst_machine_mae_delta_vs_float32": float(
                    f"{max(served_deltas):.3g}"
                ),
            }
        )
    return arms


def donation_arms(n_machines=8, epochs=5, rounds=50):
    """
    Warm serving-dispatch latency with buffer donation off (the pinned
    default) vs on (``GORDO_DONATE=1``, read once at
    :class:`FleetScorer` construction), through the SAME built fleet.
    The arms' outputs are cross-checked: bit-equality AND max abs
    delta. Donation is opt-in precisely because the alias annotation
    alone shifts XLA's fusion — the measured delta here (~1e-7 on CPU,
    where the donation itself is declined) is the documented reason the
    default stays off; the HBM-reuse latency win is TPU-expected.
    """
    import numpy as np

    from gordo_tpu.builder.fleet_build import (
        FleetModelBuilder,
        _find_jax_estimator,
    )
    from gordo_tpu.server.fleet_serving import FleetScorer

    machines = make_machines(n_machines, epochs)
    results = FleetModelBuilder(machines).build()
    ests = {}
    for model, machine in results:
        est = _find_jax_estimator(model)
        if est is not None:
            ests[machine.name] = est
    rng = np.random.default_rng(11)
    X = rng.random((64, 4)).astype("float32")
    inputs = {name: X for name in ests}

    arms = []
    baseline_outputs = None
    saved = os.environ.get("GORDO_DONATE")
    try:
        for donate in (False, True):
            os.environ["GORDO_DONATE"] = "1" if donate else "0"
            scorer = FleetScorer(ests)
            outputs = scorer.predict(inputs)  # warm
            times = []
            for _ in range(rounds):
                t0 = time.perf_counter()
                outputs = scorer.predict(inputs)
                times.append((time.perf_counter() - t0) * 1000)
            if baseline_outputs is None:
                baseline_outputs = outputs
            delta = max(
                float(
                    np.abs(
                        np.asarray(v) - np.asarray(baseline_outputs[k])
                    ).max()
                )
                for k, v in outputs.items()
            )
            arms.append(
                {
                    "donate": donate,
                    "dispatch": {**_ms_summary(times), "rounds": rounds},
                    "outputs_bitequal_vs_donate_off": bool(
                        all(
                            np.array_equal(v, baseline_outputs[k])
                            for k, v in outputs.items()
                        )
                    ),
                    "outputs_max_abs_delta_vs_donate_off": float(
                        f"{delta:.3g}"
                    ),
                }
            )
    finally:
        if saved is None:
            os.environ.pop("GORDO_DONATE", None)
        else:
            os.environ["GORDO_DONATE"] = saved
    return arms


def prefetch_sweep(depths, n_machines=8, n_rows=2048, n_features=8,
                   epochs=12, batch_size=64):
    """
    Sweep ``prefetch_depth`` over a direct :class:`FleetTrainer` fit:
    depth 0 is the historical single-``device_put`` baseline; depth K
    slices the stacked tensors' host->device transfer
    (``transfer.device_put_sliced``). Prefetching moves bytes, never math, so
    loss histories are cross-checked for bit-equality against depth 0.
    ``transfer_overlap_ratio`` is the wall-time fraction the pipelining
    recovered vs depth 0 (clamped at 0 — on CPU "transfer" is a memcpy
    and the ratio is expected to hover near zero; the overlap win is
    TPU-expected, where the slices stream over PCIe behind compute).
    """
    import numpy as np

    from gordo_tpu.models.factories.feedforward import feedforward_hourglass
    from gordo_tpu.parallel.fleet import FleetTrainer, StackedData

    rng = np.random.default_rng(3)
    Xs = [rng.random((n_rows, n_features)).astype("float32")
          for _ in range(n_machines)]
    spec = feedforward_hourglass(n_features=n_features)

    # warm the jit cache before timing: the first fit pays compilation,
    # which would otherwise be billed to the depth-0 baseline and
    # masquerade as transfer overlap in every later arm's ratio
    warm_data = StackedData.from_ragged(Xs, [x.copy() for x in Xs])
    warm_trainer = FleetTrainer(spec)
    warm_trainer.fit(
        warm_data,
        warm_trainer.machine_keys(n_machines),
        epochs=min(2, epochs),
        batch_size=batch_size,
    )

    rows = []
    baseline_losses = None
    baseline_wall = None
    # depth 0 runs first: every row's overlap ratio and bit-equality
    # check compares against a real baseline
    for depth in sorted(depths):
        start = time.perf_counter()
        data = StackedData.from_ragged(
            Xs, [x.copy() for x in Xs], prefetch_depth=depth
        )
        trainer = FleetTrainer(spec)
        keys = trainer.machine_keys(n_machines)
        _, losses = trainer.fit(data, keys, epochs=epochs,
                                batch_size=batch_size)
        wall = time.perf_counter() - start
        if baseline_losses is None:
            baseline_losses, baseline_wall = losses, wall
        t = trainer.fit_telemetry_
        rows.append(
            {
                "prefetch_depth": depth,
                "wall_time_s": round(wall, 3),
                "steady_state_sensor_timesteps_per_s": t[
                    "steady_state_sensor_timesteps_per_s"
                ],
                "transfer_overlap_ratio": round(
                    max(0.0, 1.0 - wall / baseline_wall), 4
                ),
                "losses_bitequal_vs_depth0": bool(
                    np.array_equal(losses, baseline_losses)
                ),
            }
        )
    return rows


MFU_NOTE = (
    "analytic estimate: FLOPs are counted from kernel sizes (2 x weight "
    "elements per sample, x lookback for windowed specs, training = 3 x fwd) "
    "and CV folds are approximated as 1.5 x the final fit's executed epochs "
    "— fold fits early-stop independently, so the true fold epoch count may "
    "differ"
)

_measured_peak_cache: dict = {}


def measured_peak_flops(device) -> float:
    """
    Achievable dense-matmul FLOP/s on this device, measured by timing a
    2048^3 f32 matmul (best of 5 warm reps). Used as the MFU denominator
    off-TPU, where no spec-sheet peak is tabulated: a measured achievable
    peak is honest where a guessed spec number would not be.
    """
    if device in _measured_peak_cache:
        return _measured_peak_cache[device]
    import jax
    import jax.numpy as jnp

    n = 2048
    a = jnp.ones((n, n), jnp.float32)
    b = jnp.ones((n, n), jnp.float32)
    # called once per benchmark invocation; the jit-and-measure shape is
    # the point of the probe
    f = jax.jit(lambda x, y: x @ y)  # lint: disable=retrace-risk
    f(a, b).block_until_ready()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        f(a, b).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    peak = 2.0 * n**3 / best
    _measured_peak_cache[device] = peak
    return peak


def fleet_mfu(results, build_seconds: float, device) -> "tuple[float, str]":
    """
    Aggregate model-FLOPs utilization of the whole fleet build: analytic
    training FLOPs actually executed across every machine's CV folds and
    final fit, over wall-clock x chip peak. This is the measured form of
    the design's roofline argument (docs/performance.md: one tiny model
    cannot fill the MXU — the FLEET axis is what scales arithmetic
    intensity), so it must rise with --machines.

    Returns (mfu, peak_source): peak is the tabulated bf16 spec number on
    TPU, or a measured dense-matmul rate elsewhere (measured_peak_flops).
    Analytic counts: dense fwd ~= 2 x kernel-weight elements per sample
    (x lookback for windowed specs); training ~= 3 x fwd;
    TimeSeriesSplit(3) fold train sizes sum to ~1.5 x n_samples, the
    final fit adds 1.0 x — see MFU_NOTE for the approximation caveats.
    """
    from bench import PEAK_BF16_FLOPS

    from gordo_tpu.builder.fleet_build import _find_jax_estimator

    peak = PEAK_BF16_FLOPS.get(device.device_kind)
    peak_source = "tabulated_bf16_peak"
    if peak is None:
        peak = measured_peak_flops(device)
        peak_source = "measured_matmul_f32"
    import jax

    total = 0.0
    for model, _machine in results:
        est = _find_jax_estimator(model)
        if est is None or not hasattr(est, "params_"):
            continue
        kernel_elems = sum(
            leaf.size for leaf in jax.tree.leaves(est.params_)
            if getattr(leaf, "ndim", 0) >= 2
        )
        samples = est.history_["params"]["samples"]
        # EXECUTED epochs (early stopping may end before the configured
        # budget), not the configured count
        epochs = len(est.history_["loss"])
        fwd = 2.0 * kernel_elems
        # windowed specs re-apply their kernels per lookback timestep
        lookback = getattr(est, "lookback_window", None)
        if lookback:
            fwd *= float(lookback)
        total += (1.0 + 1.5) * samples * epochs * 3.0 * fwd
    return total / build_seconds / peak, peak_source


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--machines", type=int, default=16)
    parser.add_argument("--epochs", type=int, default=5)
    parser.add_argument(
        "--sequential-sample",
        type=int,
        default=4,
        help="How many machines to time with the sequential builder "
        "(extrapolated; building all sequentially is the slow case)",
    )
    parser.add_argument(
        "--buckets",
        type=int,
        default=1,
        help="Spread machines over this many architecture buckets "
        "(distinct n_features), exercising the bucketing scheduler.",
    )
    parser.add_argument(
        "--kind",
        choices=sorted(KINDS),
        default="feedforward",
        help="Model family to build (BASELINE config #5 covers "
        "transformer/tcn).",
    )
    parser.add_argument(
        "--precision-sweep",
        default="",
        metavar="MODE[,MODE...]",
        help="Comma-separated precision modes (e.g. float32,bf16): build "
        "the same fleet once per mode and report build rate, calibration "
        "decisions, warm dispatch latency, and per-machine served MAE "
        "delta vs the float32 arm ('' disables it).",
    )
    parser.add_argument(
        "--prefetch-sweep",
        default="",
        metavar="K[,K...]",
        help="Comma-separated prefetch_depth values (e.g. 0,2) for the "
        "direct FleetTrainer transfer-pipelining sweep: wall time, "
        "steady-state throughput, transfer_overlap_ratio vs depth 0, "
        "and loss bit-equality ('' disables it).",
    )
    parser.add_argument(
        "--donation-arms",
        action="store_true",
        help="Measure warm serving dispatch with GORDO_DONATE off vs on "
        "through the same built fleet, cross-checking output "
        "bit-equality (CPU pins the no-regression floor; the HBM-reuse "
        "win is TPU-expected).",
    )
    args = parser.parse_args()

    import jax

    from gordo_tpu.builder.build_model import ModelBuilder
    from gordo_tpu.builder.fleet_build import FleetModelBuilder

    device = jax.devices()[0]
    machines = make_machines(args.machines, args.epochs, args.buckets, args.kind)

    start = time.perf_counter()
    fleet_builder = FleetModelBuilder(machines)
    fleet_results = fleet_builder.build()
    fleet_s = time.perf_counter() - start

    prec_sweep = None
    if args.precision_sweep:
        prec_sweep = precision_sweep(
            [m.strip() for m in args.precision_sweep.split(",") if m.strip()]
        )
    pf_sweep = None
    if args.prefetch_sweep:
        pf_sweep = prefetch_sweep(
            [int(d) for d in args.prefetch_sweep.split(",")]
        )
    donate_arms = donation_arms() if args.donation_arms else None

    seq_machines = make_machines(
        args.sequential_sample, args.epochs, args.buckets, args.kind
    )
    start = time.perf_counter()
    seq_results = [ModelBuilder(m).build() for m in seq_machines]
    seq_s_per_machine = (time.perf_counter() - start) / len(seq_machines)

    # MAE parity: the SAME machine built both ways must reconstruct its
    # training data equally well (the product promise of the fleet path)
    fleet_model, fleet_machine = fleet_results[0]
    seq_model, seq_machine = seq_results[0]
    fleet_mae = reconstruction_mae(fleet_model, fleet_machine)
    seq_mae = reconstruction_mae(seq_model, seq_machine)

    fleet_rate = args.machines / fleet_s * 3600
    seq_rate = 3600 / seq_s_per_machine
    mfu, peak_source = fleet_mfu(fleet_results, fleet_s, device)

    # -- internal telemetry (gordo_tpu.observability): the system's OWN
    # numbers for the same run, so external (this harness) and internal
    # (registry + telemetry report) throughput can be cross-checked in
    # the results JSON — a drift between them is itself a finding
    from gordo_tpu.observability import get_registry
    from gordo_tpu.observability.attribution import phase_attribution_block
    from gordo_tpu.observability.tracing import measure_overhead

    snapshot = get_registry().snapshot()

    def _counter_total(name: str) -> float:
        return sum(
            s["value"] for s in snapshot.get(name, {}).get("series", [])
        )

    report = fleet_builder.telemetry_report_ or {}
    bucket_fits = [
        b.get("fit") or {} for b in report.get("buckets", [])
    ]
    fit_rates = [
        f["sensor_timesteps_per_s"]
        for f in bucket_fits
        if f.get("sensor_timesteps_per_s") is not None
    ]
    internal = {
        "internal_models_per_hour": report.get("models_per_hour"),
        "internal_wall_time_s": report.get("wall_time_s"),
        # max over the buckets' FINAL-fit rates (one final fit per
        # bucket); null — not a fake 0.0 — when no fit telemetry landed
        "internal_max_bucket_fit_sensor_timesteps_per_s": (
            max(fit_rates) if fit_rates else None
        ),
        "internal_compile_time_s": sum(
            f.get("compile_time_s") or 0.0 for f in bucket_fits
        ),
        "internal_peak_hbm_bytes": (report.get("device_memory") or {}).get(
            "peak_bytes_in_use"
        ),
        "registry_train_epochs_total": _counter_total(
            "gordo_train_epochs_total"
        ),
        "registry_train_sensor_timesteps_total": _counter_total(
            "gordo_train_sensor_timesteps_total"
        ),
        "registry_build_models_total": _counter_total(
            "gordo_build_models_total"
        ),
    }
    print(
        json.dumps(
            {
                "bench_schema_version": 1,
                **internal,
                "machines": args.machines,
                "buckets": args.buckets,
                "epochs": args.epochs,
                "kind": args.kind,
                # per-precision-mode build/calibration/dispatch arms,
                # float32 first (the parity baseline)
                **({"precision_sweep": prec_sweep} if prec_sweep else {}),
                # transfer-pipelining arms (prefetch_depth sweep) and the
                # donation on/off bit-equality + latency arms
                **({"prefetch_sweep": pf_sweep} if pf_sweep else {}),
                **({"donation_arms": donate_arms} if donate_arms else {}),
                "platform": device.platform,
                "device_kind": device.device_kind,
                "fleet_build_s": round(fleet_s, 2),
                "fleet_models_per_hour": round(fleet_rate, 1),
                "sequential_models_per_hour": round(seq_rate, 1),
                "speedup": round(fleet_rate / seq_rate, 2),
                "fleet_reconstruction_mae": round(fleet_mae, 5),
                "sequential_reconstruction_mae": round(seq_mae, 5),
                # significant figures, not fixed decimals: tiny test
                # machines put fleet MFU in the 1e-7 range on a 394-TFLOP/s
                # chip, and fixed rounding would floor that to 0.0
                "mfu": float(f"{mfu:.3g}"),
                "mfu_peak_source": peak_source,
                "mfu_note": MFU_NOTE,
                # span enter/exit cost per regime (disabled/sampled-out/
                # recording): with per-epoch train.dispatch spans, the
                # per-epoch tracing tax is one of these numbers — the
                # justification for the sampling default
                "tracing_overhead": measure_overhead(samples=1000),
                # train-plane phase ledger: device dispatch vs transfer
                # seconds for the whole build, host/device split
                # included — the cost-seam view of the same run
                "phase_attribution": phase_attribution_block(
                    snapshot=snapshot
                ),
            }
        )
    )


if __name__ == "__main__":
    main()
