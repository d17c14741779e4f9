"""
Headline benchmark: LSTM-AE training throughput on TPU.

Metric (BASELINE.json north star): sensor-timesteps/sec/chip for the
LSTM autoencoder — how many (timestep x sensor) readings the training loop
consumes per second: windows x lookback x n_sensors x epochs / wall_time.

vs_baseline: the same architecture/workload trained with torch CPU (the
closest runnable stand-in for the reference's TF/Keras-per-pod engine —
TF is not installed and no GPU exists in this image; the reference ships no
published numbers, see BASELINE.md). Measured per-step on the identical
workload.

Process model: the parent stays off JAX (a process that has touched JAX
holds the chip, and a child that needs it then fails or hangs) and runs

  phase 1  torch-CPU baseline, in-process (~1 min)
  phase 2  the TPU measurement in a subprocess with a hard timeout, and
           one bounded retry if that attempt dies

There is no CPU fallback: a child that finds no ``tpu`` platform exits
non-zero, and when the chip attempt fails the run exits non-zero with no
headline value — a CPU number is never written under this metric's name.

On success prints exactly ONE JSON line on stdout; diagnostics go to stderr.
"""

import json
import os
import subprocess
import sys
import time

# workload: "50-tag plant" LSTM-AE (BASELINE.json config #2/#3 shape)
N_SENSORS = 50
LOOKBACK = 64
N_TIMESTEPS = 16384
BATCH = 512
EPOCHS = 3
ENC = (128, 64)
DEC = (64, 128)

START = time.time()
BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", "1500"))


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def remaining() -> float:
    return BUDGET_S - (time.time() - START)


def bench_jax(n_timesteps: int, epochs: int) -> dict:
    import jax

    dev = jax.devices()[0]
    log(f"jax device: {dev.device_kind} ({dev.platform})")
    if dev.platform != "tpu":
        # this is a TPU metric: no accelerator, no number
        raise SystemExit(
            f"bench.py measures on a TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind})"
        )

    import numpy as np

    from gordo_tpu.models.factories.lstm import lstm_model
    from gordo_tpu.parallel.fleet import FleetTrainer, StackedData
    from gordo_tpu.utils import enable_compile_cache

    # persistent XLA compile cache: repeat runs skip the warmup compiles
    enable_compile_cache()

    rng = np.random.default_rng(0)
    X = rng.standard_normal((n_timesteps, N_SENSORS)).astype("float32")
    data = StackedData.from_ragged([X], [X.copy()])

    spec = lstm_model(
        n_features=N_SENSORS,
        lookback_window=LOOKBACK,
        encoding_dim=ENC,
        encoding_func=("tanh",) * len(ENC),
        decoding_dim=DEC,
        decoding_func=("tanh",) * len(DEC),
        dtype="bfloat16",
        # one hand-written time scan a layer, the gates' kernels side by
        # side (specs.FusedLSTMLayer); parity pinned by
        # tests/test_fused_lstm.py
        fused=True,
        # schedule-only time-scan unroll for on-chip sweeps
        time_unroll=int(os.environ.get("BENCH_TIME_UNROLL", "1")),
        # "layer" (one scan a layer) or "stacked" (one streaming
        # scan); math is identical either way (tests/test_fused_lstm.py)
        schedule=os.environ.get("BENCH_SCHEDULE", "layer"),
    )
    trainer = FleetTrainer(spec, lookahead=0, donate=True)
    keys = trainer.machine_keys(1)

    # compile + warmup
    t0 = time.time()
    params, _ = trainer.fit(data, keys, epochs=1, batch_size=BATCH)
    compile_time = time.time() - t0
    log(f"warmup epoch (incl. compile): {compile_time:.1f}s")

    t0 = time.time()
    params, losses = trainer.fit(
        data, keys, epochs=epochs, batch_size=BATCH, params=params
    )
    jax.block_until_ready(params)
    train_time = time.time() - t0
    fit_telemetry = getattr(trainer, "fit_telemetry_", {}) or {}

    n_windows = n_timesteps - LOOKBACK + 1
    sensor_timesteps = n_windows * LOOKBACK * N_SENSORS * epochs
    rate = sensor_timesteps / train_time
    log(
        f"jax: {epochs} epochs x {n_windows} windows in {train_time:.2f}s "
        f"-> {rate:,.0f} sensor-timesteps/s"
    )
    return {
        "rate": rate,
        "train_time": train_time,
        "n_timesteps": n_timesteps,
        "epochs": epochs,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        # the system's own numbers for the timed fit: how many host
        # round-trips it paid and what the host's dispatches cost
        "n_host_syncs": fit_telemetry.get("n_host_syncs"),
        "dispatch_overhead_s": fit_telemetry.get("dispatch_overhead_s"),
        "internal_steady_state_epoch_s": fit_telemetry.get(
            "steady_state_epoch_s"
        ),
    }


def bench_torch_cpu(step_budget: int = 6) -> float:
    """Per-step-extrapolated torch-CPU rate on the identical workload."""
    import torch

    torch.manual_seed(0)
    torch.set_num_threads(max(1, torch.get_num_threads()))

    class RefLSTMAE(torch.nn.Module):
        def __init__(self):
            super().__init__()
            dims = [N_SENSORS, *ENC, *DEC]
            self.layers = torch.nn.ModuleList(
                [torch.nn.LSTM(dims[i], dims[i + 1], batch_first=True)
                 for i in range(len(dims) - 1)]
            )
            self.head = torch.nn.Linear(dims[-1], N_SENSORS)

        def forward(self, x):
            for lstm in self.layers:
                x, _ = lstm(x)
            return self.head(x[:, -1, :])

    model = RefLSTMAE()
    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    loss_fn = torch.nn.MSELoss()

    xb = torch.randn(BATCH, LOOKBACK, N_SENSORS)
    yb = torch.randn(BATCH, N_SENSORS)

    # warmup
    loss = loss_fn(model(xb), yb)
    loss.backward()
    opt.step()
    opt.zero_grad()

    t0 = time.time()
    for _ in range(step_budget):
        loss = loss_fn(model(xb), yb)
        loss.backward()
        opt.step()
        opt.zero_grad()
    per_step = (time.time() - t0) / step_budget
    rate = (BATCH * LOOKBACK * N_SENSORS) / per_step
    log(f"torch-cpu: {per_step * 1000:.0f} ms/step -> {rate:,.0f} sensor-timesteps/s")
    return rate


# Per-chip peak dense-matmul FLOP/s (bf16), keyed by jax device_kind.
# Only kinds with a cited source; a device that is not here is an error.
PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    # (jax reports a v5e chip as device_kind "TPU v5 lite")
    "TPU v5 lite": 197e12,
}


def training_flops_per_window() -> float:
    """
    Analytic FLOPs for one lookback window through one LSTM-AE training step.

    Per LSTM layer per timestep the 4 gate matmuls dominate:
    2 * (in_dim + hidden) * 4*hidden FLOPs per sample. The dense head runs on
    the final timestep only. Backward for matmul-dominated nets is ~2x the
    forward, so a training step is ~3x forward FLOPs.
    """
    dims = [N_SENSORS, *ENC, *DEC]
    fwd_per_timestep = sum(
        8 * dims[i + 1] * (dims[i] + dims[i + 1]) for i in range(len(dims) - 1)
    )
    fwd = fwd_per_timestep * LOOKBACK + 2 * dims[-1] * N_SENSORS
    return 3.0 * fwd


def compute_mfu(rate_windows_per_s: float, device_kind: str) -> float:
    """Achieved training FLOP/s over the chip's peak. A ``device_kind``
    missing from :data:`PEAK_BF16_FLOPS` raises: a utilization against a
    guessed peak is worse than none."""
    if device_kind not in PEAK_BF16_FLOPS:
        raise KeyError(
            f"no cited bf16 peak for device_kind {device_kind!r}; add it "
            f"to PEAK_BF16_FLOPS with its source "
            f"(known: {sorted(PEAK_BF16_FLOPS)})"
        )
    return (
        rate_windows_per_s
        * training_flops_per_window()
        / PEAK_BF16_FLOPS[device_kind]
    )


def run_child(n_timesteps: int, epochs: int, timeout_s: float):
    """Run the TPU measurement in a subprocess with a hard timeout.

    A hung backend init dies with the subprocess instead of wedging the
    bench. Returns the parsed result dict, or None on timeout, crash, or
    a child that found no ``tpu`` platform (it exits non-zero).
    """
    cmd = [sys.executable, __file__, "--child", str(n_timesteps), str(epochs)]
    log(f"child timeout={timeout_s:.0f}s: {' '.join(cmd[2:])}")
    try:
        proc = subprocess.run(
            cmd, timeout=timeout_s, capture_output=True, text=True
        )
    except subprocess.TimeoutExpired as exc:
        log(f"child timed out after {timeout_s:.0f}s")
        # the captured stderr is the only trace of WHERE the child wedged
        # (backend init vs compile vs train) — keep it in the round log
        partial = exc.stderr or b""
        if isinstance(partial, bytes):
            partial = partial.decode(errors="replace")
        if partial:
            sys.stderr.write(partial[-2000:])
        return None
    sys.stderr.write(proc.stderr[-2000:])
    if proc.returncode != 0:
        log(f"child failed rc={proc.returncode}")
        return None
    try:
        return json.loads(proc.stdout.strip().splitlines()[-1])
    except (ValueError, IndexError):
        log("child produced no parseable result")
        return None


def child_main(n_timesteps: int, epochs: int):
    print(json.dumps(bench_jax(n_timesteps, epochs)), flush=True)


def main():
    log(f"budget: {BUDGET_S:.0f}s")

    # phase 1: the baseline — cheap, reliable, needs no JAX
    try:
        baseline_rate = bench_torch_cpu()
    except Exception as exc:  # torch missing/broken must not kill the bench
        log(f"baseline failed: {exc}")
        baseline_rate = None

    # phase 2: the TPU measurement. Healthy runs (cold cache) finish in
    # <=300s; the cap bounds a hung backend init. One bounded retry, and
    # then the run FAILS — there is no CPU result under this metric.
    result = run_child(N_TIMESTEPS, EPOCHS, min(600.0, remaining()))
    if result is None and remaining() >= 120.0:
        log("TPU attempt failed; one bounded retry")
        result = run_child(N_TIMESTEPS, EPOCHS, min(300.0, remaining()))
    if result is None:
        log("no TPU measurement: exiting non-zero with no headline value")
        sys.exit(1)

    vs_baseline = (result["rate"] / baseline_rate) if baseline_rate else None
    n_windows = result["n_timesteps"] - LOOKBACK + 1
    windows_per_s = n_windows * result["epochs"] / result["train_time"]
    mfu = compute_mfu(windows_per_s, result["device_kind"])

    print(
        json.dumps(
            {
                "metric": "LSTM-AE training throughput (sensor-timesteps/sec/chip)",
                "value": round(result["rate"], 1),
                "unit": "sensor-timesteps/s",
                "vs_baseline": round(vs_baseline, 2) if vs_baseline else None,
                "platform": result["platform"],
                "device_kind": result["device_kind"],
                "n_timesteps": result["n_timesteps"],
                "epochs": result["epochs"],
                "dispatch_overhead_s": result.get("dispatch_overhead_s"),
                "internal_steady_state_epoch_s": result.get(
                    "internal_steady_state_epoch_s"
                ),
                # achieved/peak bf16 FLOP/s for this chip: small-model
                # fleet training is bandwidth/latency bound, so
                # single-model MFU is expected to be low; see
                # docs/performance.md for the roofline discussion.
                "mfu": round(mfu, 4),
            }
        )
    )


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--child":
        child_main(int(sys.argv[2]), int(sys.argv[3]))
    else:
        main()
