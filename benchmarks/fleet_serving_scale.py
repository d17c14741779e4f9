"""
Fleet-serving scaling harness: ms/machine of stacked-param batched
scoring as machines/request grows (VERDICT r3 item 7 — the deployment's
actual shape is hundreds of machines scored per dispatch, not the 8 the
r03 latency table measured).

Measures FleetScorer.predict directly (the server's fleet endpoint hot
path minus HTTP/JSON, which benchmarks/server_latency.py covers): one
group of same-architecture machines, params stacked once up front
(device-resident between requests — the preload story), then timed
full-group requests at increasing machines/request.

Prints one JSON object with a ms/machine scaling table.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gordo_tpu.utils import enable_compile_cache

enable_compile_cache()


def build_estimators(
    n_machines: int, n_features: int, n_rows: int, model: str = "hourglass"
):
    """n trained same-architecture estimators — trained as ONE fleet
    program (1 epoch; serving cost does not depend on fit quality).
    ``model``: "hourglass" (dense AE) or "lstm" (windowed; exercises the
    on-device window gather in the serving path)."""
    import numpy as np

    from gordo_tpu.models.core import solo_init_key
    from gordo_tpu.models.models import AutoEncoder, LSTMAutoEncoder
    from gordo_tpu.parallel.fleet import FleetTrainer, StackedData

    rng = np.random.default_rng(0)
    Xs = [rng.random((n_rows, n_features)).astype("float32") for _ in range(n_machines)]

    if model == "lstm":
        def make():
            return LSTMAutoEncoder(
                kind="lstm_model", lookback_window=16,
                encoding_dim=(32,), encoding_func=("tanh",),
                decoding_dim=(32,), decoding_func=("tanh",), fused=True,
            )
    else:
        def make():
            return AutoEncoder(kind="feedforward_hourglass")

    proto = make()
    proto.kwargs.update({"n_features": n_features, "n_features_out": n_features})
    spec = proto._build_spec()
    trainer = FleetTrainer(spec, lookahead=proto.lookahead if spec.windowed else 0)
    data = StackedData.from_ragged(Xs, [x.copy() for x in Xs])
    keys = np.stack([np.asarray(solo_init_key(0))] * n_machines)
    params, _ = trainer.fit(data, keys, epochs=1, batch_size=64)
    host = trainer.unstack_all(params, n_machines)

    estimators = {}
    for i in range(n_machines):
        est = make()
        est.kwargs.update({"n_features": n_features, "n_features_out": n_features})
        est.spec_ = spec
        est.params_ = host[i]
        est.n_features_ = n_features
        est.n_features_out_ = n_features
        estimators[f"serve-m{i}"] = est
    return estimators


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--sizes", type=int, nargs="+", default=[8, 16, 64, 128, 256])
    parser.add_argument("--rows", type=int, default=100, help="rows per machine")
    parser.add_argument("--features", type=int, default=4)
    parser.add_argument("--rounds", type=int, default=20)
    parser.add_argument("--model", choices=["hourglass", "lstm"], default="hourglass")
    args = parser.parse_args()

    import numpy as np

    import jax

    from gordo_tpu.server.fleet_serving import FleetScorer

    device = jax.devices()[0]
    rng = np.random.default_rng(1)
    table = []
    for size in args.sizes:
        estimators = build_estimators(size, args.features, 256, model=args.model)
        scorer = FleetScorer(estimators)  # params stacked + device-resident
        inputs = {
            name: rng.random((args.rows, args.features)).astype("float32")
            for name in scorer.names
        }
        scorer.predict(inputs)  # compile warmup
        start = time.perf_counter()
        for _ in range(args.rounds):
            out = scorer.predict(inputs)
        total = time.perf_counter() - start
        # windowed models emit rows - lookback + 1 - lookahead outputs
        proto = next(iter(estimators.values()))
        if getattr(proto.spec_, "windowed", False):
            expected = args.rows - proto.lookback_window + 1 - proto.lookahead
        else:
            expected = args.rows
        assert len(out) == size and all(len(v) == expected for v in out.values())
        ms_request = total / args.rounds * 1000
        table.append(
            {
                "machines_per_request": size,
                "ms_per_request": round(ms_request, 3),
                "ms_per_machine": round(ms_request / size, 4),
            }
        )
        print(f"  {size} machines: {ms_request:.1f} ms/request "
              f"({ms_request / size:.3f} ms/machine)", file=sys.stderr)

    print(
        json.dumps(
            {
                "platform": device.platform,
                "device_kind": device.device_kind,
                "model": args.model,
                "rows_per_machine": args.rows,
                "rounds": args.rounds,
                "scaling": table,
            }
        )
    )


if __name__ == "__main__":
    main()
