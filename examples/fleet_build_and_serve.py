"""
Fleet example: build a bucket of machines as ONE vmapped program, then
serve them and score the whole fleet with one batched request.

Run: python examples/fleet_build_and_serve.py
"""

import json
import os
import sys
import tempfile
import threading
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_MACHINES = 4

MACHINE_TPL = """
  - name: fleet-m{i}
    dataset:
      type: RandomDataset
      tags: [tag-0, tag-1, tag-2]
      target_tag_list: [tag-0, tag-1, tag-2]
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-02T00:00:00+00:00'
      asset: gra
    model:
      gordo_tpu.models.AutoEncoder: {{kind: feedforward_hourglass, epochs: 2}}
"""


def main():
    import numpy as np
    import yaml
    from werkzeug.serving import make_server

    from gordo_tpu import serializer
    from gordo_tpu.builder.fleet_build import FleetModelBuilder
    from gordo_tpu.server import build_app
    from gordo_tpu.workflow.config_elements.normalized_config import NormalizedConfig

    config = yaml.safe_load(
        "machines:" + "".join(MACHINE_TPL.format(i=i) for i in range(N_MACHINES))
    )
    machines = NormalizedConfig(config, project_name="fleet-example").machines

    with tempfile.TemporaryDirectory() as tmp:
        collection = os.path.join(tmp, "fleet-example", "models", "rev1")
        # one vmapped program trains the whole bucket
        for model, machine in FleetModelBuilder(machines).build():
            serializer.dump(
                model, os.path.join(collection, machine.name),
                metadata=machine.to_dict(),
            )

        os.environ["MODEL_COLLECTION_DIR"] = collection
        server = make_server("127.0.0.1", 5598, build_app(), threaded=True)
        threading.Thread(target=server.serve_forever, daemon=True).start()

        rows = np.random.default_rng(0).random((20, 3)).tolist()
        body = json.dumps(
            {"machines": {f"fleet-m{i}": rows for i in range(N_MACHINES)}}
        ).encode()
        request = urllib.request.Request(
            "http://127.0.0.1:5598/gordo/v0/fleet-example/prediction/fleet",
            data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(request) as resp:
            payload = json.loads(resp.read())

        # the same batching through the client driver: groups of machines
        # per request, raw data pulled through the machines' own dataset
        # configs with the client's provider
        import dateutil.parser

        from gordo_tpu.client import Client
        from gordo_tpu.data.providers import RandomDataProvider

        client = Client(
            project="fleet-example",
            host="127.0.0.1",
            port=5598,
            scheme="http",
            data_provider=RandomDataProvider(),
            parallelism=2,
        )
        span = (
            dateutil.parser.isoparse("2019-01-01T00:00:00+00:00"),
            dateutil.parser.isoparse("2019-01-01T06:00:00+00:00"),
        )
        # first call probes /anomaly/prediction/fleet, learns these are
        # plain models (422), and scores them per-machine; the second call
        # batches the whole group through the base fleet endpoint
        client.predict_fleet(*span, group_size=N_MACHINES)
        fleet_results = client.predict_fleet(*span, group_size=N_MACHINES)
        server.shutdown()

    print("one batched request scored:", sorted(payload["data"]))
    for name, frame, errors in sorted(fleet_results):
        print(f"client fleet: {name} rows={len(frame)} errors={errors}")


if __name__ == "__main__":
    main()
