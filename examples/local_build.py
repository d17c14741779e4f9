"""
Dev-loop example: train every machine in a small project config in-process
(no Kubernetes, no Argo) with gordo_tpu.builder.local_build — the analogue
of the reference's "Pipelines with Gordo" notebook flow.

Run: python examples/local_build.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from gordo_tpu.builder.local_build import local_build  # noqa: E402

CONFIG = """
machines:
  - name: example-machine
    dataset:
      type: RandomDataset
      train_start_date: 2018-01-01T00:00:00+00:00
      train_end_date: 2018-01-05T00:00:00+00:00
      tags: [GRA-TAG 1, GRA-TAG 2, GRA-TAG 3]
    model:
      gordo_tpu.models.anomaly.DiffBasedAnomalyDetector:
        base_estimator:
          sklearn.pipeline.Pipeline:
            steps:
              - sklearn.preprocessing.MinMaxScaler
              - gordo_tpu.models.AutoEncoder:
                  kind: feedforward_hourglass
                  epochs: 5
"""


def main():
    for model, machine in local_build(CONFIG):
        cv = machine.metadata.build_metadata.model.cross_validation
        print(f"built {machine.name}: {type(model).__name__}")
        for score_name in sorted(cv.scores)[:4]:
            print(f"  {score_name}: {cv.scores[score_name]}")


if __name__ == "__main__":
    main()
