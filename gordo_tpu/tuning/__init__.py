"""
gordo_tpu.tuning — the telemetry-driven autotuner (docs/tuning.md).

Closes the loop from recorded observability to measured knob defaults:

- :mod:`knobs <gordo_tpu.tuning.knobs>` — the knob REGISTRY: one
  declaration per performance knob (flag, env var, default, domain,
  judging signals); single source of truth for the ``tune`` CLI, the
  docs knob table, and the ``knob-discipline`` lint check.
- :mod:`corpus <gordo_tpu.tuning.corpus>` — schema-tolerant reader
  normalizing ``telemetry_report*.json`` / JSONL event logs /
  ``benchmarks/results_*.json`` / ``trajectory.json`` into observations.
- :mod:`model <gordo_tpu.tuning.model>` — the simple per-fleet cost
  model: best measured arm with piecewise interpolation.
- :mod:`profile <gordo_tpu.tuning.profile>` — the versioned
  ``tuning_profile.json`` that ``build-fleet``/``run-server`` load by
  default (explicit CLI/env always wins).
- :mod:`calibrate <gordo_tpu.tuning.calibrate>` — short measurement
  sweeps for fleets with no corpus yet.
"""

from gordo_tpu.tuning.corpus import Corpus, Observation, read_corpus
from gordo_tpu.tuning.knobs import (
    KNOBS,
    KNOBS_BY_ENV,
    KNOBS_BY_NAME,
    NON_KNOB_ENV_VARS,
    Knob,
    Signal,
    declared_env_vars,
    get_knob,
    knobs_for_subsystem,
    tunable_knobs,
)
from gordo_tpu.tuning.model import (
    ArmEvidence,
    Recommendation,
    fit_recommendations,
)
from gordo_tpu.tuning.profile import (
    PROFILE_VERSION,
    TUNING_PROFILE_FILENAME,
    TuningProfileError,
    build_profile,
    load_collection_profile,
    load_profile,
    recommended_values,
    resolve_profile_path,
    validate_profile,
    write_profile,
)

__all__ = [
    "ArmEvidence",
    "Corpus",
    "KNOBS",
    "KNOBS_BY_ENV",
    "KNOBS_BY_NAME",
    "Knob",
    "NON_KNOB_ENV_VARS",
    "Observation",
    "PROFILE_VERSION",
    "Recommendation",
    "Signal",
    "TUNING_PROFILE_FILENAME",
    "TuningProfileError",
    "build_profile",
    "declared_env_vars",
    "fit_recommendations",
    "get_knob",
    "knobs_for_subsystem",
    "load_collection_profile",
    "load_profile",
    "read_corpus",
    "recommended_values",
    "resolve_profile_path",
    "tunable_knobs",
    "validate_profile",
    "write_profile",
]
