"""Near-miss fixture for knob-discipline: registered knobs, declared
non-knobs, env WRITES, non-GORDO vars, test switches, and non-literal
reads. Nothing here may flag."""

import os
from os import environ, getenv

import click


def registered_knob_read():
    # a Knob's env_var in the registry (gordo_tpu/tuning/knobs.py)
    return os.environ.get("GORDO_PREFETCH_DEPTH")


def declared_non_knob_read():
    # classified in NON_KNOB_ENV_VARS: chaos switch, not a knob
    return os.environ.get("GORDO_FAULT_INJECT")


def env_write_is_not_a_read(value):
    os.environ["GORDO_MYSTERY_KNOB"] = value  # write: test setup shape
    environ["GORDO_SECRET_LIMIT"] = value


def non_gordo_namespace():
    return os.environ.get("JAX_PLATFORMS", getenv("PATH"))


def test_suite_switch():
    # GORDO_TEST_* is exempt: suite configuration, not production
    return os.environ.get("GORDO_TEST_POSTGRES_DSN")


_EVENT_LOG_ENV_VAR = "GORDO_TPU_EVENT_LOG"


def non_literal_read_out_of_scope():
    # reads through a named constant are not vouched for (the metric
    # check's literal-only scope)
    return os.environ.get(_EVENT_LOG_ENV_VAR)


@click.option(
    "--prefetch-depth",
    envvar="GORDO_PREFETCH_DEPTH",  # registered knob
    default=0,
)
@click.option(
    "--log-level",
    envvar="GORDO_LOG_LEVEL",  # declared non-knob
    default="INFO",
)
def command(prefetch_depth, log_level):
    return prefetch_depth, log_level
