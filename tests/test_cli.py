"""
CLI tests via click's CliRunner (reference: tests/gordo/cli/test_cli.py,
test_workflow_generator.py — argo-lint via docker is out of scope in this
image; the rendered YAML is instead parsed and structurally asserted).
"""

import json
import os

import pytest
import yaml
from click.testing import CliRunner

from gordo_tpu import __version__, serializer
from gordo_tpu.cli import gordo
from gordo_tpu.cli.cli import expand_model, get_all_score_strings
from gordo_tpu.cli.exceptions_reporter import ExceptionsReporter, ReportLevel
from gordo_tpu.workflow.validate import validate_rendered

MACHINE_YAML = """
name: cli-machine
project_name: cli-project
dataset:
  type: RandomDataset
  tags: [tag-0, tag-1, tag-2]
  target_tag_list: [tag-0, tag-1, tag-2]
  train_start_date: '2019-01-01T00:00:00+00:00'
  train_end_date: '2019-01-02T00:00:00+00:00'
  asset: gra
model:
  gordo_tpu.models.AutoEncoder:
    kind: feedforward_hourglass
    epochs: 1
"""

PROJECT_YAML = """
machines:
  - name: wf-machine-0
    dataset:
      type: RandomDataset
      tags: [tag-0, tag-1]
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-02T00:00:00+00:00'
      asset: gra
  - name: wf-machine-1
    dataset:
      type: RandomDataset
      tags: [tag-1, tag-2]
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-02T00:00:00+00:00'
      asset: gra
  - name: wf-machine-2
    dataset:
      type: RandomDataset
      tags: [tag-3]
      train_start_date: '2019-01-01T00:00:00+00:00'
      train_end_date: '2019-01-02T00:00:00+00:00'
      asset: gra
globals:
  model:
    gordo_tpu.models.AutoEncoder:
      kind: feedforward_hourglass
  runtime:
    builder:
      machines_per_pod: 2
"""


@pytest.fixture
def runner():
    return CliRunner()


def test_version(runner):
    result = runner.invoke(gordo, ["--version"])
    assert result.exit_code == 0
    assert __version__ in result.output


def test_build(runner, tmp_path):
    out_dir = str(tmp_path / "out")
    result = runner.invoke(
        gordo, ["build", MACHINE_YAML, out_dir, "--print-cv-scores"]
    )
    assert result.exit_code == 0, result.output
    model = serializer.load(out_dir)
    metadata = serializer.load_metadata(out_dir)
    assert metadata["name"] == "cli-machine"
    assert model is not None
    # Katib-format CV score lines on stdout (reference: cli.py:243-275)
    assert any("=" in line and "fold" in line for line in result.output.splitlines())


def test_build_machine_name_containing_err_succeeds(runner, tmp_path):
    """Regression guard against the reference's planted fault: its CLI
    raises FileNotFoundError for any machine whose NAME contains 'err'
    (reference gordo/cli/cli.py:178-179). Building such a machine — both
    solo and through the fleet path — must succeed here."""
    err_yaml = MACHINE_YAML.replace("name: cli-machine", "name: pump-overriderr-7")
    out_dir = str(tmp_path / "err-out")
    result = runner.invoke(gordo, ["build", err_yaml, out_dir])
    assert result.exit_code == 0, result.output
    assert serializer.load_metadata(out_dir)["name"] == "pump-overriderr-7"

    fleet_out = str(tmp_path / "err-fleet-out")
    machines = [yaml.safe_load(err_yaml) | {"name": "fleet-err-machine"}]
    result = runner.invoke(gordo, ["build-fleet", json.dumps(machines), fleet_out])
    assert result.exit_code == 0, result.output
    assert os.path.exists(os.path.join(fleet_out, "fleet-err-machine", "model.pkl"))


def test_telemetry_summarize_cli(runner, tmp_path):
    """gordo-tpu telemetry summarize renders a fleet build's telemetry
    report and event log into the human summary."""
    from gordo_tpu.observability import write_telemetry_report

    write_telemetry_report(
        tmp_path / "proj",
        {
            "kind": "fleet_build",
            "n_machines": 4,
            "n_buckets": 2,
            "wall_time_s": 10.0,
            "models_per_hour": 1440.0,
            "device_memory": {"available": False, "peak_bytes_in_use": None},
            "buckets": [],
        },
    )
    (tmp_path / "proj" / "events.jsonl").write_text(
        '{"ts": "t", "event": "build_started"}\n'
        '{"ts": "t", "event": "build_crashed", "error": "RuntimeError(boom)"}\n'
    )
    result = runner.invoke(gordo, ["telemetry", "summarize", str(tmp_path)])
    assert result.exit_code == 0, result.output
    assert "4 machines in 2 bucket(s)" in result.output
    assert "1.4k models/hour" in result.output
    assert "CRASH CONTEXT" in result.output and "boom" in result.output

    as_json = runner.invoke(
        gordo, ["telemetry", "summarize", str(tmp_path), "--as-json"]
    )
    assert as_json.exit_code == 0, as_json.output
    payload = json.loads(as_json.output)
    assert payload["schema_version"] == 4
    assert payload["reports"][0]["report"]["n_machines"] == 4
    assert payload["events"]["build"]["build_started"] == 1


def test_build_env_vars(runner, tmp_path):
    """MACHINE / OUTPUT_DIR env vars drive the build (pod semantics)."""
    out_dir = str(tmp_path / "out-env")
    result = runner.invoke(
        gordo, ["build"], env={"MACHINE": MACHINE_YAML, "OUTPUT_DIR": out_dir}
    )
    assert result.exit_code == 0, result.output
    assert os.path.exists(os.path.join(out_dir, "model.pkl"))


def test_build_insufficient_data_exit_code(runner, tmp_path):
    """Typed exit code 80 + JSON report file on InsufficientDataError."""
    bad_yaml = MACHINE_YAML.replace(
        "asset: gra", "asset: gra\n  n_samples_threshold: 100000"
    )
    report_file = str(tmp_path / "exc.json")
    result = runner.invoke(
        gordo,
        [
            "build",
            bad_yaml,
            str(tmp_path / "o"),
            "--exceptions-reporter-file",
            report_file,
            "--exceptions-report-level",
            "MESSAGE",
        ],
    )
    assert result.exit_code == 80
    with open(report_file) as f:
        report = json.load(f)
    assert report["type"] == "InsufficientDataError"
    assert "message" in report


def test_build_fleet(runner, tmp_path):
    machines = [
        yaml.safe_load(MACHINE_YAML) | {"name": f"fleet-m-{i}"} for i in range(3)
    ]
    out_dir = str(tmp_path / "fleet-out")
    # JSON is the canonical MACHINES payload (what the workflow template
    # injects); YAML block style would lead with "- " which click rejects
    # as an option when passed positionally.
    result = runner.invoke(gordo, ["build-fleet", json.dumps(machines), out_dir])
    assert result.exit_code == 0, result.output
    for i in range(3):
        sub = os.path.join(out_dir, f"fleet-m-{i}")
        assert os.path.exists(os.path.join(sub, "model.pkl"))
        meta = serializer.load_metadata(sub)
        assert meta["name"] == f"fleet-m-{i}"


def test_buckets_plan_cli(runner):
    """`gordo-tpu buckets plan` dry-runs the bucketing compiler: program
    counts, machines per program, and padding-waste %% per axis, without
    building anything (docs/parallelism.md "Bucketing compiler")."""
    base = yaml.safe_load(MACHINE_YAML)
    machines = []
    for i, ntags in enumerate((3, 4)):
        cfg = json.loads(json.dumps(base))
        cfg["name"] = f"plan-m-{i}"
        cfg["dataset"]["tags"] = [f"tag-{t}" for t in range(ntags)]
        cfg["dataset"]["target_tag_list"] = cfg["dataset"]["tags"]
        machines.append(cfg)

    result = runner.invoke(
        gordo,
        ["buckets", "plan", json.dumps(machines), "--bucket-policy", "padded"],
    )
    assert result.exit_code == 0, result.output
    assert "2 machine(s) -> 1 compiled program(s)" in result.output
    assert "exact policy would compile 2" in result.output
    assert "waste" in result.output

    as_json = runner.invoke(
        gordo,
        [
            "buckets", "plan", json.dumps(machines),
            "--bucket-policy", "padded", "--as-json",
        ],
    )
    assert as_json.exit_code == 0, as_json.output
    payload = json.loads(as_json.output)
    assert payload["n_programs"] == 1
    assert payload["n_programs_exact"] == 2
    assert payload["programs"][0]["n_features"] == 4
    assert payload["programs"][0]["machines"] == ["plan-m-0", "plan-m-1"]

    exact = runner.invoke(
        gordo, ["buckets", "plan", json.dumps(machines), "--as-json"]
    )
    assert exact.exit_code == 0, exact.output
    assert json.loads(exact.output)["n_programs"] == 2


def test_expand_model():
    expanded = expand_model(
        "gordo_tpu.models.AutoEncoder: {kind: feedforward_hourglass, "
        "epochs: {{ epochs }}}",
        {"epochs": 7},
    )
    assert expanded["gordo_tpu.models.AutoEncoder"]["epochs"] == 7
    with pytest.raises(ValueError):
        expand_model("a: {{ missing }}", {})


def test_exceptions_reporter_ordering_and_codes():
    reporter = ExceptionsReporter(
        ((Exception, 1), (ValueError, 5), (FileNotFoundError, 30), (OSError, 40))
    )
    assert reporter.exception_exit_code(None) == 0
    assert reporter.exception_exit_code(FileNotFoundError) == 30  # subclass wins
    assert reporter.exception_exit_code(OSError) == 40
    assert reporter.exception_exit_code(ValueError) == 5
    assert reporter.exception_exit_code(KeyError) == 1  # default via Exception


def test_exceptions_reporter_trimming(tmp_path):
    reporter = ExceptionsReporter(((ValueError, 5),))
    path = str(tmp_path / "r.json")
    try:
        raise ValueError("x" * 5000)
    except ValueError:
        import sys

        reporter.safe_report(
            ReportLevel.MESSAGE, *sys.exc_info(), path, max_message_len=100
        )
    with open(path) as f:
        report = json.load(f)
    assert len(report["message"]) <= 100
    assert report["message"].endswith("...")


def test_get_all_score_strings_spaces_replaced():
    class FakeMachine:
        class metadata:
            class build_metadata:
                class model:
                    class cross_validation:
                        scores = {"mean squared error": {"fold 1": 0.5}}

    lines = get_all_score_strings(FakeMachine)
    assert lines == ["mean-squared-error_fold-1=0.5"]


# --- workflow generation ----------------------------------------------------


@pytest.fixture
def project_config_file(tmp_path):
    path = tmp_path / "config.yml"
    path.write_text(PROJECT_YAML)
    return str(path)


def _render_workflows(runner, config_file, *extra):
    result = runner.invoke(
        gordo,
        [
            "workflow",
            "generate",
            "--machine-config",
            config_file,
            "--project-name",
            "wf-proj",
            "--project-revision",
            "123",
            *extra,
        ],
    )
    assert result.exit_code == 0, result.output
    docs = list(yaml.safe_load_all(result.output))
    # every rendered manifest must be structurally valid Argo/k8s, not
    # merely parseable YAML (reference lints with the argo CLI image:
    # tests/gordo/workflow/test_workflow_generator.py:88-113)
    validate_rendered(docs)
    return docs


def test_workflow_generate_renders_valid_yaml(runner, project_config_file):
    docs = _render_workflows(runner, project_config_file)
    assert len(docs) == 1
    wf = docs[0]
    assert wf["kind"] == "Workflow"
    assert wf["metadata"]["labels"]["gordo-tpu/project-name"] == "wf-proj"
    names = {t["name"] for t in wf["spec"]["templates"]}
    assert {
        "do-all",
        "ensure-single-workflow",
        "model-fleet-builder",
        "gordo-server-deployment",
        "gordo-client",
    } <= names
    # 3 machines, machines_per_pod=2 → 2 builder buckets in the DAG
    dag = next(t for t in wf["spec"]["templates"] if t["name"] == "do-all")
    build_tasks = [
        t for t in dag["dag"]["tasks"] if t["name"].startswith("build-bucket")
    ]
    assert len(build_tasks) == 2
    assert dag["dag"]["failFast"] is False
    # bucket MACHINES payload is valid JSON with the right machines
    payload = json.loads(
        build_tasks[0]["arguments"]["parameters"][0]["value"]
    )
    assert [m["name"] for m in payload] == ["wf-machine-0", "wf-machine-1"]
    # postgres reporter injected when influx enabled
    assert any(
        "PostgresReporter" in json.dumps(m) for m in payload
    )
    # one fleet client task per bucket, covering every machine, depending
    # on its bucket's build
    client_tasks = [
        t for t in dag["dag"]["tasks"] if t.get("template") == "gordo-client"
    ]
    assert len(client_tasks) == 2
    all_targets = " ".join(
        t["arguments"]["parameters"][0]["value"] for t in client_tasks
    ).split()
    assert sorted(all_targets) == ["wf-machine-0", "wf-machine-1", "wf-machine-2"]
    # client -> its waiter -> the bucket's build
    assert client_tasks[0]["dependencies"] == [
        client_tasks[0]["name"].replace("client-", "client-wait-")
    ]
    wait_tasks = {
        t["name"]: t
        for t in dag["dag"]["tasks"]
        if t["name"].startswith("client-wait")
    }
    assert any(
        dep.startswith("build-bucket")
        for dep in wait_tasks[client_tasks[0]["dependencies"][0]]["dependencies"]
    )
    # the client template drives the fleet endpoints, with memory scaled
    # to the bucket size (machines_per_pod=2 -> 2x the per-machine default)
    client_tpl = next(
        t for t in wf["spec"]["templates"] if t["name"] == "gordo-client"
    )
    assert "--fleet" in client_tpl["script"]["source"]
    assert client_tpl["script"]["resources"]["limits"]["memory"] == "8000M"
    assert client_tpl["script"]["resources"]["requests"]["memory"] == "7000M"


def test_workflow_generate_split(runner, project_config_file):
    docs = _render_workflows(
        runner, project_config_file, "--split-workflows", "2"
    )
    assert len(docs) == 2
    first_names = json.loads(docs[0]["metadata"]["annotations"]["gordo-models"])
    second_names = json.loads(docs[1]["metadata"]["annotations"]["gordo-models"])
    assert first_names == ["wf-machine-0", "wf-machine-1"]
    assert second_names == ["wf-machine-2"]


def test_workflow_generate_tpu_node_pool(runner, tmp_path):
    config = PROJECT_YAML + """
      tpu:
        enable: true
        accelerator: v5litepod-16
        chips: 4
"""
    path = tmp_path / "tpu-config.yml"
    path.write_text(config)
    docs = _render_workflows(runner, str(path))
    builder = next(
        t for t in docs[0]["spec"]["templates"] if t["name"] == "model-fleet-builder"
    )
    assert (
        builder["nodeSelector"]["cloud.google.com/gke-tpu-accelerator"]
        == "v5litepod-16"
    )
    assert builder["container"]["resources"]["limits"]["google.com/tpu"] == 4


def test_workflow_failure_semantics_rendered(runner, project_config_file):
    """
    The reference's failure-handling contract (SURVEY.md §5) must survive
    rendering: retry-with-backoff on every pod template, exceptions report
    via the pod termination message, stale-workflow cleanup, and probes on
    the server deployment.
    """
    (wf,) = _render_workflows(runner, project_config_file)
    templates = {t["name"]: t for t in wf["spec"]["templates"]}

    builder = templates["model-fleet-builder"]
    assert builder["retryStrategy"]["retryPolicy"] == "Always"
    assert "backoff" in builder["retryStrategy"]
    env = {e["name"]: e.get("value") for e in builder["container"]["env"]}
    assert {"MACHINES", "OUTPUT_DIR", "EXCEPTIONS_REPORTER_FILE"} <= set(env)
    # the exceptions report file IS the k8s termination message
    # (reference: argo-workflow.yml.template:702-703)
    assert (
        builder["container"]["terminationMessagePath"]
        == env["EXCEPTIONS_REPORTER_FILE"]
    )

    ensure = templates["ensure-single-workflow"]
    script = ensure["script"]["source"]
    # the cleanup logic: finds older-revision Running workflows and deletes
    assert "kubectl delete" in script
    assert "project-revision!=" in script

    server = templates["gordo-server-deployment"]
    (apply_step,) = server["steps"][0]
    (param,) = apply_step["arguments"]["parameters"]
    manifest = yaml.safe_load(param["value"])
    container = manifest["spec"]["template"]["spec"]["containers"][0]
    assert "livenessProbe" in container
    assert "readinessProbe" in container


def test_workflow_generate_to_file(runner, project_config_file, tmp_path):
    """--output-file writes the documents instead of stdout
    (ref: test_workflow_generator.py:157)."""
    out = tmp_path / "wf.yml"
    result = runner.invoke(
        gordo,
        [
            "workflow", "generate", "--machine-config", project_config_file,
            "--project-name", "wf-proj", "--project-revision", "123",
            "--output-file", str(out),
        ],
    )
    assert result.exit_code == 0, result.output
    docs = list(yaml.safe_load_all(out.read_text()))
    assert docs and docs[0]["kind"] == "Workflow"


def test_workflow_expected_models_env(runner, project_config_file):
    """The server deployment carries EXPECTED_MODELS so /expected-models
    serves the project's machine list (ref: test_workflow_generator.py:491)."""
    docs = _render_workflows(runner, project_config_file)
    blob = yaml.safe_dump_all(docs)
    assert "EXPECTED_MODELS" in blob
    wf = docs[0]
    server_tpl = next(
        t
        for t in wf["spec"]["templates"]
        if t["name"] == "gordo-server-deployment"
    )
    env_blob = json.dumps(server_tpl)
    for name in ("wf-machine-0", "wf-machine-1", "wf-machine-2"):
        assert name in env_blob


def test_workflow_missing_timezone_rejected(runner, tmp_path):
    """Naive timestamps in configs are config errors
    (ref: test_workflow_generator.py:422)."""
    config = PROJECT_YAML.replace(
        "'2019-01-01T00:00:00+00:00'", "'2019-01-01T00:00:00'"
    )
    path = tmp_path / "naive.yml"
    path.write_text(config)
    result = runner.invoke(
        gordo,
        [
            "workflow", "generate", "--machine-config", str(path),
            "--project-name", "wf-proj",
        ],
    )
    assert result.exit_code != 0
    assert "timezone" in str(result.exception)


def test_workflow_disable_influx(runner, tmp_path):
    """All machines opting out of influx removes the influx/postgres stack
    and the reporter wiring (ref: test_workflow_generator.py:326)."""
    config = PROJECT_YAML.replace(
        "  runtime:\n    builder:\n      machines_per_pod: 2",
        "  runtime:\n    builder:\n      machines_per_pod: 2\n"
        "    influx:\n      enable: false",
    )
    path = tmp_path / "no-influx.yml"
    path.write_text(config)
    docs = _render_workflows(runner, str(path))
    blob = yaml.safe_dump_all(docs)
    assert "gordo-influx" not in blob
    assert "PostgresReporter" not in blob


def test_workflow_unique_tags(runner, project_config_file, tmp_path):
    out = tmp_path / "tags.txt"
    result = runner.invoke(
        gordo,
        [
            "workflow",
            "unique-tags",
            "--machine-config",
            project_config_file,
            "--output-file-tag-list",
            str(out),
        ],
    )
    assert result.exit_code == 0, result.output
    tags = set(out.read_text().split())
    assert tags == {"tag-0", "tag-1", "tag-2", "tag-3"}


def test_sweep_cli(runner):
    """gordo-tpu sweep trains the grid as one program and ranks trials."""
    machine_yaml = """
name: sweep-cli-machine
project_name: sweep-proj
dataset:
  type: RandomDataset
  train_start_date: 2018-01-01T00:00:00+00:00
  train_end_date: 2018-01-02T00:00:00+00:00
  tags: [tag-0, tag-1]
  asset: gra
model:
  gordo_tpu.models.AutoEncoder:
    kind: feedforward_hourglass
    epochs: 2
    batch_size: 16
"""
    result = runner.invoke(
        gordo,
        ["sweep", machine_yaml, "--param", "lr=0.001,0.01"],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    lines = result.output.strip().splitlines()
    assert sum(1 for ln in lines if ln.startswith("trial-")) == 2
    assert lines[-1].startswith("best: learning_rate=")
    # ranked best-first
    losses = [float(ln.rsplit("loss=", 1)[1]) for ln in lines if "loss=" in ln]
    assert losses == sorted(losses)


def test_sweep_cli_bad_grid(runner):
    result = runner.invoke(
        gordo, ["sweep", "{name: m, dataset: {}, model: {}}", "--param", "lr"]
    )
    assert result.exit_code != 0


def test_run_server_cli_passes_concurrency_knobs(runner, monkeypatch):
    """--workers/--threads/--worker-connections reach run_server intact."""
    captured = {}

    def fake_run_server(host, port, workers, log_level, config=None,
                        threads=None, worker_connections=None):
        captured.update(
            host=host, port=port, workers=workers, threads=threads,
            worker_connections=worker_connections, config=config,
        )

    from gordo_tpu.server import app as server_app

    monkeypatch.setattr(server_app, "run_server", fake_run_server)
    result = runner.invoke(
        gordo,
        ["run-server", "--host", "127.0.0.1", "--port", "5001",
         "--workers", "3", "--threads", "5", "--worker-connections", "17"],
    )
    assert result.exit_code == 0, result.output
    assert captured == {
        "host": "127.0.0.1", "port": 5001, "workers": 3, "threads": 5,
        "worker_connections": 17,
        # tuned batching/cache knobs left at their defaults stay OUT of
        # the config: build_app resolves them env -> tuning profile ->
        # built-in default, so the collection's tuning_profile.json can
        # supply measured defaults (docs/tuning.md)
        "config": {
            "AOT_CACHE": True,
            # unsharded by default: the historical whole-collection
            # replica (docs/serving.md#sharded-serving-plane)
            "SHARD_MANIFEST": None,
            "REPLICA_ID": None,
        },
    }


@pytest.mark.parametrize(
    "argv",
    [
        ["run-server", "--workers", "2"],
        ["build-fleet", "--workers", "2", "[]", "unused-out"],
    ],
    ids=["run-server", "build-fleet"],
)
def test_local_workers_refused_off_cpu(runner, monkeypatch, argv):
    """Off the CPU, N>1 local workers would each need the one chip: both
    commands refuse at start with a usage error naming the reason —
    decided from JAX_PLATFORMS alone, before anything is spawned."""
    from gordo_tpu.builder import ledger as fleet_ledger
    from gordo_tpu.server import app as server_app

    def must_not_start(*args, **kwargs):
        raise AssertionError("workers were started off the CPU")

    monkeypatch.setattr(server_app, "run_server", must_not_start)
    monkeypatch.setattr(fleet_ledger, "orchestrate", must_not_start)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    result = runner.invoke(gordo, argv)
    assert result.exit_code == 2, result.output
    assert "a chip belongs to one process at a time" in result.output
    assert "JAX_PLATFORMS=cpu" in result.output


def test_run_server_cli_passes_batching_knobs(runner, monkeypatch):
    """--batch-wait-ms/--queue-limit reach the server config intact."""
    captured = {}

    def fake_run_server(host, port, workers, log_level, config=None,
                        threads=None, worker_connections=None):
        captured.update(config=config)

    from gordo_tpu.server import app as server_app

    monkeypatch.setattr(server_app, "run_server", fake_run_server)
    result = runner.invoke(
        gordo,
        ["run-server", "--batch-wait-ms", "7.5", "--queue-limit", "32"],
    )
    assert result.exit_code == 0, result.output
    assert captured["config"] == {
        # explicitly-set knobs ride the config and win over any tuning
        # profile; SCORER_CACHE_SIZE stayed at its default so it defers
        # to build_app's env -> profile -> default resolution
        # (docs/tuning.md)
        "BATCH_WAIT_MS": 7.5,
        "BATCH_QUEUE_LIMIT": 32,
        "AOT_CACHE": True,
        "SHARD_MANIFEST": None,
        "REPLICA_ID": None,
    }


def test_run_router_cli_passes_knobs(runner, monkeypatch, tmp_path):
    """run-router parses --replica id=url entries and hands every knob
    to the router config intact (docs/serving.md#sharded-serving-plane)."""
    captured = {}

    def fake_run_router(host, port, log_level, config=None, threads=None):
        captured.update(
            host=host, port=port, config=config, threads=threads
        )

    from gordo_tpu.router import app as router_app

    # delenv also registers cleanup for the value run-router exports
    monkeypatch.delenv("MODEL_COLLECTION_DIR", raising=False)
    monkeypatch.setattr(router_app, "run_router", fake_run_router)
    result = runner.invoke(
        gordo,
        ["run-router", "--host", "127.0.0.1", "--port", "5556",
         "--replica", "r0=http://h0:5555", "--replica", "r1=http://h1:5555/",
         "--collection-dir", str(tmp_path),
         "--hedge-ms", "25", "--eject-after", "2", "--max-inflight", "8",
         "--threads", "12"],
    )
    assert result.exit_code == 0, result.output
    assert captured["threads"] == 12
    assert captured["config"]["REPLICAS"] == {
        "r0": "http://h0:5555",
        "r1": "http://h1:5555",  # trailing slash normalized
    }
    assert captured["config"]["HEDGE_MS"] == 25
    assert captured["config"]["EJECT_AFTER"] == 2
    assert captured["config"]["MAX_INFLIGHT"] == 8
    # the flag exports the env var the request path resolves against
    assert os.environ["MODEL_COLLECTION_DIR"] == str(tmp_path)
    # no replicas is a usage error, not a crash at serve time
    result = runner.invoke(gordo, ["run-router"])
    assert result.exit_code != 0
    assert "replica" in result.output.lower()


def test_run_router_cli_requires_collection_dir(runner, monkeypatch, tmp_path):
    """A router launched without MODEL_COLLECTION_DIR used to die with a
    KeyError on the FIRST REQUEST; now the launch itself is a clear
    usage error, and the env var still works as the fallback."""
    captured = {}

    def fake_run_router(host, port, log_level, config=None, threads=None):
        captured.update(config=config)

    from gordo_tpu.router import app as router_app

    monkeypatch.setattr(router_app, "run_router", fake_run_router)
    monkeypatch.delenv("MODEL_COLLECTION_DIR", raising=False)
    result = runner.invoke(
        gordo, ["run-router", "--replica", "r0=http://h0:5555"]
    )
    assert result.exit_code != 0
    assert "--collection-dir" in result.output
    assert "MODEL_COLLECTION_DIR" in result.output
    assert not captured  # never reached run_router
    # env fallback: exporting the var is equivalent to the flag
    monkeypatch.setenv("MODEL_COLLECTION_DIR", str(tmp_path))
    result = runner.invoke(
        gordo, ["run-router", "--replica", "r0=http://h0:5555"]
    )
    assert result.exit_code == 0, result.output
    assert captured["config"]["REPLICAS"] == {"r0": "http://h0:5555"}


def test_router_app_answers_503_not_keyerror_without_collection_dir(
    monkeypatch,
):
    """Defense in depth for embedded apps: a router whose process lost
    the env var answers requests with a structured 503 diagnosis, not a
    KeyError-shaped 500."""
    from werkzeug.test import Client as WerkzeugClient

    from gordo_tpu.router.app import build_router_app

    monkeypatch.delenv("MODEL_COLLECTION_DIR", raising=False)
    app = build_router_app({"REPLICAS": {"r0": "http://h0:5555"}})
    client = WerkzeugClient(app)
    response = client.get("/gordo/v0/proj/machine/metadata")
    assert response.status_code == 503
    payload = json.loads(response.get_data())
    assert "MODEL_COLLECTION_DIR" in payload["error"]


def test_client_cli_help(runner):
    result = runner.invoke(gordo, ["client", "--help"])
    assert result.exit_code == 0
    for sub in ("predict", "metadata", "download-model"):
        assert sub in result.output


def test_client_predict_cli_fleet_flag(runner, monkeypatch):
    """--fleet routes through Client.predict_fleet with the group size."""
    import pandas as pd

    from gordo_tpu.client import Client

    calls = {}

    def fake_fleet(self, start, end, targets=None, revision=None, group_size=8):
        calls["group_size"] = group_size
        return [("m1", pd.DataFrame(), [])]

    monkeypatch.setattr(Client, "predict_fleet", fake_fleet)
    result = runner.invoke(
        gordo,
        [
            "client",
            "--project",
            "proj",
            "predict",
            "2019-01-01T00:00:00+00:00",
            "2019-01-02T00:00:00+00:00",
            "--fleet",
            "--fleet-group-size",
            "4",
        ],
    )
    assert result.exit_code == 0, result.output
    assert calls["group_size"] == 4
