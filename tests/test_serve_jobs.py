"""Job lifecycle + flow service tests, modeled on the reference's
SparkJobOperationTest.cs (mock client driving state transitions) and
DataX.Config.Local.Test/LocalTests.cs (real local process end-to-end)."""

import json
import os
import sys
import time

import pytest

from data_accelerator_tpu.serve.flowbuilder import FlowConfigBuilder
from data_accelerator_tpu.serve.flowservice import FlowOperation
from data_accelerator_tpu.serve.jobs import (
    JobOperation,
    JobState,
    LocalJobClient,
    TpuJobClient,
)
from data_accelerator_tpu.serve.storage import (
    JobRegistry,
    LocalDesignTimeStorage,
    LocalRuntimeStorage,
)

from test_serve_generation import make_gui


class FakeJobClient(TpuJobClient):
    """In-memory client (reference: DataX.Config.Test/Mock spark client)."""

    def __init__(self, fail_submits: int = 0):
        self.states = {}
        self.fail_submits = fail_submits
        self.submits = 0

    def submit(self, job):
        self.submits += 1
        if self.submits <= self.fail_submits:
            raise RuntimeError("transient submit failure")
        self.states[job["name"]] = JobState.Running
        job["state"] = JobState.Starting
        job["clientId"] = 4242
        return job

    def stop(self, job):
        self.states[job["name"]] = JobState.Idle
        job["state"] = JobState.Idle
        job["clientId"] = None
        return job

    def get_state(self, job):
        return self.states.get(job["name"], job.get("state") or JobState.Idle)


@pytest.fixture
def ops(tmp_path):
    design = LocalDesignTimeStorage(str(tmp_path / "design"))
    runtime = LocalRuntimeStorage(str(tmp_path / "runtime"))
    client = FakeJobClient()
    flow_ops = FlowOperation(design, runtime, job_client=client)
    return flow_ops, client


class TestJobOperation:
    def test_start_stop_sync(self, ops):
        flow_ops, client = ops
        flow_ops.save_flow(make_gui("JobFlow"))
        res = flow_ops.generate_configs("JobFlow")
        assert res.ok, res.errors
        [job] = flow_ops.start_jobs("JobFlow")
        assert job["state"] == JobState.Starting
        [job] = flow_ops.sync_jobs("JobFlow")
        assert job["state"] == JobState.Running
        [job] = flow_ops.stop_jobs("JobFlow")
        assert job["state"] == JobState.Idle

    def test_start_is_idempotent(self, ops):
        flow_ops, client = ops
        flow_ops.save_flow(make_gui("JobFlow"))
        flow_ops.generate_configs("JobFlow")
        flow_ops.start_jobs("JobFlow")
        flow_ops.start_jobs("JobFlow")
        assert client.submits == 1  # second start short-circuits on Running

    def test_retries_on_transient_failure(self, tmp_path):
        design = LocalDesignTimeStorage(str(tmp_path / "d2"))
        runtime = LocalRuntimeStorage(str(tmp_path / "r2"))
        client = FakeJobClient(fail_submits=2)
        flow_ops = FlowOperation(design, runtime, job_client=client)
        flow_ops.jobs.retry_interval_s = 0.01
        flow_ops.save_flow(make_gui("RetryFlow"))
        flow_ops.generate_configs("RetryFlow")
        [job] = flow_ops.start_jobs("RetryFlow")
        assert job["state"] == JobState.Starting
        assert client.submits == 3

    def test_restart(self, ops):
        flow_ops, client = ops
        flow_ops.jobs.retry_interval_s = 0.01
        flow_ops.save_flow(make_gui("JobFlow"))
        flow_ops.generate_configs("JobFlow")
        flow_ops.start_jobs("JobFlow")
        [job] = flow_ops.restart_jobs("JobFlow")
        assert job["state"] == JobState.Starting
        assert client.submits == 2

    def test_start_without_generate_raises(self, ops):
        flow_ops, _ = ops
        flow_ops.save_flow(make_gui("NoGen"))
        with pytest.raises(ValueError):
            flow_ops.start_jobs("NoGen")


class TestDeleteCascade:
    def test_delete_flow(self, ops):
        flow_ops, _ = ops
        flow_ops.save_flow(make_gui("DelFlow"))
        res = flow_ops.generate_configs("DelFlow")
        flow_ops.start_jobs("DelFlow")
        assert flow_ops.delete_flow("DelFlow")
        assert flow_ops.get_flow("DelFlow") is None
        assert flow_ops.registry.get(res.job_names[0]) is None
        assert not os.path.exists(res.conf_paths[0])

    def test_delete_missing(self, ops):
        flow_ops, _ = ops
        assert flow_ops.delete_flow("Nope") is False


@pytest.mark.slow
class TestLocalJobClient:
    def test_real_process_lifecycle(self, tmp_path):
        """LocalTests.cs analog: generated conf runs as a real child
        process; state transitions observed through the client."""
        design = LocalDesignTimeStorage(str(tmp_path / "design"))
        runtime = LocalRuntimeStorage(str(tmp_path / "runtime"))
        client = LocalJobClient(
            log_dir=str(tmp_path / "logs"),
            env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
        )
        flow_ops = FlowOperation(design, runtime, job_client=client)
        flow_ops.save_flow(make_gui("ProcFlow"))
        res = flow_ops.generate_configs("ProcFlow")
        assert res.ok, res.errors
        [job] = flow_ops.start_jobs("ProcFlow", batches=2)
        name = job["name"]
        job = flow_ops.jobs.wait_for_state(
            name, (JobState.Success, JobState.Error), timeout_s=120
        )
        log = open(os.path.join(str(tmp_path / "logs"), f"{name}.log")).read()
        assert job["state"] == JobState.Success, log[-2000:]
        assert "Input_DataXProcessedInput_Events_Count=100" in log


def test_control_plane_pins_itself_not_its_job_hosts(tmp_path):
    """The control plane pins ITS jax to the CPU through jax.config and
    leaves JAX_PLATFORMS as the operator set it, so the environment
    LocalJobClient hands a job host carries no cpu pin the operator did
    not ask for. Run in a fresh interpreter whose environment names an
    accelerator first, as a chip host's does."""
    import subprocess

    script = (
        "import json, os, subprocess\n"
        "from data_accelerator_tpu.serve.__main__ import pin_to_cpu\n"
        "from data_accelerator_tpu.serve.jobs import LocalJobClient\n"
        "pin_to_cpu()\n"
        "import jax\n"
        "seen = {}\n"
        "class Child:\n"
        "    pid = 1\n"
        "    def __init__(self, cmd, env=None, **kw): seen['env'] = env\n"
        "subprocess.Popen = Child\n"
        "LocalJobClient().submit({'name': 'j', 'confPath': 'x.conf'})\n"
        "print(json.dumps({\n"
        "    'own': jax.devices()[0].platform,\n"
        "    'config': jax.config.jax_platforms,\n"
        "    'environ': os.environ.get('JAX_PLATFORMS'),\n"
        "    'child': seen['env'].get('JAX_PLATFORMS'),\n"
        "}))\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "tpu,cpu"}
    done = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert done.returncode == 0, done.stderr[-2000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])
    assert got == {
        "own": "cpu", "config": "cpu",
        "environ": "tpu,cpu", "child": "tpu,cpu",
    }


def test_serve_main_pins_before_anything_else():
    """main() pins the backend first and says so; nothing in the serve
    package exports JAX_PLATFORMS."""
    import inspect

    from data_accelerator_tpu.serve import __main__ as serve_main

    body = inspect.getsource(serve_main.main)
    assert body.index("pin_to_cpu()") < body.index("FlowOperation(")
    assert 'environ["JAX_PLATFORMS"]' not in inspect.getsource(serve_main)
