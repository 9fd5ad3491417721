"""Elastic supervision: dying workers get the gang restarted.

Reference analog: torchrun elastic agent behavior the reference reaches through
``torch.distributed.run`` (``commands/launch.py:785-816``) and ``notebook_launcher``'s
``max_restarts`` (``launchers.py:40-104``).
"""

import os
import subprocess
import sys

import pytest

from accelerate_tpu.elastic import ElasticSupervisor, WorkerFailure
from accelerate_tpu.test_utils.testing import slow

CRASH_ONCE = """
import os, sys, time
flag = sys.argv[1]
rank = sys.argv[2]
if rank == "0" and not os.path.exists(flag):
    open(flag, "w").write("crashed")
    sys.exit(17)  # simulated preemption/crash on the first attempt
time.sleep(0.2)
sys.exit(0)
"""

HANG = """
import time
time.sleep(60)
"""


def _worker_cmd(body: str, *argv: str) -> list[str]:
    return [sys.executable, "-c", body, *argv]


def test_supervisor_restarts_after_worker_death(tmp_path):
    """Worker 0 dies on attempt 1; the gang restarts with a fresh coordinator and succeeds."""
    flag = str(tmp_path / "crashed_once")
    coordinators = []

    def make_plan(coordinator):
        coordinators.append(coordinator)
        return [(_worker_cmd(CRASH_ONCE, flag, str(rank)), None) for rank in range(2)]

    restarts = []
    sup = ElasticSupervisor(
        make_plan, max_restarts=2, monitor_interval=0.05,
        on_restart=lambda attempt, codes: restarts.append((attempt, codes)),
    )
    assert sup.run() == 0
    assert sup.attempts_used == 2
    assert os.path.exists(flag)
    assert len(coordinators) == 2 and coordinators[0] != coordinators[1], (
        "each attempt must get a fresh coordinator"
    )
    assert restarts and 17 in restarts[0][1], restarts


def test_supervisor_kills_survivors_on_failure(tmp_path):
    """When one worker dies, a hung survivor must be torn down, not waited on forever."""
    flag = str(tmp_path / "crashed_once")

    def make_plan(coordinator):
        return [
            (_worker_cmd(CRASH_ONCE, flag, "0"), None),  # dies with 17 on attempt 1
            (_worker_cmd(HANG), None),                   # would block a naive wait() loop
        ]

    sup = ElasticSupervisor(make_plan, max_restarts=0, monitor_interval=0.05, grace_period=1.0)
    with pytest.raises(WorkerFailure) as exc:
        sup.run()
    assert 17 in exc.value.exit_codes
    # The hung survivor was terminated (negative returncode = killed by signal).
    assert any(c is not None and c < 0 for c in exc.value.exit_codes), exc.value.exit_codes


def test_supervisor_exhausts_restart_budget(tmp_path):
    always_crash = "import sys; sys.exit(3)"

    def make_plan(coordinator):
        return [(_worker_cmd(always_crash), None)]

    sup = ElasticSupervisor(make_plan, max_restarts=1, monitor_interval=0.05)
    with pytest.raises(WorkerFailure, match="after 2 attempts"):
        sup.run()
    assert sup.attempts_used == 2


@slow
def test_multi_process_launcher_restarts_through_cli(tmp_path):
    """End-to-end: accelerate-tpu launch --multi-process --max-restarts restarts a script
    that crashes on its first run (simulated preemption) and then succeeds."""
    script = tmp_path / "train.py"
    flag = tmp_path / "first_attempt_crashed"
    script.write_text(
        "import os, sys\n"
        f"flag = {str(flag)!r}\n"
        "rank = os.environ.get('ACCELERATE_PROCESS_ID', '0')\n"
        "if rank == '0' and not os.path.exists(flag):\n"
        "    open(flag, 'w').write('x')\n"
        "    sys.exit(9)\n"
        "print('trained rank', rank)\n"
    )
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "ACCELERATE_USE_CPU": "true"}
    result = subprocess.run(
        [sys.executable, "-m", "accelerate_tpu.commands.launch",
         "--multi-process", "--num-processes", "2", "--max-restarts", "1",
         "--cpu", str(script)],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert result.returncode == 0, f"{result.stdout}\n{result.stderr}"
    assert flag.exists()


TRAIN_RESUME = '''
import os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import optax

from accelerate_tpu import Accelerator
from accelerate_tpu.data_loader import DataLoader
from accelerate_tpu.test_utils.training import (
    RegressionDataset,
    linear_regression_loss,
    make_regression_state,
)

ckpt_dir, out_path, crash_flag = sys.argv[1], sys.argv[2], sys.argv[3]

acc = Accelerator()
ds = RegressionDataset(length=32)
dl = acc.prepare(DataLoader(ds, batch_size=4))  # 8 deterministic batches = 8 steps
state = acc.create_train_state(make_regression_state(), optax.sgd(0.1))
step_fn = acc.build_train_step(linear_regression_loss)

start = 0
if os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir):
    state = acc.load_state(ckpt_dir, train_state=state)
    start = int(np.asarray(state.step))
    print(f"resumed from step {start}", flush=True)

for i, batch in enumerate(acc.skip_first_batches(dl, start), start=start):
    state, metrics = step_fn(state, batch)
    acc.save_state(ckpt_dir, train_state=state)
    if crash_flag != "none" and i == 3 and not os.path.exists(crash_flag):
        open(crash_flag, "w").write("preempted")
        os._exit(23)  # simulated TPU preemption mid-epoch, after the step-4 checkpoint

np.savez(out_path, a=np.asarray(state.params["a"]), b=np.asarray(state.params["b"]),
         step=int(np.asarray(state.step)))
'''


def test_preemption_resume_loss_parity(tmp_path):
    """The full preemption story end-to-end: train → checkpoint each step → worker killed
    mid-epoch → ElasticSupervisor restarts the gang → resume from the checkpoint
    (load_state + skip_first_batches) → final params exactly match an uninterrupted run.

    This is the integration of elastic supervision with L7 checkpointing —
    the 'TPU preemptions are routine' contract from SURVEY §7."""
    import numpy as np

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "ACCELERATE_USE_CPU": "true"}

    # Uninterrupted baseline.
    base_out = tmp_path / "baseline.npz"
    subprocess.run(
        _worker_cmd(TRAIN_RESUME, str(tmp_path / "ckpt_base"), str(base_out), "none"),
        check=True, env=env, timeout=300,
    )

    # Preempted + supervised run: attempt 1 dies at step 4, attempt 2 resumes and finishes.
    crash_flag = tmp_path / "preempted"
    resumed_out = tmp_path / "resumed.npz"

    def make_plan(coordinator):
        return [(
            _worker_cmd(TRAIN_RESUME, str(tmp_path / "ckpt_elastic"), str(resumed_out),
                        str(crash_flag)),
            env,
        )]

    sup = ElasticSupervisor(make_plan, max_restarts=2, monitor_interval=0.1)
    assert sup.run() == 0
    assert sup.attempts_used == 2, "the simulated preemption must have triggered a restart"
    assert crash_flag.exists()

    base, resumed = np.load(base_out), np.load(resumed_out)
    assert int(resumed["step"]) == int(base["step"]) == 8
    np.testing.assert_allclose(resumed["a"], base["a"], rtol=0, atol=0)
    np.testing.assert_allclose(resumed["b"], base["b"], rtol=0, atol=0)


def test_restart_emits_telemetry_record(tmp_path):
    """A gang restart is a telemetry event, not just a log line: with an enabled
    Telemetry attached, each restart emits an elastic.restart/v1 record carrying
    the attempt index and the exit codes that triggered the teardown."""
    from accelerate_tpu.telemetry import ELASTIC_RESTART_SCHEMA, Telemetry
    from accelerate_tpu.utils.dataclasses import TelemetryConfig

    flag = str(tmp_path / "crashed_once")
    tel = Telemetry(TelemetryConfig(
        enabled=True, compile_events=False, memory_stats=False
    ))

    def make_plan(coordinator):
        return [(_worker_cmd(CRASH_ONCE, flag, str(rank)), None) for rank in range(2)]

    sup = ElasticSupervisor(
        make_plan, max_restarts=2, monitor_interval=0.05, telemetry=tel
    )
    assert sup.run() == 0
    records = [r for r in tel.records if r.get("schema") == ELASTIC_RESTART_SCHEMA]
    assert len(records) == 1, records
    assert records[0]["attempt"] == 0
    assert 17 in records[0]["exit_codes"]
    assert records[0]["max_restarts"] == 2
    # ISSUE 10 satellite: the record names WHICH gang (registry-required key).
    from accelerate_tpu.telemetry.schemas import validate_record

    assert records[0]["gang_id"] == "gang0"
    assert validate_record(records[0]) == []


def test_terminal_attempt_emits_final_record(tmp_path):
    """ISSUE 9 satellite: the restart record is emitted for the attempt that
    EXHAUSTS the budget too (previously skipped — the most important restart
    event never reached telemetry), flagged ``final``; on_restart fires for it
    as well."""
    from accelerate_tpu.telemetry import ELASTIC_RESTART_SCHEMA, Telemetry
    from accelerate_tpu.utils.dataclasses import TelemetryConfig

    tel = Telemetry(TelemetryConfig(
        enabled=True, compile_events=False, memory_stats=False
    ))
    hooks = []

    def make_plan(coordinator):
        return [(_worker_cmd("import sys; sys.exit(3)"), None)]

    sup = ElasticSupervisor(
        make_plan, max_restarts=1, monitor_interval=0.05, telemetry=tel,
        on_restart=lambda attempt, codes: hooks.append((attempt, codes)),
    )
    with pytest.raises(WorkerFailure):
        sup.run()
    records = [r for r in tel.records if r.get("schema") == ELASTIC_RESTART_SCHEMA]
    assert len(records) == 2, records
    assert [r["final"] for r in records] == [False, True]
    assert all(3 in r["exit_codes"] for r in records)
    assert [h[0] for h in hooks] == [0, 1]


def test_restart_backoff_spacing(tmp_path, monkeypatch):
    """restart_backoff sleeps exponentially (backoff x 2^attempt) BETWEEN
    restarts — never after the terminal attempt — and default 0 preserves the
    historical immediate restart."""
    sleeps = []

    import accelerate_tpu.elastic as elastic_mod

    orig_sleep = elastic_mod.time.sleep

    def record_sleep(s):
        if s >= 0.5:  # backoff sleeps only (monitor interval is 0.05)
            sleeps.append(s)
        else:
            orig_sleep(s)

    monkeypatch.setattr(elastic_mod.time, "sleep", record_sleep)

    def make_plan(coordinator):
        return [(_worker_cmd("import sys; sys.exit(3)"), None)]

    sup = ElasticSupervisor(make_plan, max_restarts=2, monitor_interval=0.05,
                            restart_backoff=0.5)
    with pytest.raises(WorkerFailure):
        sup.run()
    # 3 attempts -> 2 restarts -> 2 backoff sleeps: 0.5, 1.0 (no jitter)
    assert sleeps == [0.5, 1.0], sleeps

    sleeps.clear()
    sup = ElasticSupervisor(make_plan, max_restarts=1, monitor_interval=0.05)
    with pytest.raises(WorkerFailure):
        sup.run()
    assert sleeps == []  # default: immediate restart, unchanged


def test_backoff_jitter_bounds():
    sup = ElasticSupervisor(lambda c: [], restart_backoff=1.0,
                            backoff_jitter=0.5)
    for attempt in range(3):
        for _ in range(20):
            d = sup._backoff_delay(attempt)
            base = 1.0 * 2 ** attempt
            assert 0.5 * base <= d <= 1.5 * base
    with pytest.raises(ValueError, match="backoff_jitter"):
        ElasticSupervisor(lambda c: [], backoff_jitter=2.0)
    with pytest.raises(ValueError, match="restart_backoff"):
        ElasticSupervisor(lambda c: [], restart_backoff=-1.0)


def test_attempt_timeout_tears_down_hung_gang(tmp_path):
    """ISSUE 9 satellite: a gang where one worker exits 0 and another hangs
    forever used to be monitored forever — attempt_timeout is the liveness
    horizon that tears it down and counts the attempt as failed."""
    from accelerate_tpu.telemetry import ELASTIC_RESTART_SCHEMA, Telemetry
    from accelerate_tpu.utils.dataclasses import TelemetryConfig

    tel = Telemetry(TelemetryConfig(
        enabled=True, compile_events=False, memory_stats=False
    ))

    def make_plan(coordinator):
        return [
            (_worker_cmd("import sys; sys.exit(0)"), None),  # exits 0 early
            (_worker_cmd(HANG), None),                       # hangs forever
        ]

    sup = ElasticSupervisor(make_plan, max_restarts=0, monitor_interval=0.05,
                            grace_period=1.0, attempt_timeout=1.0,
                            telemetry=tel)
    with pytest.raises(WorkerFailure, match="timed out"):
        sup.run()
    assert sup.attempt_timeouts == 1
    records = [r for r in tel.records if r.get("schema") == ELASTIC_RESTART_SCHEMA]
    assert len(records) == 1 and records[0]["timeout"] is True
    assert records[0]["final"] is True


def test_no_restart_no_telemetry_record(tmp_path):
    """A clean run emits no restart records; a disabled Telemetry is never written to."""
    from accelerate_tpu.telemetry import ELASTIC_RESTART_SCHEMA, Telemetry
    from accelerate_tpu.utils.dataclasses import TelemetryConfig

    tel = Telemetry(TelemetryConfig(
        enabled=True, compile_events=False, memory_stats=False
    ))

    def make_plan(coordinator):
        return [(_worker_cmd("import sys; sys.exit(0)"), None)]

    sup = ElasticSupervisor(make_plan, max_restarts=1, monitor_interval=0.05,
                            telemetry=tel)
    assert sup.run() == 0
    assert not [r for r in tel.records if r.get("schema") == ELASTIC_RESTART_SCHEMA]


# ---------------------------------------------------------------- fleet supervisor
def test_fleet_supervisor_independent_per_gang_budgets():
    """ISSUE 10 satellite: each gang owns its restart budget and backoff
    schedule — one flapping replica cannot consume its neighbors' budget, and
    every failure (including the budget-exhausting one) emits an
    elastic.restart/v1 record carrying the gang_id."""
    from accelerate_tpu.elastic import FleetSupervisor
    from accelerate_tpu.telemetry import ELASTIC_RESTART_SCHEMA, Telemetry
    from accelerate_tpu.telemetry.schemas import validate_record
    from accelerate_tpu.utils.dataclasses import TelemetryConfig

    class Clock:
        t = 100.0

        def __call__(self):
            return self.t

    clock = Clock()
    tel = Telemetry(TelemetryConfig(enabled=True, compile_events=False,
                                    memory_stats=False))
    sup = FleetSupervisor(max_restarts=1, restart_backoff=2.0,
                          telemetry=tel, clock=clock)
    assert sup.may_restart("replica0") and sup.may_restart("replica1")

    # First failure of replica0: restart in budget, gated by the backoff.
    assert sup.record_failure("replica0", reason="crash") is True
    assert not sup.may_restart("replica0")         # backoff (2s) not elapsed
    assert sup.restart_at("replica0") == 102.0     # base * 2^0
    clock.t = 102.5
    assert sup.may_restart("replica0")
    # replica1 is untouched by replica0's history.
    assert sup.attempts_used("replica1") == 0 and sup.may_restart("replica1")

    # Second failure exhausts replica0's budget; replica1 keeps its own.
    assert sup.record_failure("replica0", reason="crash") is False
    assert not sup.budget_left("replica0")
    assert not sup.may_restart("replica0")
    assert sup.budget_left("replica1")
    assert sup.stats()["exhausted"] == ["replica0"]

    records = [r for r in tel.records
               if r.get("schema") == ELASTIC_RESTART_SCHEMA]
    assert [r["gang_id"] for r in records] == ["replica0", "replica0"]
    assert [r["attempt"] for r in records] == [0, 1]
    assert [r["final"] for r in records] == [False, True]
    assert all(validate_record(r) == [] for r in records)


def test_fleet_supervisor_validation():
    from accelerate_tpu.elastic import FleetSupervisor

    with pytest.raises(ValueError, match="max_restarts"):
        FleetSupervisor(max_restarts=-1)
    with pytest.raises(ValueError, match="restart_backoff"):
        FleetSupervisor(restart_backoff=-0.1)
    with pytest.raises(ValueError, match="backoff_jitter"):
        FleetSupervisor(backoff_jitter=1.5)


def test_supervisor_gang_id_param(tmp_path):
    """A non-default gang_id threads into the restart record."""
    from accelerate_tpu.telemetry import ELASTIC_RESTART_SCHEMA, Telemetry
    from accelerate_tpu.utils.dataclasses import TelemetryConfig

    flag = str(tmp_path / "crashed_once")
    tel = Telemetry(TelemetryConfig(enabled=True, compile_events=False,
                                    memory_stats=False))

    def make_plan(coordinator):
        return [(_worker_cmd(CRASH_ONCE, flag, "0"), None)]

    sup = ElasticSupervisor(make_plan, max_restarts=1, monitor_interval=0.05,
                            telemetry=tel, gang_id="train-gang-3")
    assert sup.run() == 0
    (record,) = [r for r in tel.records
                 if r.get("schema") == ELASTIC_RESTART_SCHEMA]
    assert record["gang_id"] == "train-gang-3"
