"""bench.py hides neither a failure nor the device.

The orchestrating parent never imports jax (a chip belongs to one process at
a time, so a parent that had touched jax would hold the chip its children
need), every workload runs as a ``--only`` child pinned to ``--platform``
(default ``tpu``), a failed workload makes the exit code non-zero, and every
result line names the device it ran on. No workload is rerun on the CPU
under a chip cell's name.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

# What remains is cifar_32px and allreduce — here both fail at backend
# start-up, in seconds: the sandbox has no TPU and the default platform is tpu.
FAST_FLAGS = [
    "--skip_224", "--skip_lm", "--skip_unet", "--skip_decode", "--skip_spec",
    "--skip_fleet", "--skip_disagg", "--skip_prefix", "--skip_slo",
]


def test_failed_children_exit_nonzero_and_parent_never_imports_jax():
    """No TPU here, so every child dies at ``jax.devices()``: the round must
    say so with its exit code (the old orchestrator returned 0 whatever
    failed), still emit the combined line, and the parent must have stayed
    off jax throughout."""
    code = (
        "import runpy, sys\n"
        f"sys.argv = [{BENCH!r}, *{FAST_FLAGS!r}]\n"
        "try:\n"
        f"    runpy.run_path({BENCH!r}, run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    rc = e.code\n"
        "print('JAX_IN_PARENT', 'jax' in sys.modules)\n"
        "sys.exit(rc)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert proc.returncode == 1, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[-1] == "JAX_IN_PARENT False"
    combined = json.loads(lines[-2])
    assert combined["value"] is None
    assert "cifar_32px" in combined["error"]
    for key in ("cifar_32px", "allreduce"):
        assert "failed" in combined["details"][key]
        # Failed is failed: no CPU rerun dressed up as a result.
        assert "degraded" not in combined["details"][key]


def test_child_result_names_its_device():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, BENCH, "--only", "allreduce", "--platform", "cpu"],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    detail = json.loads(proc.stdout.strip().splitlines()[-1])["detail"]
    assert detail["platform"] == "cpu"
    assert detail["device_kind"] == "cpu"
    assert detail["device_count"] == 1


def test_parent_lines_carry_device_and_any_failure_fails_the_round(
    monkeypatch, capsys
):
    """The parent's own plumbing, children canned: per-workload lines copy
    platform / device_kind / device_count from the child's detail, and one
    failed workload among successes still makes the round exit non-zero."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_under_test", BENCH)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)

    device = {"platform": "tpu", "device_kind": "TPU v5 lite",
              "device_count": 1}

    def canned(key, argv, budget_s):
        if key == "allreduce":
            return {"failed": "workload exited 1 without a result line"}
        return {"images_per_s_per_chip": 123.0, **device}

    monkeypatch.setattr(bench, "_run_isolated", canned)
    assert bench.main(FAST_FLAGS) == 1
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    cifar = next(
        ln for ln in lines if ln.get("metric", "").startswith("resnet50_bf16_cifar32")
    )
    assert cifar["value"] == 123.0
    assert {k: cifar[k] for k in device} == device
    assert lines[-1]["error"] == "failed workloads: ['allreduce']"

    monkeypatch.setattr(
        bench, "_run_isolated",
        lambda key, argv, budget_s: {
            "all_reduce_ms_mean": 0.1, "images_per_s_per_chip": 1.0, **device
        },
    )
    assert bench.main(FAST_FLAGS) == 0
