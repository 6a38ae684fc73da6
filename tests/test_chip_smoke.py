"""``chip_smoke.py`` and the start-up rules it stands on, without a chip.

The smoke's phases are functions of a ``Size``; here they run at toy size on
the virtual CPU mesh with the platform check left out (``run_phases`` never
calls it) and the kernels interpreted. What only a chip can show — Mosaic's
compile, bf16 parity, the cache across processes — is the smoke's own job.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

from deeplearning_mpi_tpu.compiler import cache  # noqa: E402
from deeplearning_mpi_tpu.telemetry import flops  # noqa: E402

# float32: XLA:CPU cannot run the interpreted kernels' bf16 dots.
TOY = chip_smoke.Size(
    num_layers=2, d_model=32, num_heads=2, head_dim=16, d_ff=64,
    dtype="float32", seq_len=64, batch=1, learning_rate=1e-2,
    max_slots=2, block_size=8, max_blocks_per_seq=4, num_blocks=12,
    prefill_chunk=8, num_requests=2, prompt_len_min=4, prompt_len_max=10,
    max_new_tokens=4, handoff_requests=1, window=16, wide_head_dim=32,
    decode_len=512, decode_batches=(2,), decode_window=200,
)


def test_phases_at_toy_size(tmp_path):
    """Every phase, multi-device ones included (the harness has 8 virtual
    devices, so the trainer runs data-parallel, then data x model + ZeRO-1,
    and the ring-flash case joins the kernels)."""
    results = chip_smoke.run_phases(
        TOY, tmp_path, platform="cpu", interpret=True
    )
    n = jax.device_count()
    assert list(results) == [
        "kernels", "hello_world", "train", "train_dp_tp_zero",
        "serve_selftest", "serve_handoff",
    ]
    assert f"ring_flash_sp{n}" in results["kernels"]
    for name in ("train", "train_dp_tp_zero"):
        assert results[name]["devices"] == n
        assert results[name]["steps"] == 9
        assert results[name]["loss_last"] < results[name]["loss_first"]
    assert results["serve_selftest"]["tokens"] == 2 * TOY.max_new_tokens
    assert results["serve_handoff"]["requests"] == 1


def test_a_failed_check_fails_the_phase(tmp_path):
    """Nothing is caught and carried on from: a kernel that misses its
    reference raises out of the phase."""
    with pytest.raises(RuntimeError, match="exceeds"):
        chip_smoke._run_kernel_case(
            "broken", lambda x: x + 1.0, lambda x: x, (jnp.ones((8,)),),
            interpret=True, min_mosaic=0, tol=3e-2,
        )


def test_without_a_tpu_it_exits_nonzero_naming_the_platform():
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert proc.stdout.strip() == ""  # no result of any kind


def test_compile_cache_goes_where_the_environment_says(monkeypatch):
    updates = {}
    monkeypatch.setattr(
        cache.jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert cache.configure() == Path("/x")
    assert "jax_compilation_cache_dir" not in updates  # none set in code

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cache.configure() == REPO / ".jax_cache"
    assert updates["jax_compilation_cache_dir"] == str(REPO / ".jax_cache")


def test_only_the_cache_module_places_the_cache():
    """With JAX_COMPILATION_CACHE_DIR set, nothing in the tree may point
    jax's cache elsewhere: the one place that writes the option is
    compiler/cache.py."""
    offenders = [
        str(p.relative_to(REPO))
        for root in ("deeplearning_mpi_tpu", "tools")
        for p in (REPO / root).rglob("*.py")
        if "jax_compilation_cache_dir" in p.read_text()
        and p != REPO / "deeplearning_mpi_tpu" / "compiler" / "cache.py"
    ]
    offenders += [
        name for name in ("bench.py", "chip_smoke.py", "__graft_entry__.py")
        if "jax_compilation_cache_dir" in (REPO / name).read_text()
    ]
    assert offenders == []


class _Device:
    def __init__(self, kind, platform="tpu"):
        self.device_kind, self.platform = kind, platform


def test_peak_rates_follow_the_reported_device_kind(monkeypatch):
    monkeypatch.delenv("DMT_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("DMT_LINK_BANDWIDTH", raising=False)
    # What a v5e really reports (BENCH_r04.json, chip run of PR 21).
    assert flops.device_peak_flops(_Device("TPU v5 lite")) == 197e12
    assert flops.device_link_bandwidth(_Device("TPU v5 lite")) == 200e9
    assert flops.device_peak_flops(_Device("TPU v6 lite")) == 918e12
    assert flops.device_peak_flops(_Device("TPU v4")) == 275e12
    for fn in (flops.device_peak_flops, flops.device_link_bandwidth):
        with pytest.raises(ValueError, match="unknown TPU device_kind"):
            fn(_Device("TPU v9 mega"))
    assert (
        flops.device_peak_flops(_Device("cpu", "cpu"))
        == flops.CPU_NOMINAL_PEAK_FLOPS
    )


def test_supervisors_refuse_to_share_a_tpu(tmp_path):
    """N jax workers on one host cannot each own the TPU: refused at spawn
    with one line, not left to hang on the device lock."""
    from deeplearning_mpi_tpu.resilience.cluster import workers_would_share_tpu
    from deeplearning_mpi_tpu.resilience.pod import PodFailure, PodSupervisor
    from deeplearning_mpi_tpu.serving import FleetFailure, FleetSupervisor

    assert workers_would_share_tpu({"JAX_PLATFORMS": "tpu,cpu"}, 2)
    assert not workers_would_share_tpu({"JAX_PLATFORMS": "tpu"}, 1)
    assert not workers_would_share_tpu({"JAX_PLATFORMS": "cpu"}, 4)
    assert not workers_would_share_tpu({}, 4)  # no chips on this host

    with pytest.raises(PodFailure, match="one process at a time"):
        PodSupervisor(
            [sys.executable, "-c", "pass"], 2, tmp_path / "pod",
            env={"JAX_PLATFORMS": "tpu"},
        ).run()
    fleet = FleetSupervisor(
        {"vocab_size": 256, "num_layers": 1, "num_heads": 1, "head_dim": 8,
         "d_model": 8, "d_ff": 16},
        {"max_slots": 1, "block_size": 8, "num_blocks": 4,
         "max_blocks_per_seq": 2, "prefill_chunk": 8, "max_queue": 4},
        2, tmp_path / "fleet", env={"JAX_PLATFORMS": "tpu"},
    )
    with pytest.raises(FleetFailure, match="one process at a time"):
        fleet.run([{"arrival": 0.0, "prompt": [1, 2, 3], "max_new": 1,
                    "deadline": 0.0}])


def test_first_divergence_margin_tells_a_tie_from_a_divergence():
    """serve_lm --selftest's rule for a reduced-precision dtype: a first
    divergence is a rounding tie only where the float32 margin is within
    twice the forward's own rounding error. In float32 that error is 0, so
    any token but the argmax is a real divergence."""
    from types import SimpleNamespace

    from deeplearning_mpi_tpu.cli.serve_lm import _first_divergence_margins
    from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM

    cfg = TransformerConfig.tiny()
    model = TransformerLM(config=cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    prompt = np.arange(1, 7, dtype=np.int32)
    logits = np.asarray(model.apply({"params": params}, prompt[None])[0, -1])
    top, bottom = int(logits.argmax()), int(logits.argmin())
    req = SimpleNamespace(
        prompt=prompt, prompt_len=6, max_new_tokens=4, generated=[top, 0, 0, 0]
    )
    (same, far) = _first_divergence_margins(
        cfg, model, params, [(req, 0, top, top), (req, 0, top, bottom)]
    )
    assert same[2] == 0.0 and same[3] < 1e-5  # margin 0: a tie
    assert far[2] > 2 * far[3]  # the worst token: a real divergence
