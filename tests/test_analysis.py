"""dmt-lint static passes + the DMT_SANITIZE runtime sanitizer.

Two halves, mirroring the analysis package itself:

- every rule must catch its seeded violation in ``tests/fixtures/lint/``
  at the exact ``file:line`` (and ONLY its own rule must fire there), the
  clean fixture must pass everything, and the repo tree itself must lint
  clean modulo the audited suppressions;
- the sanitizer must classify injected KV double-free / use-after-free,
  trip on a post-warmup retrace, and flip the donation canary on a
  mutated state leaf — while staying silent on the clean paths.
"""

import re
from pathlib import Path

import numpy as np
import pytest

from deeplearning_mpi_tpu.analysis import sanitizer
from deeplearning_mpi_tpu.analysis.core import (
    REPO_ROOT,
    Finding,
    SourceFile,
    load_suppressions,
    run_lint,
)
from deeplearning_mpi_tpu.analysis.lint import main as lint_main
from deeplearning_mpi_tpu.analysis.passes import all_rules

FIXTURES = Path(__file__).parent / "fixtures" / "lint"
SEEDED_RE = re.compile(r"#\s*seeded:\s*(DMT\d+)")


def _seeded(path: Path) -> tuple[str, int]:
    """(rule id, 1-based line) of the fixture's seeded-violation marker."""
    for lineno, line in enumerate(path.read_text().splitlines(), 1):
        m = SEEDED_RE.search(line)
        if m:
            return m.group(1), lineno
    raise AssertionError(f"no seeded marker in {path}")


def _fixture_files() -> list[Path]:
    files = sorted(FIXTURES.glob("viol_*.py"))
    assert len(files) >= 6, "fixture corpus must seed at least 6 rules"
    return files


class TestRuleCatalog:
    def test_every_rule_has_a_seeded_fixture(self):
        seeded_rules = {_seeded(f)[0] for f in _fixture_files()}
        assert seeded_rules == {r.id for r in all_rules()}

    @pytest.mark.parametrize("fixture", _fixture_files(), ids=lambda p: p.stem)
    def test_rule_catches_seeded_violation_at_exact_line(self, fixture):
        rule_id, line = _seeded(fixture)
        findings = run_lint([fixture], suppressions={})
        hits = [f for f in findings if not f.suppressed]
        assert [(f.rule, f.line) for f in hits] == [(rule_id, line)], (
            f"{fixture.name}: expected exactly ({rule_id}, {line}), got "
            f"{[(f.rule, f.path, f.line) for f in hits]}"
        )

    def test_clean_fixture_passes_every_rule(self):
        findings = run_lint([FIXTURES / "clean.py"], suppressions={})
        assert findings == []

    def test_corpus_catch_rate_is_total(self):
        """The acceptance property: 100% of seeded violations reported."""
        expected = {(f"tests/fixtures/lint/{p.name}",) + _seeded(p)
                    for p in _fixture_files()}
        findings = run_lint([FIXTURES], suppressions={})
        got = {(f.path, f.rule, f.line) for f in findings if not f.suppressed}
        assert got == expected

    def test_unparseable_file_is_a_framework_finding(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("def broken(:\n")
        findings = run_lint([bad], suppressions={})
        assert [f.rule for f in findings] == ["DMT000"]


class TestSuppressions:
    def test_inline_disable_suppresses_that_line_only(self, tmp_path):
        f = tmp_path / "mod.py"
        f.write_text(
            "def record(registry):\n"
            "    registry.counter('nope_a')  # dmt-lint: disable=DMT007 — test\n"
            "    registry.counter('nope_b')\n"
        )
        findings = run_lint([f], suppressions={})
        by_line = {x.line: x.suppressed for x in findings}
        assert by_line == {2: True, 3: False}

    def test_file_suppression_requires_justification(self, tmp_path):
        supp = tmp_path / "supp.txt"
        supp.write_text("some/file.py:DMT005:\n")
        with pytest.raises(ValueError, match="justification"):
            load_suppressions(supp)

    def test_file_suppression_applies_by_path_and_rule(self, tmp_path):
        supp = tmp_path / "supp.txt"
        supp.write_text("# comment\n\npkg/a.py:DMT005: audited writer\n")
        table = load_suppressions(supp)
        assert table == {("pkg/a.py", "DMT005"): "audited writer"}
        f = Finding("DMT005", "pkg/a.py", 3, "msg")
        findings = run_lint(
            [FIXTURES / "viol_jsonl.py"],
            suppressions={("tests/fixtures/lint/viol_jsonl.py", "DMT005"):
                          "fixture is the audited writer"},
        )
        assert all(x.suppressed for x in findings) and findings

    def test_repo_tree_lints_clean(self):
        """The `make lint` gate: zero unsuppressed findings on the repo,
        and every suppression carries its recorded justification."""
        findings = run_lint()
        loud = [f.render() for f in findings if not f.suppressed]
        assert loud == [], "\n".join(loud)
        assert all(f.justification for f in findings if f.suppressed)

    def test_cli_exit_codes(self, capsys):
        assert lint_main(["--no-suppressions", str(FIXTURES)]) == 1
        assert lint_main(["--no-suppressions", str(FIXTURES / "clean.py")]) == 0
        out = capsys.readouterr()
        assert "DMT001" in out.out
        assert "0 finding(s)" in out.err

    def test_suppression_file_entries_point_at_real_files(self):
        table = load_suppressions(REPO_ROOT / "tools" / "lint_suppressions.txt")
        assert table, "repo suppression file must parse"
        for (path, rule), why in table.items():
            assert (REPO_ROOT / path).is_file(), f"stale suppression: {path}"
            assert why


@pytest.fixture()
def sanitize_on(monkeypatch):
    monkeypatch.setenv("DMT_SANITIZE", "1")
    sanitizer.reset_trips()
    yield
    sanitizer.reset_trips()


class TestSanitizer:
    def test_disabled_by_default(self, monkeypatch):
        monkeypatch.delenv("DMT_SANITIZE", raising=False)
        assert not sanitizer.enabled()
        monkeypatch.setenv("DMT_SANITIZE", "0")
        assert not sanitizer.enabled()

    def test_kv_double_free_classified(self, sanitize_on):
        from deeplearning_mpi_tpu.serving.kv_pool import PagedKVPool

        pool = PagedKVPool(8, 4)
        blocks = pool.alloc(2)
        pool.free(blocks)
        with pytest.raises(sanitizer.SanitizerError, match="double free"):
            pool.free(blocks)
        assert sanitizer.trip_counts()[sanitizer.KV_DOUBLE_FREE] == 1

    def test_kv_use_after_free_classified(self, sanitize_on):
        from deeplearning_mpi_tpu.serving.kv_pool import PagedKVPool

        pool = PagedKVPool(8, 4)
        blocks = pool.alloc(2)
        pool.free(blocks)
        with pytest.raises(sanitizer.SanitizerError, match="use-after-free"):
            pool.record_fill(blocks)
        assert sanitizer.trip_counts()[sanitizer.KV_USE_AFTER_FREE] == 1

    def test_kv_clean_cycle_trips_nothing(self, sanitize_on):
        from deeplearning_mpi_tpu.serving.kv_pool import PagedKVPool

        pool = PagedKVPool(8, 4)
        for _ in range(3):
            blocks = pool.alloc(3)
            pool.record_fill(blocks)
            pool.free(blocks)
        pool.check()
        assert sanitizer.trip_counts() == {}

    def test_unallocated_free_stays_a_value_error(self, sanitize_on):
        """Never-allocated is a caller bug, not a poison trip — the
        classification boundary the sanitizer exists to draw."""
        from deeplearning_mpi_tpu.serving.kv_pool import PagedKVPool

        pool = PagedKVPool(8, 4)
        with pytest.raises(ValueError, match="not allocated"):
            pool.free([3])
        assert sanitizer.trip_counts() == {}

    def test_compile_tick_trips_only_post_warmup(self, sanitize_on):
        sanitizer.check_compile_tick(post_warmup=False)  # warmup: fine
        with pytest.raises(sanitizer.SanitizerError, match="AFTER warmup"):
            sanitizer.check_compile_tick(post_warmup=True)
        assert sanitizer.trip_counts()[sanitizer.RETRACE_TRIPS] == 1

    def test_engine_retrace_tripwire(self, sanitize_on):
        """A warmed engine must serve without tripping; a genuine
        post-warmup retrace (un-pretraced gather width) must trip."""
        import jax
        import jax.numpy as jnp

        from deeplearning_mpi_tpu.models import TransformerConfig, TransformerLM
        from deeplearning_mpi_tpu.serving.engine import EngineConfig, ServingEngine
        from deeplearning_mpi_tpu.serving.scheduler import RequestState

        cfg = TransformerConfig.tiny()
        model = TransformerLM(config=cfg, dtype=jnp.float32)
        params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))[
            "params"
        ]
        eng = ServingEngine(
            cfg, params,
            EngineConfig(max_slots=2, block_size=8, num_blocks=16,
                         max_blocks_per_seq=4, prefill_chunk=8, max_queue=8),
            dtype=jnp.float32,
        )
        eng.warmup()
        req = eng.submit(np.arange(1, 9, dtype=np.int32), 4)
        while not eng.scheduler.idle():
            eng.step()
        assert req.state is RequestState.FINISHED
        assert sanitizer.trip_counts().get(sanitizer.RETRACE_TRIPS, 0) == 0
        idle = jnp.zeros((2,), jnp.int32)
        with pytest.raises(sanitizer.SanitizerError, match="AFTER warmup"):
            eng._decode_jit(
                eng.params, eng._kv, jnp.zeros((2, 3), jnp.int32),
                idle, idle, jnp.zeros((2,), bool),
            )
        assert sanitizer.trip_counts()[sanitizer.RETRACE_TRIPS] == 1

    def test_donation_canary(self, sanitize_on):
        state = {"w": np.arange(12, dtype=np.float32), "b": np.zeros(2)}
        canary = sanitizer.donation_canary(state)
        canary.verify(state)  # untouched: clean
        state["b"][0] = 7.0
        with pytest.raises(sanitizer.SanitizerError, match="changed across"):
            canary.verify(state)
        assert sanitizer.trip_counts()[sanitizer.DONATION_TRIPS] == 1

    def test_trips_mirrored_into_registry(self, sanitize_on):
        from deeplearning_mpi_tpu.telemetry.registry import MetricsRegistry

        reg = MetricsRegistry()
        sanitizer.attach_registry(reg)
        try:
            with pytest.raises(sanitizer.SanitizerError):
                sanitizer.trip(sanitizer.RETRACE_TRIPS, "test trip")
            assert reg.counter(sanitizer.RETRACE_TRIPS).value == 1
        finally:
            sanitizer.attach_registry(None)


class TestSchemaCoversRepo:
    def test_schema_names_are_canonical_style(self):
        from deeplearning_mpi_tpu.telemetry.schema import METRICS

        for name in METRICS:
            assert re.fullmatch(r"[a-z][a-z0-9_]+", name), name

    def test_marker_parsing(self, tmp_path):
        f = tmp_path / "m.py"
        f.write_text(
            "# dmt-lint: scope=serving\n"
            "def loop():  # dmt-lint: hot-loop\n"
            "    pass\n"
        )
        src = SourceFile(f, f.read_text())
        assert src.declared_scope() == "serving"
        func = next(iter(src.functions()))
        assert src.is_marked_hot(func)
