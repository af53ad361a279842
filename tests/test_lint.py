"""tft-lint tier-1 gate: the whole suite runs clean over torchft_tpu/,
every pass's selftest passes, and a seeded violation of EACH pass is
caught (the suite must distrust itself before CI trusts it)."""

import os
import subprocess
import sys
import textwrap

import pytest

from torchft_tpu.analysis import PASSES, Project, run_passes
from torchft_tpu.analysis.cli import main as lint_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "torchft_tpu")


class TestSuiteIsClean:
    def test_tree_lints_clean_with_empty_baselines(self, capsys):
        """The acceptance bar: `python -m torchft_tpu.analysis torchft_tpu/`
        exits 0 — every project invariant holds on the shipped tree, with
        nothing grandfathered."""
        rc = lint_main([PKG])
        out = capsys.readouterr().out
        assert rc == 0, f"tft-lint found violations:\n{out}"
        assert "0 finding(s)" in out
        # nothing hides behind the baselines either
        assert "baselined" not in out

    def test_baseline_files_ship_empty(self):
        bdir = os.path.join(PKG, "analysis", "baselines")
        for p in PASSES:
            path = os.path.join(bdir, f"{p.id}.txt")
            assert os.path.isfile(path), f"missing baseline file for {p.id}"
            lines = [
                ln
                for ln in open(path, encoding="utf-8").read().splitlines()
                if ln.strip() and not ln.lstrip().startswith("#")
            ]
            assert lines == [], f"{p.id} baseline is not empty: {lines}"

    def test_module_entrypoint_subprocess(self):
        """The exact CI invocation, end to end."""
        proc = subprocess.run(
            [sys.executable, "-m", "torchft_tpu.analysis", "torchft_tpu/"],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr


class TestSelftests:
    @pytest.mark.parametrize("lint_pass", PASSES, ids=lambda p: p.id)
    def test_pass_selftest(self, lint_pass):
        lint_pass.selftest()  # raises SelftestError on miss

    def test_selftest_cli(self, capsys):
        assert lint_main(["--selftest"]) == 0


# One seeded violation per pass: source planted in a synthetic project
# tree; the named pass must flag it and the CLI must exit 1.
_SEEDED = {
    "lock-discipline": {
        "pkg/bad.py": textwrap.dedent(
            """
            import time, threading
            _lock = threading.Lock()
            def f():
                with _lock:
                    time.sleep(1)
            """
        ),
    },
    "env-hygiene": {
        "pkg/bad.py": 'import os\nX = os.environ.get("TORCHFT_SNEAKY", "")\n',
    },
    "metrics-sync": {
        "pkg/bad.py": (
            "from torchft_tpu.utils.metrics import counter\n"
            'M = counter("myapp_rogue_total", "wrong namespace")\n'
        ),
    },
    "metrics-cardinality": {
        "pkg/bad.py": textwrap.dedent(
            """
            from torchft_tpu.utils.metrics import gauge
            G = gauge("torchft_peer_lag", "d")
            def export(peers):
                for p in peers:
                    G.labels(peer=p.addr).set(p.lag)
            """
        ),
    },
    "retry-ban": {
        "pkg/bad.py": textwrap.dedent(
            """
            import time
            def fetch():
                while True:
                    try:
                        return do()
                    except ConnectionError:
                        time.sleep(1)
            """
        ),
    },
    "fault-coverage": {
        "pkg/utils/faults.py": 'KNOWN_SITES = ("pg.allreduce",)\n',
        "pkg/bad.py": (
            "from torchft_tpu.utils import faults\n"
            'faults.check("pg.allreduce")\n'
            'faults.check("pg.not_a_site")\n'
        ),
    },
    "plan-discipline": {
        "pkg/bad.py": textwrap.dedent(
            """
            from torchft_tpu.ops import topology

            def sneaky_side_channel(world):
                # peer-communication structure built OUTSIDE the plan
                # layer: invisible to the tft-plan verifier
                topo = topology.parse_topology("hosts:2", world)
                return topology.synthesize_plan(topo, 0)
            """
        ),
    },
    "span-vocab": {
        "pkg/manager.py": 'PROTOCOL_PHASES = ("ring", "commit")\n',
        "pkg/bad.py": textwrap.dedent(
            """
            def emit(tracer):
                # off-vocabulary name AND no flight-recorder reach
                tracer.export_span("made_up_phase", "t", 0, 1)
            """
        ),
    },
}


def _plant(tmp_path, files):
    (tmp_path / "docs").mkdir(exist_ok=True)
    (tmp_path / "docs" / "observability.md").write_text("")
    (tmp_path / "docs" / "robustness.md").write_text("`pg.allreduce`\n")
    paths = []
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(src)
        paths.append(str(path))
    return paths


class TestSeededViolations:
    @pytest.mark.parametrize("pass_id", sorted(_SEEDED), ids=str)
    def test_seeded_violation_is_caught(self, tmp_path, pass_id):
        paths = _plant(tmp_path, _SEEDED[pass_id])
        project = Project(str(tmp_path), paths)
        lint_pass = next(p for p in PASSES if p.id == pass_id)
        results = run_passes([lint_pass], project, baseline_dir=str(tmp_path / "nobase"))
        findings = [f for r in results for f in r.findings]
        assert findings, f"{pass_id} missed its seeded violation"
        assert any(f.pass_id == pass_id for f in findings)

    def test_cli_exits_nonzero_on_seeded_violation(self, tmp_path, capsys):
        paths = _plant(tmp_path, _SEEDED["retry-ban"])
        rc = lint_main([*paths, "--passes", "retry-ban", "--baseline-dir", str(tmp_path / "nb")])
        assert rc == 1
        assert "sleep-in-loop" in capsys.readouterr().out


class TestFragmentSpanFamily:
    """ISSUE 18: `fragment.*` is a first-class span family — the vocab
    pass must accept a well-formed fragment.hop emitter and still bite
    on a near-miss family name."""

    def _run(self, tmp_path, src):
        paths = _plant(tmp_path, {"pkg/frag.py": textwrap.dedent(src)})
        project = Project(str(tmp_path), paths)
        lint_pass = next(p for p in PASSES if p.id == "span-vocab")
        results = run_passes(
            [lint_pass], project, baseline_dir=str(tmp_path / "nb")
        )
        return [f for r in results for f in r.findings]

    def test_fragment_hop_span_with_flight_reach_is_clean(self, tmp_path):
        findings = self._run(
            tmp_path,
            """
            from torchft_tpu.utils import flightrecorder as _flightrec

            def note_hop(tracer):
                _flightrec.RECORDER.record(op="fragment.hop", status="ok")
                tracer.export_span("fragment.hop", "t", 0, 1)
            """,
        )
        assert findings == [], [f.message for f in findings]

    def test_near_miss_fragment_family_is_caught(self, tmp_path):
        findings = self._run(
            tmp_path,
            """
            from torchft_tpu.utils import flightrecorder as _flightrec

            def note_hop(tracer):
                _flightrec.RECORDER.record(op="fragments.hop", status="ok")
                tracer.export_span("fragments.hop", "t", 0, 1)
            """,
        )
        assert any(
            f.pass_id == "span-vocab" and "fragments.hop" in f.message
            for f in findings
        ), [f.message for f in findings]


class TestPhaseCallVocabulary:
    """ISSUE 24: the vocab pass follows the one span primitive — every
    ``phase(...)`` / ``_phase(...)`` literal comes from PROTOCOL_PHASES or
    PHASE_PARTS, and no second naming scheme lives under ``torchft``."""

    MANAGER = (
        'PROTOCOL_PHASES = ("ring", "commit", "heal_send")\n'
        'PHASE_PARTS = ("ring.d2h", "heal_send.hash")\n'
    )

    def _run(self, tmp_path, src):
        paths = _plant(tmp_path, {
            "pkg/manager.py": self.MANAGER,
            "pkg/mod.py": textwrap.dedent(src),
        })
        project = Project(str(tmp_path), paths)
        lint_pass = next(p for p in PASSES if p.id == "span-vocab")
        results = run_passes(
            [lint_pass], project, baseline_dir=str(tmp_path / "nb")
        )
        return [f for r in results for f in r.findings]

    def test_the_call_forms_of_the_tree_are_clean(self, tmp_path):
        findings = self._run(
            tmp_path,
            """
            from torchft_tpu.utils import tracing

            class M:
                def _phase(self, name, **attrs):
                    return tracing.phase(name, self.acc, **attrs)

                def step(self, sink):
                    with self._phase("commit"):
                        pass
                    ring = self._phase("ring").begin()
                    with tracing.under(ring), tracing.phase(".d2h"):
                        pass
                    with self._phase("heal_send"), tracing.phase(".hash"):
                        pass
            """,
        )
        assert findings == [], [f.message for f in findings]

    @pytest.mark.parametrize(
        "call, needle",
        [
            ('tracing.phase("ring.made_up", sink)', "ring.made_up"),
            ('tracing.phase("ring.d2h", sink)', "ring.d2h"),
            ('tracing.phase(".made_up")', ".made_up"),
            ('self._phase("made_up")', "made_up"),
            ('tracing.phase(name_from_elsewhere(), sink)', "not a literal"),
            (
                'jax.profiler.TraceAnnotation("torchft::manager::_pg::configure")',
                "torchft::manager",
            ),
            ('jax.profiler.TraceAnnotation("torchft.made_up")', "torchft.made_up"),
        ],
    )
    def test_off_vocabulary_call_is_caught(self, tmp_path, call, needle):
        findings = self._run(
            tmp_path,
            f"""
            def step(self, sink, tracing, jax):
                with {call}:
                    pass
            """,
        )
        assert any(
            f.pass_id == "span-vocab" and needle in f.message for f in findings
        ), [f.message for f in findings]

    def test_annotations_outside_the_prefix_are_not_ours(self, tmp_path):
        findings = self._run(
            tmp_path,
            """
            def step(jax):
                with jax.profiler.TraceAnnotation("bench.ring"):
                    pass
                with jax.profiler.TraceAnnotation("torchft.ring.d2h"):
                    pass
            """,
        )
        assert findings == [], [f.message for f in findings]


class TestBaselineWorkflow:
    def test_write_baseline_then_clean(self, tmp_path, capsys):
        """Grandfathering: --write-baseline makes a dirty tree pass, and
        the fingerprints are line-number-free (stable under edits above)."""
        paths = _plant(tmp_path, _SEEDED["retry-ban"])
        bdir = str(tmp_path / "baselines")
        assert lint_main([*paths, "--passes", "retry-ban", "--baseline-dir", bdir, "--write-baseline"]) == 0
        capsys.readouterr()
        assert lint_main([*paths, "--passes", "retry-ban", "--baseline-dir", bdir]) == 0
        out = capsys.readouterr().out
        assert "1 baselined" in out
        # shifting the finding down two lines must not churn the baseline
        bad = tmp_path / "pkg" / "bad.py"
        bad.write_text("# moved\n# down\n" + bad.read_text())
        assert lint_main([*paths, "--passes", "retry-ban", "--baseline-dir", bdir]) == 0

    def test_rewrite_baseline_keeps_grandfathered_findings(self, tmp_path, capsys):
        """--write-baseline twice in a row must be idempotent: the second
        write grandfathers the FULL finding set, not just the (already
        filtered, hence empty) fresh ones."""
        paths = _plant(tmp_path, _SEEDED["retry-ban"])
        bdir = str(tmp_path / "baselines")
        base = [*paths, "--passes", "retry-ban", "--baseline-dir", bdir]
        assert lint_main([*base, "--write-baseline"]) == 0
        assert lint_main([*base, "--write-baseline"]) == 0  # re-run: no erase
        capsys.readouterr()
        assert lint_main(base) == 0
        assert "1 baselined" in capsys.readouterr().out
