"""tft-verify tier-1 gate (model-checker leg).

Three proofs, mirroring tests/test_lint.py's trust ladder:

1. the UNMUTATED protocol model explores every bounded scenario clean,
   inside a hard wall-clock budget (the checker stays cheap enough for CI);
2. the mutation gate — each seeded protocol bug (skip the commit-failure
   quorum bump, heal from a stale source, drop the majority guard, ...)
   is provably caught by exactly the invariant that documents it;
3. a counterexample trace round-trips through torchft-diagnose and names
   the violating replica and phase, in the same vocabulary production
   flight dumps use.
"""

import json
import time

import pytest

from torchft_tpu import diagnose
from torchft_tpu.analysis import model_checker as mc
from torchft_tpu.analysis import protocol_model as pm
from torchft_tpu.analysis.verify_cli import main as verify_main
from torchft_tpu.manager import PROTOCOL_PHASES

#: tier-1 wall budget for the FULL clean exploration (ISSUE 7 acceptance:
#: 30 s; observed ~1 s on the dev container, so 30 s is pure headroom).
CLEAN_BUDGET_S = 30.0


class TestCleanExploration:
    def test_all_scenarios_explore_clean_within_budget(self):
        t0 = time.monotonic()
        for name, cfg in mc.SCENARIOS.items():
            r = mc.explore(cfg)
            assert r.ok, (
                f"scenario {name!r} violated {r.violation.invariant}: "
                f"{r.violation.message}\ntrace: {r.trace}"
            )
            assert r.states > 0 and r.transitions >= r.states - 1
        r = mc.explore_votes()
        assert r.ok, f"vote sub-model violated: {r.violation}"
        elapsed = time.monotonic() - t0
        assert elapsed < CLEAN_BUDGET_S, (
            f"clean exploration took {elapsed:.1f}s, budget {CLEAN_BUDGET_S}s"
        )

    def test_scenarios_reach_goals(self):
        """Every scenario that can make progress has goal states — a
        bounded space with zero goals would vacuously 'verify' nothing."""
        for name, cfg in mc.SCENARIOS.items():
            r = mc.explore(cfg)
            if name == "partition":
                # the one deliberately-stuck scenario: the majority guard
                # must HOLD the lone participant at bay, forever
                assert r.goal_states == 0
            else:
                assert r.goal_states > 0, f"{name} never reaches its goal"

    def test_partition_scenario_never_forms_quorum(self):
        """The split-brain guard, positively: with 2 of 3 replicas
        partitioned away (heartbeating, never joining), no quorum ever
        forms — the model has no 'form' transition in its entire space."""
        cfg = mc.SCENARIOS["partition"]
        st = pm.initial_state(cfg)
        assert all(
            t[0] != "form" for t in pm.enabled_transitions(cfg, st)
        )
        r = mc.explore(cfg)
        assert r.ok and r.goal_states == 0

    def test_exploration_is_deterministic(self):
        a = mc.explore(mc.SCENARIOS["churn"])
        b = mc.explore(mc.SCENARIOS["churn"])
        assert (a.states, a.transitions, a.goal_states) == (
            b.states,
            b.transitions,
            b.goal_states,
        )


class TestMutationGate:
    @pytest.mark.parametrize("mutation", pm.MUTATIONS, ids=lambda m: m.name)
    def test_seeded_protocol_bug_is_caught(self, mutation):
        r = mc.check_mutation(mutation.name)
        assert not r.ok, (
            f"mutation {mutation.name} explored clean — the checker "
            f"cannot see the bug class it documents"
        )
        assert r.violation is not None
        assert r.violation.invariant == mutation.catches, (
            f"mutation {mutation.name} caught by {r.violation.invariant}, "
            f"expected {mutation.catches}"
        )
        assert r.trace, "violation must carry a replayable trace"

    def test_every_mutation_has_a_scenario(self):
        assert set(mc.MUTATION_SCENARIOS) == {m.name for m in pm.MUTATIONS}
        for scenario in mc.MUTATION_SCENARIOS.values():
            assert (
                scenario == "votes"
                or scenario in mc.SCENARIOS
                or scenario in mc.RESIZE_SCENARIOS
                or scenario in mc.ELECTION_SCENARIOS
                or scenario in mc.RESTORE_SCENARIOS
            )

    def test_every_invariant_is_exercised_by_a_mutation(self):
        """No dead invariants: each safety predicate must be the catcher
        of record for at least one seeded bug (else we cannot know it can
        fire at all)."""
        caught = {m.catches for m in pm.MUTATIONS}
        assert set(pm.INVARIANTS) <= caught | {"vote-integrity"}
        assert "vote-integrity" in caught


class TestLiveness:
    @pytest.mark.parametrize(
        "schedule", mc.LIVENESS_SCHEDULES, ids=lambda s: s[0]
    )
    def test_fair_schedule_reaches_goal(self, schedule):
        name, scenario, rotation = schedule
        ok, used, trace = mc.run_schedule(mc.SCENARIOS[scenario], rotation)
        assert ok, (
            f"schedule {name} livelocked after {used} transitions; "
            f"tail: {trace[-10:]}"
        )


class TestVoteSubModel:
    def test_clean_barrier_space(self):
        r = mc.explore_votes(world=2, steps=2, drops=1)
        assert r.ok and r.goal_states > 0

    def test_resend_mutation_double_delivers(self):
        r = mc.explore_votes(mutations=frozenset({"resend_vote"}))
        assert not r.ok
        assert r.violation.invariant == "vote-integrity"


class TestResizeSubModel:
    """ISSUE 11: the online-parallelism-switching (resize) scenario —
    layout-epoch-monotone + all-commit-same-epoch proven over churn
    (crash mid-reshard, rejoin, failed transfers) and the two seeded
    switch-protocol bugs provably caught."""

    def test_clean_resize_space_reaches_switches(self):
        r = mc.explore_resize(mc.RESIZE_SCENARIOS["resize"])
        assert r.ok, f"resize scenario violated: {r.violation}"
        # non-vacuous: the bounded space contains completed switches
        assert r.goal_states > 0

    def test_exploration_is_deterministic(self):
        a = mc.explore_resize(mc.RESIZE_SCENARIOS["resize"])
        b = mc.explore_resize(mc.RESIZE_SCENARIOS["resize"])
        assert (a.states, a.transitions, a.goal_states) == (
            b.states, b.transitions, b.goal_states
        )

    def test_mixed_commit_splits_the_fleet(self):
        r = mc.explore_resize(
            mc.RESIZE_SCENARIOS["resize"],
            mutations=frozenset({"commit_mixed_epochs"}),
        )
        assert not r.ok
        assert r.violation.invariant == "all-commit-same-epoch"

    def test_epoch_reuse_after_rollback_is_caught(self):
        r = mc.explore_resize(
            mc.RESIZE_SCENARIOS["resize"],
            mutations=frozenset({"reuse_epoch_after_rollback"}),
        )
        assert not r.ok
        assert r.violation.invariant == "layout-epoch-monotone"

    def test_counterexample_renders_as_flight_dump(self, tmp_path):
        r = mc.check_mutation("commit_mixed_epochs")
        assert not r.ok and r.trace
        path = str(tmp_path / "resize_cex.jsonl")
        mc.write_flight_dump(r, path)
        lines = [json.loads(ln) for ln in open(path) if ln.strip()]
        assert lines[0]["flight"] == "meta"
        errs = [rec for rec in lines[1:] if rec["status"] == "error"]
        assert len(errs) == 1
        # the violating phase renders in the Manager's vocabulary
        assert errs[0]["op"] == "layout_commit"


class TestElectionSubModel:
    """ISSUE 13: the coordination-plane HA (leased leader election)
    scenario — at-most-one-leader-per-term, term monotonicity and
    quorum-id monotonicity across failover proven over candidacies,
    lease grants/expiry and a leader crash, with the two seeded
    election bugs provably caught by their named invariants."""

    def test_clean_election_space_reaches_quorums(self):
        r = mc.explore_election(mc.ELECTION_SCENARIOS["election"])
        assert r.ok, f"election scenario violated: {r.violation}"
        # non-vacuous: the bounded space contains post-takeover quorums
        assert r.goal_states > 0

    def test_exploration_is_deterministic(self):
        a = mc.explore_election(mc.ELECTION_SCENARIOS["election"])
        b = mc.explore_election(mc.ELECTION_SCENARIOS["election"])
        assert (a.states, a.transitions, a.goal_states) == (
            b.states, b.transitions, b.goal_states
        )

    def test_space_contains_takeovers(self):
        """The clean space must actually exercise failover: some path
        establishes two leaderships (else quorum-id-monotone-across-
        failover would be vacuously true)."""
        cfg = mc.ELECTION_SCENARIOS["election"]
        # a crash is enabled somewhere and the expire budget allows the
        # survivors' promises to lapse afterwards
        assert cfg.crash_budget >= 1
        assert cfg.expire_budget >= cfg.n_peers - 1

    def test_two_leaders_same_term_is_caught(self):
        r = mc.explore_election(
            mc.ELECTION_SCENARIOS["election"],
            mutations=frozenset({"two_leaders_same_term"}),
        )
        assert not r.ok
        assert r.violation.invariant == "at-most-one-leader-per-term"

    def test_reuse_quorum_seq_after_takeover_is_caught(self):
        r = mc.explore_election(
            mc.ELECTION_SCENARIOS["election"],
            mutations=frozenset({"reuse_quorum_seq_after_takeover"}),
        )
        assert not r.ok
        assert r.violation.invariant == "quorum-id-monotone-across-failover"

    def test_counterexample_renders_as_flight_dump(self, tmp_path):
        r = mc.check_mutation("two_leaders_same_term")
        assert not r.ok and r.trace
        path = str(tmp_path / "election_cex.jsonl")
        mc.write_flight_dump(r, path)
        lines = [json.loads(ln) for ln in open(path) if ln.strip()]
        assert lines[0]["flight"] == "meta"
        errs = [rec for rec in lines[1:] if rec["status"] == "error"]
        assert len(errs) == 1
        # the violating phase renders in the Manager's vocabulary
        assert errs[0]["op"] == "quorum_rpc"


class TestRestoreSubModel:
    """ISSUE 17: the durable-store cold-restore scenario — the fleet-wide
    cut selection must be complete (digest-valid bytes for every
    fragment), version-consistent (one outer sync, never a cross-version
    splice) and newest-first, proven over every per-disk spill order,
    one bit-rot and the whole-fleet crash, with both seeded restore bugs
    provably caught by their named invariants."""

    def test_clean_restore_space_reaches_restores(self):
        r = mc.explore_restore(mc.RESTORE_SCENARIOS["restore"])
        assert r.ok, f"restore scenario violated: {r.violation}"
        # non-vacuous: the bounded space contains completed restores
        assert r.goal_states > 0

    def test_exploration_is_deterministic(self):
        a = mc.explore_restore(mc.RESTORE_SCENARIOS["restore"])
        b = mc.explore_restore(mc.RESTORE_SCENARIOS["restore"])
        assert (a.states, a.transitions, a.goal_states) == (
            b.states, b.transitions, b.goal_states
        )

    def test_space_contains_torn_blobs_and_partial_spills(self):
        """The clean space must exercise the failure shapes the
        invariants guard against: a rot budget (torn blobs exist) and a
        mid-spill crash (incomplete newest versions exist) — else
        restore-cut-complete/-consistent would be vacuously true."""
        cfg = mc.RESTORE_SCENARIOS["restore"]
        assert cfg.rot_budget >= 1
        assert cfg.n_versions >= 2 and cfg.n_fragments >= 2

    def test_serve_torn_blob_is_caught(self):
        r = mc.explore_restore(
            mc.RESTORE_SCENARIOS["restore"],
            mutations=frozenset({"serve_torn_blob"}),
        )
        assert not r.ok
        assert r.violation.invariant == "restore-cut-complete"

    def test_mix_versions_in_cut_is_caught(self):
        r = mc.explore_restore(
            mc.RESTORE_SCENARIOS["restore"],
            mutations=frozenset({"mix_versions_in_cut"}),
        )
        assert not r.ok
        assert r.violation.invariant == "restore-cut-consistent"

    def test_counterexample_renders_as_flight_dump(self, tmp_path):
        r = mc.check_mutation("serve_torn_blob")
        assert not r.ok and r.trace
        path = str(tmp_path / "restore_cex.jsonl")
        mc.write_flight_dump(r, path)
        lines = [json.loads(ln) for ln in open(path) if ln.strip()]
        assert lines[0]["flight"] == "meta"
        errs = [rec for rec in lines[1:] if rec["status"] == "error"]
        assert len(errs) == 1
        # the violating phase renders in the Manager's vocabulary
        assert errs[0]["op"] == "heal_recv"


class TestDiagnoseRoundTrip:
    """Acceptance: a checker counterexample renders through
    torchft-diagnose and names the violating replica/phase."""

    def test_counterexample_names_replica_and_phase(self, tmp_path):
        r = mc.check_mutation("heal_from_stale")
        assert not r.ok
        path = str(tmp_path / "cex.jsonl")
        mc.write_flight_dump(r, path)
        entries, warnings = diagnose.load_records([path])
        report = diagnose.analyze(entries)
        v = r.violation
        assert report["failure"] is not None
        assert report["failure"]["reported_by"] == v.replica_id
        assert report["failure"]["phase"] == pm.MODEL_PHASE_OPS[v.phase]
        assert v.invariant in report["failure"]["detail"]
        # the culprit signal singles out the same replica with no
        # verify-specific logic in diagnose
        assert report["culprit"] is not None
        assert report["culprit"]["replica_id"] == v.replica_id
        text = diagnose.render_text(entries, report, warnings)
        assert v.replica_id in text and v.invariant in text

    def test_dump_is_valid_flight_dialect(self, tmp_path):
        r = mc.check_mutation("commit_despite_error")
        path = str(tmp_path / "cex.jsonl")
        mc.write_flight_dump(r, path)
        lines = [json.loads(ln) for ln in open(path) if ln.strip()]
        assert lines[0]["flight"] == "meta"
        assert all(rec["flight"] == "rec" for rec in lines[1:])
        # one error record exactly: the violation itself
        errs = [rec for rec in lines[1:] if rec["status"] == "error"]
        assert len(errs) == 1
        assert errs[0]["replica_id"] == r.violation.replica_id


class TestPhaseVocabulary:
    def test_model_ops_render_in_manager_phase_vocabulary(self):
        """Counterexample traces must speak the language operators know
        from production dumps: every model op maps into the Manager's
        canonical phase names ('crash' is the one model-only marker)."""
        allowed = set(PROTOCOL_PHASES) | {"crash"}
        assert set(pm.MODEL_PHASE_OPS.values()) <= allowed

    def test_manager_phase_vocabulary_matches_recorded_phases(self):
        """PROTOCOL_PHASES is the closed set of top-level names a
        ``tracing.phase`` is opened under (``self._phase("...")`` in the
        Manager, ``_tracing.phase("...")`` in the layers beneath), and
        PHASE_PARTS the closed set of parts — scan the sources so a new
        literal cannot drift past either."""
        import ast
        import inspect

        from torchft_tpu import manager as mgr
        from torchft_tpu.checkpointing import fragments, http_transport
        from torchft_tpu.parallel import process_group

        timed, counted = set(), set()
        for mod in (mgr, process_group, http_transport, fragments):
            tree = ast.parse(inspect.getsource(mod))
            for node in ast.walk(tree):
                if not (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                ):
                    continue
                # the name: a phase's first argument, add_seconds' second
                at = {"_phase": 0, "phase": 0, "add_seconds": 1}.get(node.func.attr)
                if (
                    at is None
                    or len(node.args) <= at
                    or not isinstance(node.args[at], ast.Constant)
                ):
                    continue
                # add_seconds: seconds put into a phase's sink beside its
                # timed parts (``heal_diff.hidden``), a part with no span
                (counted if at else timed).add(node.args[at].value)
        # no hand-rolled timing beside the primitive
        assert "_record_phase" not in inspect.getsource(mgr)
        top = {n for n in timed if "." not in n}
        assert top == set(PROTOCOL_PHASES)
        full = {n for n in timed if "." in n and not n.startswith(".")}
        relative = {n for n in timed if n.startswith(".")}
        assert full | counted <= set(mgr.PHASE_PARTS)
        assert all(any(p.endswith(r) for p in mgr.PHASE_PARTS) for r in relative)
        # every part is timed (or counted) somewhere, under its full name or
        # its last component, and its whole is a top-level phase or a part of one
        # (``ring.wire.arrive`` lies in ``ring.wire``, that in ``ring``)
        for part in mgr.PHASE_PARTS:
            whole, _, last = part.rpartition(".")
            assert whole in PROTOCOL_PHASES or whole in mgr.PHASE_PARTS
            assert part.partition(".")[0] in PROTOCOL_PHASES
            assert part in full | counted or "." + last in relative, part


class TestVerifyCli:
    def test_selftest_exits_zero(self, capsys):
        assert verify_main(["--selftest"]) == 0
        out = capsys.readouterr().out
        assert "caught" in out and "MISSED" not in out

    def test_unknown_scenario_exits_two(self, capsys):
        assert verify_main(["--scenario", "nope"]) == 2

    def test_mutate_dump_cli(self, tmp_path, capsys):
        path = str(tmp_path / "cex.jsonl")
        rc = verify_main(["--mutate", "drop_majority_guard", "--dump", path])
        assert rc == 1  # a violation was (correctly) found
        assert (tmp_path / "cex.jsonl").exists()

    def test_list_cli(self, capsys):
        assert verify_main(["--list"]) == 0
        out = capsys.readouterr().out
        for name in mc.SCENARIOS:
            assert f"scenario {name}" in out
