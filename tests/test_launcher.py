"""Launcher: replica-group env injection, restart budget, chaos hook.

Mirrors the reference's launcher semantics (torchx component roles + env
triple + torchrun --max_restarts, reference torchft/torchx.py:11-83).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from torchft_tpu.launcher import ReplicaGroupLauncher, main, replica_app_spec


class TestReplicaAppSpec:
    def test_roles_and_env(self):
        spec = replica_app_spec(
            "--steps", "5", replicas=3, script="train.py", lighthouse="lh:1234"
        )
        assert len(spec["roles"]) == 3
        for i, role in enumerate(spec["roles"]):
            assert role["env"]["REPLICA_GROUP_ID"] == str(i)
            assert role["env"]["NUM_REPLICA_GROUPS"] == "3"
            assert role["env"]["TORCHFT_LIGHTHOUSE"] == "lh:1234"
            assert role["args"] == ["train.py", "--steps", "5"]

    def test_rejects_zero_replicas(self):
        with pytest.raises(ValueError):
            replica_app_spec(replicas=0)

    def test_caller_env_cannot_override_role_identity(self):
        # forwarding os.environ from a process that itself runs under the
        # launcher must not clobber the per-role triple
        spec = replica_app_spec(
            replicas=2,
            env={"REPLICA_GROUP_ID": "7", "NUM_REPLICA_GROUPS": "99", "FOO": "x"},
            lighthouse="lh:1",
        )
        for i, role in enumerate(spec["roles"]):
            assert role["env"]["REPLICA_GROUP_ID"] == str(i)
            assert role["env"]["NUM_REPLICA_GROUPS"] == "2"
            assert role["env"]["FOO"] == "x"


def _script(tmp_path, body):
    path = tmp_path / "worker.py"
    path.write_text(textwrap.dedent(body))
    return str(path)


class TestReplicaGroupLauncher:
    def test_env_injection_and_success(self, tmp_path):
        script = _script(
            tmp_path,
            f"""
            import os
            out = os.path.join({str(tmp_path)!r}, "out_" + os.environ["REPLICA_GROUP_ID"])
            with open(out, "w") as f:
                f.write(os.environ["NUM_REPLICA_GROUPS"] + " " +
                        os.environ["TORCHFT_LIGHTHOUSE"])
            """,
        )
        launcher = ReplicaGroupLauncher(
            [sys.executable, script], replicas=2, lighthouse_addr="lh:9999"
        )
        codes = launcher.run(timeout=60)
        assert codes == {0: 0, 1: 0}
        for r in range(2):
            content = (tmp_path / f"out_{r}").read_text()
            assert content == "2 lh:9999"

    def test_restart_budget_until_success(self, tmp_path):
        # fails until a marker file exists (created on first attempt), then
        # succeeds — exercises exactly one restart
        script = _script(
            tmp_path,
            f"""
            import os, sys
            marker = os.path.join({str(tmp_path)!r},
                                  "m_" + os.environ["REPLICA_GROUP_ID"])
            if not os.path.exists(marker):
                open(marker, "w").close()
                sys.exit(3)
            sys.exit(0)
            """,
        )
        launcher = ReplicaGroupLauncher(
            [sys.executable, script], replicas=2, max_restarts=2,
            lighthouse_addr="lh:9999", restart_backoff=0.0,
        )
        codes = launcher.run(timeout=60)
        assert codes == {0: 0, 1: 0}

    def test_refuses_to_spawn_while_holding_an_accelerator(self, monkeypatch):
        """One process per chip: trainers started by a parent whose own
        JAX backend sits on the accelerator would fail or hang."""
        import jax

        jax.devices()  # the backend is up (on the CPU here)
        launcher = ReplicaGroupLauncher(
            [sys.executable, "-c", "pass"], replicas=1, lighthouse_addr="x:1"
        )
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="holds the accelerator"):
            launcher.run(timeout=5)

    def test_max_restarts_exhausted(self, tmp_path):
        script = _script(tmp_path, "import sys; sys.exit(7)\n")
        launcher = ReplicaGroupLauncher(
            [sys.executable, script], replicas=1, max_restarts=1,
            lighthouse_addr="lh:9999", restart_backoff=0.0,
        )
        codes = launcher.run(timeout=60)
        assert codes == {0: 7}

    def test_local_lighthouse_spawned(self, tmp_path, monkeypatch):
        monkeypatch.delenv("TORCHFT_LIGHTHOUSE", raising=False)
        script = _script(
            tmp_path,
            f"""
            import os
            with open(os.path.join({str(tmp_path)!r}, "lh"), "w") as f:
                f.write(os.environ["TORCHFT_LIGHTHOUSE"])
            """,
        )
        launcher = ReplicaGroupLauncher([sys.executable, script], replicas=1)
        codes = launcher.run(timeout=60)
        assert codes == {0: 0}
        addr = (tmp_path / "lh").read_text()
        assert ":" in addr

    def test_cli_roundtrip(self, tmp_path):
        script = _script(tmp_path, "import sys; sys.exit(0)\n")
        rc = main(
            ["--replicas", "1", "--lighthouse", "lh:9", "--timeout", "60",
             "--", sys.executable, script]
        )
        assert rc == 0


class TestSlurmRunnerDryRun:
    def test_dry_run_emits_sbatch_lines(self):
        out = subprocess.run(
            [sys.executable, "examples/slurm_runner.py", "--replicas", "2",
             "--dry-run", "--", sys.executable, "examples/train_ddp.py"],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert out.returncode == 0, out.stderr
        lines = [l for l in out.stdout.splitlines() if l.startswith("sbatch")]
        assert len(lines) == 2
        assert "REPLICA_GROUP_ID=0" in lines[0]
        assert "REPLICA_GROUP_ID=1" in lines[1]
        assert "NUM_REPLICA_GROUPS=2" in lines[0]
        # wrapped command must be `<interpreter> <script> [args]` with the
        # leading `python` stripped, the script not duplicated
        assert lines[0].count("examples/train_ddp.py") == 1
        assert "python examples/train_ddp.py" not in lines[0].split("--wrap=")[0]

    def test_dry_run_with_script_args(self):
        out = subprocess.run(
            [sys.executable, "examples/slurm_runner.py", "--replicas", "1",
             "--dry-run", "--", "python", "examples/train_diloco.py",
             "--steps", "10"],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert out.returncode == 0, out.stderr
        (line,) = [l for l in out.stdout.splitlines() if l.startswith("sbatch")]
        assert line.count("examples/train_diloco.py") == 1
        assert "--steps 10" in line
