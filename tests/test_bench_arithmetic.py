"""Guard bench.py's MFU arithmetic: the published model-FLOPs formula and
peak-TFLOPs lookup are the credibility of the headline MFU number."""

import numpy as np
import pytest


class TestModelFlops:
    def _cfg(self):
        from torchft_tpu.models.transformer import TransformerConfig

        return TransformerConfig(
            vocab_size=32000, d_model=1536, n_heads=6, n_kv_heads=3,
            d_ff=4096, n_layers=16, max_seq_len=1024,
        )

    def test_param_count_matches_actual_tree(self):
        import jax

        from bench import _model_flops_per_step
        from torchft_tpu.models.transformer import init_params

        cfg = self._cfg()
        fl = _model_flops_per_step(cfg, batch=8, seq=1024)
        params = init_params(jax.random.PRNGKey(0), cfg)
        # matmul params = everything except norms and the (gather-only)
        # embedding; the TIED head reuses embed as a matmul, so add V*E
        leaves = jax.tree_util.tree_leaves_with_path(params)
        total = 0
        for path, leaf in leaves:
            name = str(path)
            if "norm" in name or "embed" in name:
                continue
            total += leaf.size
        total += cfg.vocab_size * cfg.d_model  # tied head
        assert fl["params_matmul"] == total, (fl["params_matmul"], total)

    def test_flops_formula_structure(self):
        from bench import _model_flops_per_step

        cfg = self._cfg()
        b, t = 8, 1024
        fl = _model_flops_per_step(cfg, b, t)
        n = fl["params_matmul"]
        mm = 6 * n * b * t
        attn = 3 * (2 * 2 * b * t * t * cfg.d_model) * cfg.n_layers
        assert fl["flops"] == mm + attn
        assert fl["tokens"] == b * t

    def test_peak_flops_lookup(self):
        from bench import _peak_flops

        assert _peak_flops("TPU v5 lite") == 197e12
        assert _peak_flops("TPU v4") == 275e12
        assert _peak_flops("TPU v6e") == 918e12
        with pytest.raises(ValueError, match="no published bf16 peak"):
            _peak_flops("Unknown Chip")


class TestCompactTailSummary:
    """The LAST bench stdout line must fit (and survive) the driver's
    2000-byte tail capture with the primary recovery metric intact
    (VERDICT r5 #2 — the r5 number was truncated out of the tail)."""

    def _fake_result(self):
        # representative of a real emission, padded so the FULL line is
        # far larger than the tail window
        return {
            "metric": "recovery_to_healthy_step_latency",
            "unit": "s",
            "value": 0.412,
            "vs_baseline": 0.412,
            "recovery_cycles_s": [0.398, 0.412, 0.455],
            "recovery_phases_ms": {
                "teardown": 12.0, "manager_init": 55.1, "quorum_rpc": 140.2,
                "pg_configure": 61.0, "heal_recv": 90.5, "ring": 33.3,
                "commit": 8.8,
            },
            "overhead_pct": 1.92,
            "crosscheck": {
                "converged_2pts": True, "gap_pts": 0.8,
                "noise_floor_bound": False,
                "pair_ratios": [1.01] * 64,  # bulk the full line
            },
            "model_overhead_pct": 0.12,
            "model": {
                "mfu_pct": 57.1, "step_ms": 225.0,
                "config": "d1536 L16 " * 40,
            },
            "diloco": {
                "shaped": {
                    "1.0": {"winner": "int8", "int8_speedup_x": 1.62,
                            "f32_sync_s": 9.1, "int8_sync_s": 5.6},
                    "0.5": {"winner": "int8", "int8_speedup_x": 2.4},
                    "0.1": {"winner": "int8", "int8_speedup_x": 3.4},
                },
                "wire_reduction_x": 3.99,
                "padding": ["x" * 100] * 40,
            },
            "serving": {
                "servers": 4, "clients": 8, "payload_mb": 2.0,
                "wire": "int8",
                "published_cps": 9.1, "delivered_total": 4000,
                "delivered_cps": 334.0, "fetch_p50_ms": 2.2,
                "fetch_p99_ms": 58.0, "failed_fetches": 0,
                "failovers": 27,
                "kill": {"victim": "bench0", "victim_children": 2,
                         "at_version": 55},
                "bitwise_identical_after_failover": True,
            },
            "ha": {
                "peers": 3, "lease_ms": 500, "trials": 3,
                "kill_to_quorum_p50_s": 0.81, "kill_to_quorum_max_s": 1.4,
                "kill_to_quorum_s": [0.7, 0.81, 1.4],
                "quorum_id_monotone": True, "term_advanced": True,
                "takeover_terms": [2, 2, 2],
            },
            "serving_depth": {
                "payload_mb": 2.0, "fragments": 8, "publishes": 3,
                "d3_rtt50_speedup_x": 2.1,
                "d3_rtt50_flat_p50_ms": 980.0,
                "d3_rtt50_stream_p50_ms": 466.0,
                "d3_rtt50_delta_p50_ms": 120.0,
                "d3_rtt50_staleness_p50_ms": 510.0,
                "d3_rtt50_frag_staleness_p50_ms": 410.0,
                "d3_rtt50_frag_staleness_max_ms": 495.0,
                "winner": "stream",
                "rtt_50ms": {"d3": {"flat_p50_ms": 980.0}},
            },
        }

    def test_summary_under_budget_with_primary_metric(self):
        import json

        from bench import COMPACT_SUMMARY_MAX_BYTES, compact_summary

        line = json.dumps(compact_summary(self._fake_result()))
        assert len(line.encode()) < COMPACT_SUMMARY_MAX_BYTES
        parsed = json.loads(line)
        assert parsed["metric"] == "recovery_to_healthy_step_latency"
        assert parsed["value"] == 0.412
        assert parsed["compact"] is True
        assert parsed["mfu_pct"] == 57.1
        assert parsed["overhead_pct"] == 1.92
        assert parsed["crosscheck"]["converged_2pts"] is True
        assert parsed["diloco_winners"]["0.5"]["winner"] == "int8"
        assert len(parsed["recovery_phases_ms_top"]) == 4
        # the serving headline survives the budget (ISSUE 12): sustained
        # checkpoints/sec, p99 fetch, and the post-failover verdict
        assert parsed["serving"]["published_cps"] == 9.1
        assert parsed["serving"]["fetch_p99_ms"] == 58.0
        assert parsed["serving"]["bitwise_identical_after_failover"] is True
        assert parsed["serving"]["failed_fetches"] == 0
        # the HA failover headline survives the budget (ISSUE 13):
        # leader-kill -> next-quorum latency + the monotonicity verdicts
        assert parsed["ha"]["kill_to_quorum_p50_s"] == 0.81
        assert parsed["ha"]["quorum_id_monotone"] is True
        assert parsed["ha"]["term_advanced"] is True
        # the fragment-provenance headline survives the budget
        # (ISSUE 18): per-fragment staleness spread at depth 3 / 50 ms
        assert parsed["fragments"]["stale_p50_ms"] == 410.0
        assert parsed["fragments"]["stale_max_ms"] == 495.0
        assert parsed["serving_depth"]["d3_rtt50_speedup_x"] == 2.1

    def test_tail_of_captured_emission_parses_to_summary(self):
        """Simulate the driver: capture full-result line + compact line,
        keep only the last 2000 bytes, parse the last complete line."""
        import json

        from bench import compact_summary, last_json_line

        result = self._fake_result()
        emission = (
            "recovery cycle 2: 0.455s phases {...}\n"  # stderr-ish noise
            + json.dumps(result) + "\n"
            + json.dumps(compact_summary(result)) + "\n"
        )
        assert len(json.dumps(result)) > 2000  # the r5 failure mode
        tail = emission[-2000:]
        parsed = last_json_line(tail)
        assert parsed["compact"] is True
        assert parsed["value"] == 0.412
        assert parsed["metric"] == "recovery_to_healthy_step_latency"

    def test_degrades_on_partial_result(self):
        from bench import compact_summary

        out = compact_summary({"error": "boom", "value": None})
        assert out["error"] == "boom"
        assert out["metric"] == "recovery_to_healthy_step_latency"

    def test_budget_enforced_on_pathological_input(self):
        import json

        from bench import COMPACT_SUMMARY_MAX_BYTES, compact_summary

        result = self._fake_result()
        # a phase dict with huge keys cannot push the line past budget
        result["recovery_phases_ms"] = {
            "phase_" + "x" * 300 + str(i): float(i) for i in range(8)
        }
        line = json.dumps(compact_summary(result))
        assert len(line.encode()) <= COMPACT_SUMMARY_MAX_BYTES
