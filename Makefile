# Developer entry points.  The native core builds via native/Makefile
# (wheels trigger it from setup.py); this file wires the repo-level
# verification gates CI and humans share.

PYTHON ?= python

.PHONY: native verify lint typecheck plan-verify test tier1 trace-smoke reshard-smoke serve-smoke serve-soak ha-smoke heal-smoke links-smoke cold-restore-smoke fragments-smoke

native:
	$(MAKE) -C native

# The correctness gate: project-invariant lint (tft-lint), the protocol
# model checker's self-consistency (mutation gate + clean steady space +
# wire extractor selftest), then the full bounded exploration + liveness
# + wire-schema drift pass.  Exit code != 0 on any finding/violation.
verify:
	$(PYTHON) -m torchft_tpu.analysis torchft_tpu/
	$(PYTHON) -m torchft_tpu.analysis.verify_cli --selftest
	$(PYTHON) -m torchft_tpu.analysis.verify_cli

lint:
	$(PYTHON) -m torchft_tpu.analysis torchft_tpu/

# The tft-plan gate alone (ISSUE 19): exhaustive small-world plan
# enumeration on the reduction/serving/stripe planes + the seeded
# plan-mutation catalog, each caught by its named invariant.  Also part
# of the default `tft-verify` full gate (and therefore `make verify`).
plan-verify:
	$(PYTHON) -m torchft_tpu.analysis.verify_cli --scenario plan

# mypy strict over the analysis + utils layers (mirrors the slow-marked
# tests/test_typecheck.py gate); requires mypy on PATH.
typecheck:
	$(PYTHON) -m mypy --config-file mypy.ini torchft_tpu/analysis torchft_tpu/utils torchft_tpu/ops/topology.py

# tier-1: the default CI selection (ROADMAP.md).
tier1:
	$(PYTHON) -m pytest tests/ -m "not slow" -q

test: tier1

# Distributed-tracing round trip alone: live 2-replica + lighthouse run
# with a forced heal against the TORCHFT_TRACE_FILE span sink, ONE trace
# id per step across the fleet, and the diagnose critical-path ledger
# (docs/observability.md "Distributed tracing").
trace-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_tracing_integ.py -q -m "not slow"

# Online-parallelism-switching round trip alone: the live shrink/grow
# reshard integration incl. the tier-1 mid-reshard chaos tests (kill a
# replica between stage and commit -> completed switch without the
# victim or clean rollback, never a wedge; docs/architecture.md
# "Online parallelism switching").
reshard-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_reshard_integ.py -q -m "not slow"

# Weight-serving tier round trip alone: tree synthesis, payload codec,
# fan-out round trips, and the tier-1 chaos smoke — kill a tree node
# mid-fetch, clients complete from a failover source with
# bitwise-identical weights (docs/architecture.md "Weight-serving tier").
serve-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_serving.py -q -m "not slow"

# The slow serving soak: 32 stub clients against a churning tree with
# staggered server kills; asserts the p99 fetch bound and zero failed
# fetches after failover settles.
serve-soak:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_serving.py -q -m "slow"

# Coordination-plane HA round trip alone: 3 lighthouse subprocesses,
# SIGKILL the active leader mid-quorum-round and mid-serving-fetch —
# the fleet re-quorums with monotone term-prefixed quorum ids, serving
# clients complete bitwise-identical, never a wedge
# (docs/architecture.md "Coordination-plane HA").
ha-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_ha.py tests/test_ha_integ.py -q -m "not slow"

# Striped-heal round trip alone (ISSUE 15): streamed fragment staging,
# multi-source striping with per-fragment failover (kill a stripe source
# mid-heal, poisoned-fragment rejection), delta rejoins, the delta-heal
# golden fixture, and the fleet-level striped recovery chaos test
# (docs/architecture.md "Striped heal").
heal-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_heal_striped.py tests/test_golden_fixtures.py -q -m "not slow"

# Durable-store round trip alone (ISSUE 17): store unit surface (dedup,
# torn-blob digest verify, cut selection, spiller, durable.py on the
# store), whole-fleet SIGKILL cold restore with bitwise resume, the
# torn-disk failover and degrade-to-fresh chaos legs, and the
# cold-restore golden fixture (docs/architecture.md "Durable fragment
# store").
cold-restore-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_store.py tests/test_cold_restore.py tests/test_golden_fixtures.py -q -m "not slow"

# Fleet link-state plane round trip alone: passive estimator accuracy
# on a shaped topology (closed-loop vs the declared RTT/Gbps), the
# record() hot-path budget, heartbeat digest -> lighthouse matrix ->
# /links.json aggregation, the serving staleness ledger, and the
# dropped-link-report chaos degradation (docs/observability.md
# "Link-state plane").
links-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_linkstats.py -q -m "not slow"

# Fragment provenance plane round trip alone (ISSUE 18): the version
# vector's semantics, the hop-audit ring + crash-durable .prov
# companion dumps, heartbeat digest -> lighthouse per-(host, frag_id)
# matrix -> /fragments.json (incl. the 64-node 16 KB byte budget and
# per-fragment staleness consistency), and torchft-diagnose --fragment
# naming a poisoned hop from dumps alone (docs/observability.md
# "Fragment provenance plane").
fragments-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_provenance.py -q -m "not slow"
