"""LocalSGD / DiLoCo integration: threads-as-replicas with the real stack.

Mirrors reference torchft/local_sgd_integ_test.py: LocalSGD recovery,
DiLoCo recovery, and a third replica joining mid-run (upscale).
"""

import time
from concurrent.futures import ThreadPoolExecutor
from typing import List

import numpy as np
import optax
import pytest

from torchft_tpu.coordination import LighthouseClient, LighthouseServer
from torchft_tpu.local_sgd import DiLoCo, LocalSGD
from torchft_tpu.manager import Manager
from torchft_tpu.parallel.process_group import ProcessGroupTCP

from torchft_tpu.utils import faults
from torchft_tpu.utils.faults import FaultRule, InjectedFault


def fail_at(replica: int, step: int) -> FaultRule:
    """Replica-crash rule for the DiLoCo runners (train.step site)."""
    return FaultRule(site="train.step", replica=f"diloco_{replica}", step=step)


@pytest.fixture(autouse=True)
def clean_faults():
    faults.FAULTS.configure([], seed=0)
    yield
    faults.FAULTS.configure([])


@pytest.fixture
def lighthouse():
    server = LighthouseServer(
        min_replicas=2, join_timeout_ms=100, heartbeat_timeout_ms=1000
    )
    yield server
    server.shutdown()


@pytest.fixture
def patient_lighthouse():
    """Waits two seconds, not a tenth, for a live replica that has not yet
    asked: a replica that joins mid-run asks out of phase with the others,
    and a quorum that goes without the late one each round (a different
    one each time) strands whichever is left out of the LAST round below
    ``min_replicas``, stepping forever."""
    server = LighthouseServer(
        min_replicas=2, join_timeout_ms=2000, heartbeat_timeout_ms=1000
    )
    yield server
    server.shutdown()


class DiLoCoRunner:
    """Replica running DiLoCo: deterministic inner updates so outer syncs
    are exactly comparable across replicas."""

    def __init__(
        self,
        replica_id: int,
        lighthouse_addr: str,
        outer_syncs: int = 4,
        sync_every: int = 4,
        n_fragments: int = 2,
        algo: str = "diloco",
        inner_sleep: float = 0.0,
        quantize: bool = False,
        device_quantize=None,
        param_elems: int = 4,
    ) -> None:
        self.replica_id = replica_id
        self.lighthouse_addr = lighthouse_addr
        self.outer_syncs = outer_syncs
        self.sync_every = sync_every
        self.n_fragments = n_fragments
        self.algo = algo
        self.inner_sleep = inner_sleep
        self.quantize = quantize
        self.device_quantize = device_quantize
        self.param_elems = param_elems

    def run(self) -> dict:
        for attempt in range(3):
            try:
                return self._train()
            except InjectedFault:
                continue
        raise RuntimeError("exhausted attempts")

    def _train(self) -> dict:
        params = {
            "layer0": np.zeros(self.param_elems, dtype=np.float32),
            "layer1": np.zeros(self.param_elems, dtype=np.float32),
        }
        holder = {"p": params}

        def get_params():
            return dict(holder["p"])

        def set_params(p):
            holder["p"] = dict(p)

        manager = Manager(
            pg=ProcessGroupTCP(timeout=10.0),
            min_replica_size=1,
            lighthouse_addr=self.lighthouse_addr,
            replica_id=f"diloco_{self.replica_id}",
            group_rank=0,
            group_world_size=1,
            use_async_quorum=False,
            timeout=20.0,
            quorum_timeout=20.0,
            load_state_dict=lambda sd: holder.__setitem__(
                "p", {k: np.array(v) for k, v in sd.items()}
            ),
            state_dict=lambda: {k: np.array(v) for k, v in holder["p"].items()},
        )
        try:
            if self.algo == "diloco":
                algo = DiLoCo(
                    manager,
                    [["layer0"], ["layer1"]][: self.n_fragments]
                    if self.n_fragments > 1
                    else [["layer0", "layer1"]],
                    get_params,
                    set_params,
                    optax.sgd(0.5, momentum=0.9, nesterov=True),
                    sync_every=self.sync_every,
                    should_quantize=self.quantize,
                    device_quantize=self.device_quantize,
                )
            else:
                algo = LocalSGD(manager, get_params, set_params, self.sync_every)
            target_steps = self.outer_syncs * (
                self.n_fragments if self.algo == "diloco" else 1
            )
            while manager.current_step() < target_steps:
                faults.check(
                    "train.step",
                    replica=f"diloco_{self.replica_id}",
                    step=manager.current_step(),
                )
                if self.inner_sleep:
                    time.sleep(self.inner_sleep)
                # deterministic inner update (same on all replicas)
                p = get_params()
                set_params(
                    {k: v - 0.01 * (1.0 + i) for i, (k, v) in enumerate(sorted(p.items()))}
                )
                algo.step()
            return {
                "params": get_params(),
                "manager_state": manager.state_dict(),
            }
        finally:
            manager.shutdown()


def run_replicas(runners) -> "List[dict]":
    with ThreadPoolExecutor(max_workers=len(runners)) as ex:
        futures = [ex.submit(r.run) for r in runners]
        return [f.result(timeout=180) for f in futures]


def assert_params_equal(results):
    base = results[0]["params"]
    for other in results[1:]:
        for k in base:
            np.testing.assert_array_equal(base[k], other["params"][k])


class TestLocalSGDInteg:
    def test_local_sgd_healthy(self, lighthouse):
        runners = [
            DiLoCoRunner(
                i, lighthouse.address(), algo="local_sgd", outer_syncs=3)
            for i in range(2)
        ]
        results = run_replicas(runners)
        assert all(r["manager_state"]["step"] == 3 for r in results)
        assert_params_equal(results)

    def test_local_sgd_recovery(self, lighthouse):
        faults.FAULTS.configure([fail_at(replica=1, step=1)])
        runners = [
            DiLoCoRunner(
                i, lighthouse.address(), algo="local_sgd", outer_syncs=4)
            for i in range(2)
        ]
        results = run_replicas(runners)
        assert faults.FAULTS.injected() == 1
        assert all(r["manager_state"]["step"] == 4 for r in results)
        assert_params_equal(results)


    @pytest.mark.parametrize("leaves", ["device", "mixed"])
    def test_local_sgd_alone_hands_set_params_device_arrays(self, leaves):
        """At world size 1 the average of device parameters is those
        parameters: ``set_params`` gets them back as ``jax.Array``, and no
        leaf makes the round trip over the host.  A host leaf beside them
        comes back as a host copy."""
        import jax
        import jax.numpy as jnp

        server = LighthouseServer(min_replicas=1, join_timeout_ms=100)
        params = {"w": jnp.arange(8, dtype=jnp.float32) / 3, "b": jnp.ones((2, 3))}
        if leaves == "mixed":
            params["h"] = np.arange(3, dtype=np.float32)
        handed = []
        manager = Manager(
            pg=ProcessGroupTCP(timeout=10.0), min_replica_size=1,
            load_state_dict=lambda sd: None, state_dict=lambda: {},
            replica_id="lsgd_alone", lighthouse_addr=server.address(),
            group_rank=0, group_world_size=1, use_async_quorum=False,
            timeout=10.0,
        )
        try:
            lsgd = LocalSGD(manager, lambda: dict(params), handed.append, sync_every=2)
            for _ in range(4):
                lsgd.step()
            assert len(handed) == 2 and manager.current_step() == 2
            for got in handed:
                assert set(got) == set(params)
                for key in ("w", "b"):
                    assert isinstance(got[key], jax.Array) and got[key] is params[key]
                if leaves == "mixed":
                    assert type(got["h"]) is np.ndarray
                    assert not np.shares_memory(got["h"], params["h"])
                    np.testing.assert_array_equal(got["h"], params["h"])
        finally:
            manager.shutdown()
            server.shutdown()


class TestDiLoCoInteg:
    def test_diloco_healthy_two_fragments(self, lighthouse):
        runners = [
            DiLoCoRunner(
                i, lighthouse.address(), outer_syncs=3)
            for i in range(2)
        ]
        results = run_replicas(runners)
        # step counts fragment syncs: 3 rounds x 2 fragments
        assert all(r["manager_state"]["step"] == 6 for r in results)
        assert_params_equal(results)

    def test_diloco_quantized_allreduce(self, lighthouse):
        # int8-quantized pseudogradient exchange: lossy vs f32, but the
        # dequantized result is identical bytes on every replica, so
        # cross-replica bitwise equality still holds
        runners = [
            DiLoCoRunner(
                i, lighthouse.address(), outer_syncs=3, quantize=True
            )
            for i in range(2)
        ]
        results = run_replicas(runners)
        assert all(r["manager_state"]["step"] == 6 for r in results)
        assert_params_equal(results)

    def test_diloco_device_quantized_pipeline(self, lighthouse, monkeypatch):
        """DiLoCo's quantized leg routed through ``device_quantize=True``
        (ROADMAP item 1 / ISSUE 8 satellite): the pseudogradients stay
        jax arrays, the Pallas int8 kernel (interpret mode on CPU)
        quantizes before the D2H copy, and the per-chunk payload copies
        ride the chunked wire pipeline.  Parity: bitwise-equal across
        replicas (same reduced bytes), and close to the host-codec run
        (paths differ only by own-slice quantization error)."""
        from torchft_tpu.ops import pallas_quant

        launches = []
        real = pallas_quant.fused_quantize_into_int8

        def counted(mat):
            launches.append(mat.shape)
            return real(mat)

        monkeypatch.setattr(
            pallas_quant, "fused_quantize_into_int8", counted
        )
        # fragments big enough that the (rows, 2048) matrix splits into
        # several pipeline chunks at CHUNK_ROWS=2 — the "full chunked
        # pipeline" part of the satellite
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", "2")
        dev = run_replicas(
            [
                DiLoCoRunner(
                    i, lighthouse.address(), outer_syncs=2, quantize=True,
                    device_quantize=True, param_elems=12_000,
                )
                for i in range(2)
            ]
        )
        assert launches, "device path never hit the Pallas quantizer"
        assert_params_equal(dev)
        host = run_replicas(
            [
                DiLoCoRunner(
                    i, lighthouse.address(), outer_syncs=2, quantize=True,
                    device_quantize=False, param_elems=12_000,
                )
                for i in range(2)
            ]
        )
        assert_params_equal(host)
        for k, v in dev[0]["params"].items():
            hv = host[0]["params"][k]
            denom = np.abs(hv).max() + 1e-9
            assert np.abs(np.asarray(v) - hv).max() / denom < 0.05, k

    def test_diloco_recovery(self, lighthouse):
        faults.FAULTS.configure([fail_at(replica=1, step=2)])
        runners = [
            DiLoCoRunner(
                i, lighthouse.address(), outer_syncs=4)
            for i in range(2)
        ]
        results = run_replicas(runners)
        assert faults.FAULTS.injected() == 1
        assert all(r["manager_state"]["step"] == 8 for r in results)
        assert_params_equal(results)

    def test_diloco_upscale_mid_run(self, patient_lighthouse):
        lighthouse = patient_lighthouse
        # Third replica joins after the first two have synced a couple of
        # times.  The join is gated on OBSERVED fleet progress (lighthouse
        # ``max_step``), not a wall-clock delay: a fixed sleep assumes the
        # first two replicas are mid-run when it expires, which a loaded
        # host breaks in both directions (the load-flake CHANGES PR 3
        # recorded).  inner_sleep paces every remaining step at >= 0.2 s,
        # so triggering at max_step >= 2 leaves ~1.6 s of join headroom
        # regardless of how slowly this test got scheduled.
        runners = [
            DiLoCoRunner(
                i, lighthouse.address(), outer_syncs=5, inner_sleep=0.05
            )
            for i in range(3)
        ]
        status = LighthouseClient(lighthouse.address())
        join_seen = {}

        def run_third():
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                doc = status.status(timeout=5.0)
                if doc.get("max_step", 0) >= 2:
                    break
                time.sleep(0.02)
            join_seen["max_step"] = doc.get("max_step", 0)
            return runners[2].run()

        with ThreadPoolExecutor(max_workers=3) as ex:
            futures = [ex.submit(runners[0].run), ex.submit(runners[1].run)]
            futures.append(ex.submit(run_third))
            # one shared deadline: sequential per-future waits would stack
            # to 3x on a wedge and hold CI for ~11 minutes before failing
            deadline = time.monotonic() + 180.0
            ordered = [
                f.result(timeout=max(0.0, deadline - time.monotonic()))
                for f in futures
            ]
        status.close()
        # the join landed mid-run: progress had started but not finished
        assert 2 <= join_seen["max_step"] < 10, join_seen
        assert all(r["manager_state"]["step"] == 10 for r in ordered)
        assert_params_equal(ordered)
