"""Quantized collective correctness vs eager (reference:
torchft/quantization_test.py + collectives_test.py), plus the chunked
overlapped pipeline's invariants: bitwise parity with the monolithic
codec, bufpool steady-state, and mid-pipeline chaos."""

import threading
import time

import numpy as np
import pytest

from tests.test_process_group import make_group, run_parallel, store  # noqa: F401
from torchft_tpu.ops import quantization as q
from torchft_tpu.ops.collectives import allreduce_quantized, reduce_scatter_quantized
from torchft_tpu.parallel.process_group import REDUCE_AVG, REDUCE_SUM


class TestQuantization:
    def test_quantize_round_trip(self):
        rng = np.random.default_rng(0)
        a = rng.standard_normal((16, 256)).astype(np.float32)
        scales, payload = q.quantize(a)
        out = q.dequantize(scales, payload, a.shape, a.dtype)
        # int8 row-scale error bound: absmax/127 per element
        bound = (np.abs(a).max(axis=1, keepdims=True) / 127.0) * 0.51
        assert np.all(np.abs(out - a) <= bound + 1e-7)

    def test_pack_unpack(self):
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        scales, payload = q.quantize(a)
        s2, p2 = q.unpack(q.pack(scales, payload), 3, 4)
        np.testing.assert_array_equal(scales, s2)
        np.testing.assert_array_equal(payload, p2)

    def test_zero_rows(self):
        a = np.zeros((4, 8), dtype=np.float32)
        scales, payload = q.quantize(a)
        out = q.dequantize(scales, payload, a.shape, a.dtype)
        np.testing.assert_array_equal(out, a)

    def test_reduce_quantized(self):
        rng = np.random.default_rng(1)
        arrays = [rng.standard_normal((4, 64)).astype(np.float32) for _ in range(3)]
        bufs = [q.pack(*q.quantize(a)) for a in arrays]
        reduced = q.reduce_quantized(bufs, 4, 64)
        scales, payload = q.unpack(reduced, 4, 64)
        out = q.dequantize(scales, payload, (4, 64), np.float32)
        expected = sum(arrays)
        assert np.abs(out - expected).max() < np.abs(expected).max() * 0.05


class TestQuantizedCollectives:
    @pytest.mark.parametrize("op", [REDUCE_SUM, REDUCE_AVG])
    def test_allreduce_quantized_vs_eager(self, store, op):  # noqa: F811
        world = 3
        pgs = make_group(store, world, prefix="qar")
        rng = np.random.default_rng(7)
        data = [
            [rng.standard_normal((33, 65)).astype(np.float32), rng.standard_normal(100).astype(np.float32)]
            for _ in range(world)
        ]
        expected = [sum(d[i] for d in data) for i in range(2)]
        if op == REDUCE_AVG:
            expected = [e / world for e in expected]

        def run(rank, _):
            return allreduce_quantized(data[rank], op, pgs[rank]).wait(timeout=30)

        for result in run_parallel(world, run):
            for got, want in zip(result, expected):
                assert got.shape == want.shape
                rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
                assert rel < 0.05, f"quantization error too large: {rel}"
        for pg in pgs:
            pg.shutdown()

    def test_allreduce_quantized_average_by(self, store):  # noqa: F811
        # Manager passes the live participant count (not pg size).
        world = 2
        pgs = make_group(store, world, prefix="qavg")
        data = [np.full((8, 16), 2.0, dtype=np.float32) for _ in range(world)]

        def run(rank, _):
            return allreduce_quantized(
                [data[rank]], REDUCE_AVG, pgs[rank], average_by=4
            ).wait(timeout=30)

        for result in run_parallel(world, run):
            np.testing.assert_allclose(result[0], np.full((8, 16), 1.0), rtol=0.02)
        for pg in pgs:
            pg.shutdown()

    def test_reduce_scatter_quantized(self, store):  # noqa: F811
        world = 2
        pgs = make_group(store, world, prefix="qrs")
        rng = np.random.default_rng(3)
        data = [rng.standard_normal((8, 32)).astype(np.float32) for _ in range(world)]
        expected = sum(data)

        def run(rank, _):
            return reduce_scatter_quantized(data[rank], REDUCE_SUM, pgs[rank]).wait(
                timeout=30
            )

        results = run_parallel(world, run)
        for rank, got in enumerate(results):
            want = expected[rank * 4 : (rank + 1) * 4]
            rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
            assert rel < 0.05
        for pg in pgs:
            pg.shutdown()

    def test_rejects_int_arrays(self, store):  # noqa: F811
        pgs = make_group(store, 2, prefix="qint")
        with pytest.raises(ValueError, match="floating point"):
            allreduce_quantized([np.ones(4, dtype=np.int32)], REDUCE_SUM, pgs[0])
        for pg in pgs:
            pg.shutdown()

    def test_device_quantize_matches_host_path(self, store):  # noqa: F811
        """The Pallas (device) quantizer must produce bitwise-identical
        collective results to the host codec — they share the wire format
        (reference integration: torchft/collectives.py:297-415)."""
        import jax.numpy as jnp

        world = 2
        pgs_d = make_group(store, world, prefix="qdev")
        pgs_h = make_group(store, world, prefix="qhost")
        rng = np.random.default_rng(11)
        # big enough that the (rows, 2048) padding is amortized and the
        # wire-byte ratio approaches the codec's 4x
        data = [
            [
                rng.standard_normal((256, 300)).astype(np.float32),
                rng.standard_normal(5000).astype(np.float32),
            ]
            for _ in range(world)
        ]

        def run_device(rank, _):
            # jax arrays + explicit flag exercises the Pallas path (in
            # interpreter mode off-TPU)
            arrays = [jnp.asarray(a) for a in data[rank]]
            w = allreduce_quantized(
                arrays, REDUCE_SUM, pgs_d[rank], device_quantize=True
            )
            out = w.wait(timeout=30)
            return out, w.wire_bytes, w.unquantized_wire_bytes

        def run_host(rank, _):
            return allreduce_quantized(
                data[rank], REDUCE_SUM, pgs_h[rank], device_quantize=False
            ).wait(timeout=30)

        dev_results = run_parallel(world, run_device)
        host_results = run_parallel(world, run_host)
        # The two paths share the wire format but intentionally diverge on
        # a rank's OWN slice: the host path feeds it into the reduce as
        # raw f32 (zero codec error on own data), while the device path
        # quantizes the full matrix in one Pallas launch before the
        # device->host copy.  So: every rank agrees bitwise WITHIN a path
        # (each slice is reduced by exactly one owner, then allgathered),
        # and across paths the results agree to quantization error.
        for arrs in zip(*(r[0] for r in dev_results)):
            for other in arrs[1:]:
                np.testing.assert_array_equal(np.asarray(arrs[0]), np.asarray(other))
        for arrs in zip(*host_results):
            for other in arrs[1:]:
                np.testing.assert_array_equal(arrs[0], other)
        true_sums = [sum(d[i] for d in data) for i in range(2)]
        for (dev_out, wire, unq), host_out in zip(dev_results, host_results):
            for d_arr, h_arr, want in zip(dev_out, host_out, true_sums):
                scale = np.abs(want).max() + 1e-9
                rel_d = np.abs(np.asarray(d_arr) - want).max() / scale
                rel_h = np.abs(h_arr - want).max() / scale
                assert rel_d < 0.05 and rel_h < 0.05, (rel_d, rel_h)
                # the raw-own-slice host path must not be LESS accurate
                # than the all-quantized device path (small tolerance:
                # rounding interplay can tip individual elements)
                assert rel_h <= rel_d * 1.05 + 1e-6, (rel_h, rel_d)
            # measured wire-byte reduction: int8 payload + f32 row scales
            # vs f32 — must be close to 4x for these sizes
            assert wire < unq / 3.5, (wire, unq)
        for pg in pgs_d + pgs_h:
            pg.shutdown()

    def test_manager_quantized_allreduce_device_leaves(self):
        """Manager.allreduce(should_quantize=True) accepts jax-array pytrees
        and routes them through the quantized collective unconverted (the
        device leaves stay device-side until the codec's int8 hop)."""
        import jax.numpy as jnp

        from torchft_tpu.coordination import LighthouseServer
        from torchft_tpu.manager import Manager
        from torchft_tpu.parallel.process_group import ProcessGroupTCP

        lighthouse = LighthouseServer(
            min_replicas=2, join_timeout_ms=100, heartbeat_timeout_ms=1000
        )
        managers = []
        try:
            for r in range(2):
                managers.append(
                    Manager(
                        pg=ProcessGroupTCP(timeout=20.0),
                        min_replica_size=2,
                        load_state_dict=lambda sd: None,
                        state_dict=lambda: {"x": np.zeros(1)},
                        lighthouse_addr=lighthouse.address(),
                        replica_id=f"qmgr_{r}",
                        group_rank=0,
                        group_world_size=1,
                        use_async_quorum=True,
                        timeout=20.0,
                        quorum_timeout=20.0,
                        # both replicas join fresh at step 0; without this
                        # one of them would heal and contribute zeros
                        init_sync=False,
                    )
                )
            value = {"g": jnp.full((64, 64), 2.0, dtype=jnp.float32)}

            def run(rank, _):
                m = managers[rank]
                m.start_quorum()
                out = m.allreduce(value, should_quantize=True).wait(timeout=30)
                assert m.should_commit()
                return out

            for result in run_parallel(2, run):
                np.testing.assert_allclose(
                    np.asarray(result["g"]), np.full((64, 64), 2.0), rtol=0.02
                )
        finally:
            for m in managers:
                m.shutdown()
            lighthouse.shutdown()


def test_quantize_subnormal_rows_stay_finite():
    """Rows whose absmax is below 127/f32max would overflow the reciprocal
    scale to inf (NaN payloads); they must encode as exact zeros instead."""
    from torchft_tpu.ops import quantization as q

    a = np.full((3, 64), 1e-38, dtype=np.float32)
    a[1] = 0.0
    a[2] = 1.0  # a normal row for contrast
    scales, payload = q.quantize(a)
    assert np.all(np.isfinite(scales))
    out = q.dequantize(scales, payload, a.shape, np.float32)
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out[0], 0.0)  # sub-quantizable -> zero
    np.testing.assert_array_equal(out[1], 0.0)
    np.testing.assert_allclose(out[2], 1.0, atol=1e-2)


class TestFp8Wire:
    """fp8_e4m3 wire format (the reference's SM90 fp8e4nv analog,
    torchft/quantization.py:30-41): same 1 byte/element wire size as int8,
    host codec only (device kernel path stays int8, mirroring the
    reference's hardware gating)."""

    def test_codec_round_trip(self):
        rng = np.random.default_rng(3)
        a = rng.standard_normal((16, 256)).astype(np.float32)
        scales, payload = q.quantize(a, q.WIRE_FP8)
        assert payload.itemsize == 1
        out = q.dequantize(scales, payload, a.shape, a.dtype)
        # e4m3 relative step is 2^-3 of the exponent bucket; bound per
        # element by absmax/448 * (448/|x| rounding) <= |x| * 2^-3 + lsb
        bound = np.abs(a) * (2.0 ** -3) + (
            np.abs(a).max(axis=1, keepdims=True) / 448.0
        )
        assert np.all(np.abs(out - a) <= bound + 1e-7)

    def test_pack_unpack_fp8(self):
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        scales, payload = q.quantize(a, q.WIRE_FP8)
        s2, p2 = q.unpack(
            q.pack(scales, payload, q.WIRE_FP8), 3, 4, q.WIRE_FP8
        )
        np.testing.assert_array_equal(scales, s2)
        np.testing.assert_array_equal(
            payload.view(np.uint8), p2.view(np.uint8)
        )

    def test_allreduce_fp8_wire(self, store):  # noqa: F811
        world = 2
        pgs = make_group(store, world, prefix="fp8ar")
        rng = np.random.default_rng(11)
        data = [
            [rng.standard_normal((40, 50)).astype(np.float32)]
            for _ in range(world)
        ]
        expected = sum(d[0] for d in data)

        def run(rank, _):
            w = allreduce_quantized(
                data[rank], REDUCE_SUM, pgs[rank], wire_dtype=q.WIRE_FP8
            )
            out = w.wait(timeout=30)
            return out, w.wire_bytes, w.wire_dtype

        results = run_parallel(world, run)
        for (got,), wire_bytes, wd in results:
            assert wd == q.WIRE_FP8
            rel = np.abs(got - expected).max() / np.abs(expected).max()
            assert rel < 0.1, f"fp8 error too large: {rel}"
        # identical wire size to the int8 leg (1 byte payload + f32 scales)
        def run_int8(rank, _):
            w = allreduce_quantized(data[rank], REDUCE_SUM, pgs[rank])
            w.wait(timeout=30)
            return w.wire_bytes

        int8_bytes = run_parallel(world, run_int8)
        assert results[0][1] == int8_bytes[0]
        for pg in pgs:
            pg.shutdown()

    def test_reduce_scatter_fp8(self, store):  # noqa: F811
        world = 2
        pgs = make_group(store, world, prefix="fp8rs")
        rng = np.random.default_rng(12)
        data = [rng.standard_normal((8, 6)).astype(np.float32) for _ in range(world)]
        expected = sum(data)

        def run(rank, _):
            return reduce_scatter_quantized(
                data[rank], REDUCE_SUM, pgs[rank], wire_dtype=q.WIRE_FP8
            ).wait(timeout=30)

        results = run_parallel(world, run)
        for rank, got in enumerate(results):
            want = expected[rank * 4 : (rank + 1) * 4]
            rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
            assert rel < 0.1
        for pg in pgs:
            pg.shutdown()

    def test_device_quantize_rejects_fp8(self, store):  # noqa: F811
        (pg,) = make_group(store, 1, prefix="fp8dev")
        with pytest.raises(ValueError, match="int8 wire only"):
            allreduce_quantized(
                [np.ones(4, np.float32)], REDUCE_SUM, pg,
                device_quantize=True, wire_dtype=q.WIRE_FP8,
            )
        pg.shutdown()

    def test_env_default_wire(self, store, monkeypatch):  # noqa: F811
        monkeypatch.setenv("TORCHFT_QUANT_WIRE", q.WIRE_FP8)
        world = 2
        pgs = make_group(store, world, prefix="fp8env")

        def run(rank, _):
            w = allreduce_quantized(
                [np.full(8, float(rank + 1), np.float32)], REDUCE_SUM, pgs[rank]
            )
            w.wait(timeout=30)
            return w.wire_dtype

        assert set(run_parallel(world, run)) == {q.WIRE_FP8}
        for pg in pgs:
            pg.shutdown()

    def test_unknown_wire_rejected(self, store):  # noqa: F811
        (pg,) = make_group(store, 1, prefix="badwire")
        with pytest.raises(ValueError, match="wire_dtype"):
            allreduce_quantized(
                [np.ones(4, np.float32)], REDUCE_SUM, pg, wire_dtype="int4"
            )
        pg.shutdown()

    def test_wire_mismatch_fails_loudly(self):
        # divergent TORCHFT_QUANT_WIRE across ranks must error at unpack,
        # never silently decode the other grid (the on-wire header check)
        a = np.arange(8, dtype=np.float32).reshape(2, 4)
        buf = q.pack(*q.quantize(a, q.WIRE_FP8), q.WIRE_FP8)
        with pytest.raises(ValueError, match="wire format mismatch"):
            q.unpack(buf, 2, 4, q.WIRE_INT8)
        buf8 = q.pack(*q.quantize(a))
        with pytest.raises(ValueError, match="wire format mismatch"):
            q.unpack(buf8, 2, 4, q.WIRE_FP8)

    def test_reduce_scatter_env_default(self, store, monkeypatch):  # noqa: F811
        monkeypatch.setenv("TORCHFT_QUANT_WIRE", q.WIRE_FP8)
        world = 2
        pgs = make_group(store, world, prefix="fp8rsenv")
        data = [np.full((4, 4), float(r + 1), np.float32) for r in range(world)]

        def run(rank, _):
            return reduce_scatter_quantized(
                data[rank], REDUCE_SUM, pgs[rank]
            ).wait(timeout=30)

        for rank, got in enumerate(run_parallel(world, run)):
            np.testing.assert_allclose(got, 3.0, rtol=0.1)
        for pg in pgs:
            pg.shutdown()

    def test_cross_rank_wire_mismatch_fails_loudly(self, store):  # noqa: F811
        # two ranks with DIVERGENT wire settings (the partial-rollout
        # hazard): the allreduce must error on the header check, never
        # resolve with silently mis-decoded gradients
        world = 2
        pgs = make_group(store, world, prefix="wiremix", timeout=5.0)
        data = [np.ones(64, np.float32) for _ in range(world)]

        def run(rank, _):
            wd = q.WIRE_FP8 if rank == 0 else q.WIRE_INT8
            try:
                out = allreduce_quantized(
                    [data[rank]], REDUCE_SUM, pgs[rank], wire_dtype=wd
                ).wait(timeout=10)
            except Exception as e:  # noqa: BLE001
                return e
            return out

        results = run_parallel(world, run)
        assert all(isinstance(r, Exception) for r in results), results
        assert any("wire format mismatch" in str(r) for r in results), results
        for pg in pgs:
            pg.shutdown()

    def test_contribution_snapshotted_at_call_time(self, store):  # noqa: F811
        """Mutating the input array AFTER submitting the collective must
        not change any rank's contribution: peer slices quantize
        synchronously and the own slice is snapshotted at call time (it
        enters the reduce as raw f32 later, asynchronously)."""
        world = 2
        pgs = make_group(store, world, prefix="qsnap")
        data = [np.full(4096, 1.0 + r, dtype=np.float32) for r in range(world)]
        expected = np.full(4096, 3.0, dtype=np.float32)
        barrier = threading.Barrier(world)

        def run(rank, _):
            w = allreduce_quantized([data[rank]], REDUCE_SUM, pgs[rank])
            data[rank][:] = -999.0  # caller reuses its buffer immediately
            barrier.wait(timeout=10)
            return w.wait(timeout=30)

        for result in run_parallel(world, run):
            rel = np.abs(result[0] - expected).max() / 3.0
            assert rel < 0.05, f"mutated input leaked into the reduction: {rel}"
        for pg in pgs:
            pg.shutdown()

    def test_reduce_scatter_contribution_snapshotted(self, store):  # noqa: F811
        world = 2
        pgs = make_group(store, world, prefix="qsnaprs")
        data = [np.full((8, 512), 1.0 + r, dtype=np.float32) for r in range(world)]

        def run(rank, _):
            w = reduce_scatter_quantized(data[rank], REDUCE_SUM, pgs[rank])
            data[rank][:] = -999.0
            return w.wait(timeout=30)

        for rank, got in enumerate(run_parallel(world, run)):
            rel = np.abs(got - 3.0).max() / 3.0
            assert rel < 0.05, f"mutated input leaked into the reduction: {rel}"
        for pg in pgs:
            pg.shutdown()

    def test_reduce_scatter_wire_accounting(self, store):  # noqa: F811
        world = 2
        pgs = make_group(store, world, prefix="qrsw")
        data = [np.ones((8, 512), dtype=np.float32) for _ in range(world)]

        def run(rank, _):
            w = reduce_scatter_quantized(data[rank], REDUCE_SUM, pgs[rank])
            w.wait(timeout=30)
            return w.wire_bytes, w.unquantized_wire_bytes, w.wire_dtype

        for wire, unq, dt in run_parallel(world, run):
            assert dt == "int8"
            # half the rows cross the wire, quantized ~4x smaller
            assert unq == 4 * 4 * 512  # f32 bytes of the peer's slice
            assert 0 < wire < unq / 3.5, (wire, unq)
        for pg in pgs:
            pg.shutdown()


# ---------------------------------------------------------------------------
# chunked overlapped pipeline (the r6 rebuild)
# ---------------------------------------------------------------------------

# Big enough that the (rows, 2048) flat matrix yields multi-row rank
# slices (slice_rows ~ 49 at world 3), so small TORCHFT_QUANT_CHUNK_ROWS
# values produce real multi-chunk pipelines including a padded-tail chunk
# (total is NOT a multiple of 2048, and rows pad up to a world multiple).
_PIPE_SHAPES = ((100, 501), (50_000,))


def _pipe_data(world: int, seed: int = 5):
    rng = np.random.default_rng(seed)
    return [
        [rng.standard_normal(s).astype(np.float32) for s in _PIPE_SHAPES]
        for _ in range(world)
    ]


def _run_quantized(pgs, data, wire_dtype, op=REDUCE_SUM):
    def run(rank, _):
        w = allreduce_quantized(
            data[rank], op, pgs[rank], wire_dtype=wire_dtype
        )
        out = w.wait(timeout=30)
        return out, dict(w.quant_stats), w.wire_bytes

    return run_parallel(len(pgs), run)


class TestFp8VsInt8Accuracy:
    """The measured fp8_e4m3 justification (ROADMAP item 1 tail /
    ISSUE 8 satellite): on HEAVY-TAILED pseudogradients — rows whose
    absmax is dominated by outliers, the regime DiLoCo pseudograds drift
    into as fragments diverge — int8's uniform grid burns its 8 bits on
    the outlier range and fp8's exponent grid wins decisively.  On
    well-conditioned (near-Gaussian) rows int8 keeps the better RMSE, so
    int8 stays the default wire."""

    @staticmethod
    def _codec_err(a: np.ndarray, wire: str) -> "tuple[float, float]":
        scales, payload = q.quantize(a, wire)
        out = q.dequantize(scales, payload, a.shape, a.dtype)
        e = out - a
        rmse = float(np.sqrt(np.mean(e**2)))
        mean_rel = float(np.mean(np.abs(e) / (np.abs(a) + 1e-12)))
        return rmse, mean_rel

    def test_fp8_wins_on_heavy_tailed_rows(self):
        rng = np.random.default_rng(42)
        # student-t(2): infinite variance — every row carries outliers
        heavy = rng.standard_t(2, (256, 2048)).astype(np.float32)
        i8_rmse, i8_rel = self._codec_err(heavy, q.WIRE_INT8)
        f8_rmse, f8_rel = self._codec_err(heavy, q.WIRE_FP8)
        # measured margins (seed 42): rmse 0.249 vs 0.067, mean rel
        # 0.316 vs 0.023 — assert the conservative halves of those gaps
        assert f8_rmse < i8_rmse / 2, (f8_rmse, i8_rmse)
        assert f8_rel < i8_rel / 4, (f8_rel, i8_rel)

    def test_fp8_wins_on_outlier_spiked_rows(self):
        rng = np.random.default_rng(7)
        # laplace body with 0.1% 50x outliers: the "one huge coordinate
        # per row" shape that wrecks absmax-scaled uniform grids
        a = (
            rng.laplace(0, 1, (256, 2048))
            * (1 + 50 * (rng.random((256, 2048)) < 1e-3))
        ).astype(np.float32)
        i8_rmse, i8_rel = self._codec_err(a, q.WIRE_INT8)
        f8_rmse, f8_rel = self._codec_err(a, q.WIRE_FP8)
        assert f8_rmse < i8_rmse / 2, (f8_rmse, i8_rmse)
        assert f8_rel < i8_rel / 4, (f8_rel, i8_rel)

    def test_int8_stays_default_on_gaussian_rows(self):
        rng = np.random.default_rng(42)
        gauss = rng.standard_normal((256, 2048)).astype(np.float32)
        i8_rmse, _ = self._codec_err(gauss, q.WIRE_INT8)
        f8_rmse, _ = self._codec_err(gauss, q.WIRE_FP8)
        # uniform grid fits the compact range ~3x better in RMSE — the
        # reason int8 remains the default for well-conditioned grads
        assert i8_rmse < f8_rmse / 2, (i8_rmse, f8_rmse)


class TestChunkedPipeline:
    """Bitwise parity of the chunked pipeline vs the monolithic codec
    (K=1), bufpool steady-state, and the overlap accounting surface."""

    @pytest.mark.parametrize("wire_dtype", [q.WIRE_INT8, q.WIRE_FP8])
    def test_chunked_bitwise_parity_world3(
        self, store, monkeypatch, wire_dtype  # noqa: F811
    ):
        """Chunked vs monolithic output must be BIT-identical for both
        wire formats — world 3 exercises uneven global row slicing and a
        zero-padded tail chunk."""
        world = 3
        data = _pipe_data(world)
        pgs = make_group(store, world, prefix=f"pmono{wire_dtype}")
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", str(10**9))
        mono = _run_quantized(pgs, data, wire_dtype)
        for pg in pgs:
            pg.shutdown()
        assert mono[0][1]["n_chunks"] == 1

        pgs = make_group(store, world, prefix=f"pchunk{wire_dtype}")
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", "4")
        chunked = _run_quantized(pgs, data, wire_dtype, op=REDUCE_AVG)
        # AVG vs SUM differ; rerun monolithic AVG for the comparison
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", str(10**9))
        pgs2 = make_group(store, world, prefix=f"pmonoA{wire_dtype}")
        mono_avg = _run_quantized(pgs2, data, wire_dtype, op=REDUCE_AVG)
        for pg in pgs + pgs2:
            pg.shutdown()

        assert chunked[0][1]["n_chunks"] > 2, chunked[0][1]
        for (mono_out, _, _), (chunk_out, _, _) in zip(mono_avg, chunked):
            for m, c in zip(mono_out, chunk_out):
                np.testing.assert_array_equal(m, c)

    @pytest.mark.parametrize("wire_dtype", [q.WIRE_INT8, q.WIRE_FP8])
    def test_chunked_parity_numpy_fallback(
        self, store, monkeypatch, wire_dtype  # noqa: F811
    ):
        """The numpy codec path must satisfy the same chunked-vs-
        monolithic bit identity for BOTH wire formats (its per-row math
        is shared, but the row-range plumbing — incl. the fp8 astype
        widen leg — differs)."""
        monkeypatch.setenv("TORCHFT_NO_NATIVE_QUANT", "1")
        world = 2
        data = _pipe_data(world, seed=9)
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", str(10**9))
        pgs = make_group(store, world, prefix=f"pnpm{wire_dtype}")
        mono = _run_quantized(pgs, data, wire_dtype)
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", "7")
        pgs2 = make_group(store, world, prefix=f"pnpc{wire_dtype}")
        chunked = _run_quantized(pgs2, data, wire_dtype)
        for pg in pgs + pgs2:
            pg.shutdown()
        assert chunked[0][1]["n_chunks"] > 2
        for (mono_out, _, _), (chunk_out, _, _) in zip(mono, chunked):
            for m, c in zip(mono_out, chunk_out):
                np.testing.assert_array_equal(m, c)

    def test_chunked_device_path_parity(
        self, store, monkeypatch  # noqa: F811
    ):
        """Device (Pallas) quantize feeds the same chunk queue: chunked
        device-path output is bit-identical to monolithic device-path
        output (one kernel launch either way; per-chunk device→host
        copies must not change a byte)."""
        import jax.numpy as jnp

        world = 2
        data = _pipe_data(world, seed=11)

        def run_dev(pgs):
            def run(rank, _):
                arrays = [jnp.asarray(a) for a in data[rank]]
                w = allreduce_quantized(
                    arrays, REDUCE_SUM, pgs[rank], device_quantize=True
                )
                return w.wait(timeout=60), dict(w.quant_stats)

            return run_parallel(world, run)

        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", str(10**9))
        pgs = make_group(store, world, prefix="pdevm")
        mono = run_dev(pgs)
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", "8")
        pgs2 = make_group(store, world, prefix="pdevc")
        chunked = run_dev(pgs2)
        for pg in pgs + pgs2:
            pg.shutdown()
        assert chunked[0][1]["n_chunks"] > 1
        for (mono_out, _), (chunk_out, _) in zip(mono, chunked):
            for m, c in zip(mono_out, chunk_out):
                np.testing.assert_array_equal(np.asarray(m), np.asarray(c))

    def test_chunked_reduce_scatter_parity(
        self, store, monkeypatch  # noqa: F811
    ):
        world = 2
        rng = np.random.default_rng(3)
        data = [
            rng.standard_normal((64, 700)).astype(np.float32)
            for _ in range(world)
        ]

        def run_rs(pgs):
            def run(rank, _):
                return reduce_scatter_quantized(
                    data[rank], REDUCE_SUM, pgs[rank]
                ).wait(timeout=30)

            return run_parallel(world, run)

        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", str(10**9))
        pgs = make_group(store, world, prefix="prsm")
        mono = run_rs(pgs)
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", "7")
        pgs2 = make_group(store, world, prefix="prsc")
        chunked = run_rs(pgs2)
        for pg in pgs + pgs2:
            pg.shutdown()
        for m, c in zip(mono, chunked):
            np.testing.assert_array_equal(m, c)

    def test_wire_accounting_independent_of_chunking(
        self, store, monkeypatch  # noqa: F811
    ):
        """Per-chunk headers aside, wire bytes must not balloon with K,
        and the ~4x reduction vs f32 holds at any chunking."""
        world = 2
        data = _pipe_data(world, seed=2)
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", str(10**9))
        pgs = make_group(store, world, prefix="pwm")
        mono = _run_quantized(pgs, data, q.WIRE_INT8)
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", "4")
        pgs2 = make_group(store, world, prefix="pwc")
        chunked = _run_quantized(pgs2, data, q.WIRE_INT8)
        for pg in pgs + pgs2:
            pg.shutdown()
        wire_mono, wire_chunk = mono[0][2], chunked[0][2]
        k = chunked[0][1]["n_chunks"]
        assert k > 2
        # chunking adds exactly (K-1) extra 4-byte pack headers per hop
        # direction pair vs the monolithic buffer
        assert wire_mono < wire_chunk <= wire_mono + 2 * (world - 1) * 4 * k
        total = sum(int(np.prod(s)) for s in _PIPE_SHAPES)
        assert wire_chunk < 4 * total / 3.0  # still ~4x under f32

    def test_overlap_stats_surface(self, store, monkeypatch):  # noqa: F811
        """quant_stats carries the pipeline accounting bench consumes."""
        world = 2
        data = _pipe_data(world, seed=4)
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", "8")
        pgs = make_group(store, world, prefix="postats")
        results = _run_quantized(pgs, data, q.WIRE_INT8)
        for pg in pgs:
            pg.shutdown()
        for _, stats, _ in results:
            assert stats["n_chunks"] >= 1
            assert stats["codec_s"] >= 0.0
            assert stats["wire_s"] >= 0.0
            assert stats["wall_s"] > 0.0
            assert 0.0 <= stats["overlap_efficiency"] <= 1.0

    def test_bufpool_steady_state_no_growth(
        self, store, monkeypatch  # noqa: F811
    ):
        """After one warm collective of a given shape, a repeat takes
        every staging buffer — wire bufs, accumulators, reduced pieces,
        pool-backed receives — from the pool: zero new allocations
        (misses) in steady state.

        Cross-rank give/take ordering can jitter by one buffer under
        full-suite load (a taker racing the previous round's returner),
        so the zero-growth bar is required of ANY repeat out of three,
        not the first: a genuinely non-recycling staging buffer misses
        on EVERY repeat, so detection power is unchanged while one-off
        scheduling jitter stops failing the suite."""
        from torchft_tpu.utils.bufpool import POOL

        world = 2
        data = _pipe_data(world, seed=6)
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", "8")
        pgs = make_group(store, world, prefix="ppool")
        _run_quantized(pgs, data, q.WIRE_INT8)  # warm: populates the pool
        growth: "list[int]" = []
        try:
            for _attempt in range(3):
                misses_before = POOL.misses
                results = _run_quantized(pgs, data, q.WIRE_INT8)
                growth.append(POOL.misses - misses_before)
                assert results[0][1]["n_chunks"] > 2
                if growth[-1] == 0:
                    break
        finally:
            for pg in pgs:
                pg.shutdown()
        assert growth[-1] == 0, (
            f"steady-state pool misses grew on every repeat: {growth} "
            f"(a staging buffer is not recycling)"
        )


class TestChunkedChaos:
    def test_fault_mid_pipeline_drains_and_recovers(
        self, store, monkeypatch  # noqa: F811
    ):
        """An injected pg.allreduce.chunk failure MID-pipeline (step =
        chunk index 1: after chunk 0's alltoall is already on the wire)
        must fail the Work promptly on every rank — abort drains the
        codec workers, nothing deadlocks (tier-1 runs with
        TORCHFT_LOCKCHECK=1 armed) — and the SAME process groups must
        complete a clean collective afterwards (op streams left in
        sync)."""
        from torchft_tpu.utils import faults
        from torchft_tpu.utils.faults import FaultRule, InjectedFault

        world = 2
        data = _pipe_data(world, seed=8)
        monkeypatch.setenv("TORCHFT_QUANT_CHUNK_ROWS", "8")
        pgs = make_group(store, world, prefix="pchaos")
        # pg.allreduce.chunk carries the CHUNK index (the pg.allreduce
        # site keeps its training-step namespace); times=world lets BOTH
        # ranks' drivers (sharing this process's registry) inject at
        # chunk 1 and stop submitting at the same point in the op stream
        faults.FAULTS.configure(
            [FaultRule(site="pg.allreduce.chunk", step=1, times=world)],
            seed=1,
        )

        def run(rank, _):
            w = allreduce_quantized([data[rank][1]], REDUCE_SUM, pgs[rank])
            t0 = time.perf_counter()
            try:
                w.wait(timeout=30)
                return None, 0.0
            except Exception as e:  # noqa: BLE001
                return e, time.perf_counter() - t0

        results = run_parallel(world, run)
        for exc, elapsed in results:
            assert isinstance(exc, InjectedFault), exc
            assert elapsed < 20.0, "mid-pipeline abort did not drain promptly"
        assert faults.FAULTS.injected("pg.allreduce.chunk") == world

        # recovery on the SAME pgs: both ranks aborted at the same chunk,
        # so the sockets' op streams are still in lockstep
        faults.FAULTS.configure([], seed=0)
        expected = [sum(d[1] for d in data)]
        clean = _run_quantized(pgs, [[d[1]] for d in data], q.WIRE_INT8)
        for out, _, _ in clean:
            rel = np.abs(out[0] - expected[0]).max() / (
                np.abs(expected[0]).max() + 1e-9
            )
            assert rel < 0.05, rel
        for pg in pgs:
            pg.shutdown()
