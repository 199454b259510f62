"""Placement-aware backend pool + mesh-parallel trunk embed lanes.

Two tiers:

- in-process: the pool's dict-compatibility with the old registry, the
  single-device fallback (``devices=1`` must be byte-identical in
  results *and* telemetry to the pre-pool path), and the refusal of a
  device count larger than jax exposes;
- subprocess (``_run``): real 2-device behavior under
  ``--xla_force_host_platform_device_count=2`` — jax fixes the device
  topology at first import, so simulated devices cannot be created
  after the test process has imported jax.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core.zoo import ZooModel
from repro.pipeline.backend import (BackendPool, JaxBackend, InferSpec,
                                    MeshJaxBackend, NumpyBackend,
                                    make_backends)
from repro.pipeline.batcher import BatcherStats
from repro.pipeline.cost import HardwareProfile, calibrate

REPO = Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 2) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=480)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def _zoo_model(mode: str, rng, in_dim: int = 16, width: int = 24) -> ZooModel:
    kw = {}
    if mode == "radial":
        kw = dict(centers=rng.standard_normal((8, in_dim))
                  .astype(np.float32), sigma=1.3)
    return ZooModel(name=f"zm_{mode}", source_family="gauss",
                    W=rng.standard_normal((in_dim, width))
                    .astype(np.float32), mode=mode, **kw)


def _spec(zm: ZooModel, version: str) -> InferSpec:
    class _RM:
        zoo_model = zm
        features = staticmethod(zm.features)
        head = staticmethod(lambda F: np.asarray(F).mean(axis=1))
        head_kind = "mean"
    return InferSpec(kind="embed", task="t", col="x", out="f",
                     table="tb", version=version, model=_RM(),
                     stats=BatcherStats())


# -- the pool is a drop-in registry ----------------------------------------

def test_pool_is_dict_compatible_registry():
    pool = make_backends("auto")
    assert isinstance(pool, dict) and isinstance(pool, BackendPool)
    assert pool.device_count == 1 and pool.mesh is None
    assert isinstance(pool["host"], NumpyBackend)
    assert isinstance(pool["tpu"], JaxBackend)
    assert not isinstance(pool["tpu"], MeshJaxBackend)
    assert set(pool) == {"host", "tpu"}
    assert isinstance(pool.backend_for("nonexistent"), NumpyBackend)
    assert len(pool.distinct()) == 2


def test_pool_numpy_kind_never_meshes():
    pool = make_backends("numpy", device_count=4)
    assert pool.device_count == 1 and pool.mesh is None
    assert all(isinstance(b, NumpyBackend) for b in pool.values())


def test_pool_unknown_kind_raises():
    with pytest.raises(ValueError, match="unknown backend kind"):
        make_backends("torch")


def test_pool_clamps_to_available_devices():
    """Asking for a wider mesh than jax exposes is an error naming what
    exists — never a quietly narrower pool that looks like the mesh the
    caller asked for."""
    import jax
    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"jax exposes {n} cpu device"):
        make_backends("jax", device_count=n + 1)
    with pytest.raises(ValueError, match="jax exposes"):
        make_backends("auto", device_count=n + 1)


# -- single-device fallback parity (satellite: devices=1 byte-identical) --

@pytest.mark.parametrize("mode", ["linear", "relu", "proj1d", "radial"])
def test_single_device_pool_parity_vs_oracle(mode):
    """devices=1 through the pool == pre-refactor JaxBackend, byte for
    byte, and both match the numpy oracle within atol 1e-5."""
    rng = np.random.default_rng(0)
    zm = _zoo_model(mode, rng)
    X = rng.standard_normal((37, 16)).astype(np.float32)

    pool = make_backends("jax", device_count=1)
    pooled = pool["tpu"]
    legacy = JaxBackend()            # the pre-pool construction
    sp, sl = _spec(zm, f"v_{mode}"), _spec(zm, f"v_{mode}")
    Ep = np.asarray(pooled.run_infer(sp, {"x": X})["f"])
    El = np.asarray(legacy.run_infer(sl, {"x": X})["f"])
    assert Ep.tobytes() == El.tobytes()          # byte-identical
    Eo = np.asarray(zm.features(X))
    np.testing.assert_allclose(Ep, Eo, atol=1e-5)
    # telemetry parity: same staging, bucketing, and stats accounting
    assert pooled.stage_count == legacy.stage_count == 1
    assert pooled.compile_count == legacy.compile_count
    assert (sp.stats.rows, sp.stats.batches) == \
        (sl.stats.rows, sl.stats.batches) == (37, 1)


def test_session_device_count_clamps_and_serves():
    """A numpy session has no devices to span, so any device_count
    serves on one; a jax session asking for more devices than exist
    refuses to start."""
    import jax
    from repro.engine import MorphingServer, MorphingSession
    sess = MorphingSession(backend="numpy", device_count=4,
                           auto_calibrate=False)
    assert sess.device_count == 1
    srv = MorphingServer(session=sess)
    assert srv.devices == 1
    assert srv.stats().devices == 1
    with pytest.raises(ValueError, match="jax exposes"):
        MorphingSession(backend="jax", auto_calibrate=False,
                        device_count=len(jax.devices()) + 1)


def test_server_devices_conflicting_with_session_raises():
    from repro.engine import MorphingServer, MorphingSession
    sess = MorphingSession(backend="numpy", auto_calibrate=False)
    with pytest.raises(ValueError, match="conflicts"):
        MorphingServer(session=sess, devices=2)


def test_hardware_profile_mesh_fields_default_single_device():
    hw = HardwareProfile("host", 1e9, 1e9)
    assert hw.device_count == 1
    assert hw.per_device_flops == 1e9
    mesh_hw = HardwareProfile("tpu", 4e9, 1e9, device_count=4)
    assert mesh_hw.per_device_flops == 1e9
    measured = HardwareProfile("tpu", 4e9, 1e9, device_count=4,
                               device_flops_per_s=1.5e9)
    assert measured.per_device_flops == 1.5e9


def test_calibrate_single_device_profile_unchanged_shape():
    prof = calibrate(NumpyBackend(), "host", rows=(64, 256), repeats=1)
    assert prof.measured and prof.device_count == 1
    assert prof.device_flops_per_s == 0.0
    assert prof.per_device_flops == prof.flops_per_s


# -- 2 simulated devices (subprocess) --------------------------------------

def test_mesh_backend_parity_all_modes_two_devices():
    print(_run("""
        import numpy as np
        from repro.core.zoo import ZooModel
        from repro.pipeline.backend import (JaxBackend, MeshJaxBackend,
                                            InferSpec)
        from repro.pipeline.batcher import BatcherStats

        def spec(zm, version):
            class RM:
                zoo_model = zm
                features = staticmethod(zm.features)
                head = staticmethod(lambda F: np.asarray(F).mean(axis=1))
                head_kind = 'mean'
            return InferSpec(kind='embed', task='t', col='x', out='f',
                             table='tb', version=version, model=RM(),
                             stats=BatcherStats())

        rng = np.random.default_rng(0)
        mesh_b = MeshJaxBackend()
        assert mesh_b.device_count == 2, mesh_b.device_count
        single = JaxBackend()
        for mode in ('linear', 'relu', 'proj1d', 'radial'):
            kw = {}
            if mode == 'radial':
                kw = dict(centers=rng.standard_normal((8, 16))
                          .astype(np.float32), sigma=1.3)
            zm = ZooModel(name=f'm_{mode}', source_family='g',
                          W=rng.standard_normal((16, 24))
                          .astype(np.float32), mode=mode, **kw)
            X = rng.standard_normal((37, 16)).astype(np.float32)
            Em = np.asarray(mesh_b.run_infer(spec(zm, f'v{mode}'),
                                             {'x': X})['f'])
            Es = np.asarray(single.run_infer(spec(zm, f'v{mode}'),
                                             {'x': X})['f'])
            Eo = np.asarray(zm.features(X))
            # not byte-identical: each device runs its own shard's rows,
            # and the shard size changes the matmul's blocking
            # (fused_embed's block shape, XLA's dot tiling), so rounding
            # differs by a few float32 ulps. rtol 1e-6 is about eight
            # ulps (epsilon 1.2e-7); atol 4e-6 is two ulps of proj1d's
            # squared projection (Z**2 reaches ~25 here, ulp 1.9e-6)
            np.testing.assert_allclose(Em, Es, rtol=1e-6, atol=4e-6)
            np.testing.assert_allclose(Em, Eo, atol=1e-5)
        # power-of-two buckets are already mesh multiples: identical
        # compile telemetry on a 2-device mesh
        assert mesh_b.compile_count == single.compile_count
        print('mesh parity ok')
    """))


def test_mesh_pool_server_end_to_end_two_devices():
    print(_run("""
        import numpy as np, tempfile
        from repro.core import make_task, pretrain_model
        from repro.core.task import TaskSpec
        from repro.engine import MorphingServer, MorphingSession
        from repro.pipeline.backend import MeshJaxBackend

        rng = np.random.default_rng(0)
        src = make_task(rng, 'gauss', n=120, dim=16, classes=3)
        zoo = [pretrain_model(src, width=48, seed=1, name='m0',
                              mode='linear')]
        X = rng.standard_normal((400, 16)).astype(np.float32)
        y = (X.sum(1) > 0).astype(np.float32)

        def build(devices):
            sess = MorphingSession(zoo=zoo, root=tempfile.mkdtemp(),
                                   backend='jax', device_count=devices,
                                   model_store='decoupled')
            sess.register_table('t', {'x': X})
            sess.create_task(TaskSpec('s', 'series', ('P', 'N')))
            sess.registry._resolution['s'] = 0
            sess.resolve_task('s', X[:64], y[:64])
            return MorphingServer(session=sess)

        s1 = build(1).start()
        a = s1.predict('PREDICT x USING TASK s FROM t').scores
        b1 = list(s1._lanes.values())[0].batch_rows
        s1.stop()

        s2 = build(2).start()
        r = s2.predict('PREDICT x USING TASK s FROM t')
        st = s2.stats()
        assert st.devices == 2, st.devices
        assert st.mesh_rows_per_s > 0
        assert isinstance(s2.session.backends['tpu'], MeshJaxBackend)
        b2 = list(s2._lanes.values())[0].batch_rows
        s2.stop()
        # mesh lanes budget against aggregate throughput (Eq. 11 x N)
        assert b2 >= b1, (b1, b2)
        # serving scores are device-count invariant
        assert np.abs(np.asarray(r.scores) - np.asarray(a)).max() < 1e-6
        print('server mesh ok', b1, b2)
    """))


def test_calibrate_mesh_reports_both_rates_two_devices():
    print(_run("""
        from repro.pipeline.backend import MeshJaxBackend
        from repro.pipeline.cost import calibrate

        prof = calibrate(MeshJaxBackend(), 'tpu', rows=(64, 512),
                         repeats=1)
        assert prof.measured
        assert prof.device_count == 2, prof.device_count
        # mesh-aggregate and per-device rates both measured
        assert prof.flops_per_s > 0
        assert prof.device_flops_per_s > 0
        assert prof.per_device_flops == prof.device_flops_per_s
        print('calibrate mesh ok')
    """))


def test_mesh_bucket_rounding_three_devices():
    """A non-power-of-two mesh rounds buckets up to mesh multiples so
    the batch axis splits evenly under shard_map."""
    print(_run("""
        import numpy as np
        from repro.core.zoo import ZooModel
        from repro.pipeline.backend import MeshJaxBackend, InferSpec
        from repro.pipeline.batcher import BatcherStats

        b = MeshJaxBackend()
        assert b.device_count == 3
        assert b._bucket_for(5) == 33      # pow2->32, rounded to x3
        assert b._bucket_for(40) == 66     # pow2->64, rounded to x3
        rng = np.random.default_rng(0)
        zm = ZooModel(name='m', source_family='g',
                      W=rng.standard_normal((16, 24)).astype(np.float32),
                      mode='relu')
        X = rng.standard_normal((40, 16)).astype(np.float32)

        class RM:
            zoo_model = zm
            features = staticmethod(zm.features)
            head = staticmethod(lambda F: np.asarray(F).mean(axis=1))
            head_kind = 'mean'
        spec = InferSpec(kind='embed', task='t', col='x', out='f',
                         table='tb', version='v', model=RM(),
                         stats=BatcherStats())
        E = np.asarray(b.run_infer(spec, {'x': X})['f'])
        np.testing.assert_allclose(E, zm.features(X), atol=1e-5)
        print('bucket rounding ok')
    """, devices=3))
