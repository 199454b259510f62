"""Execution-backend layer: jitted forward parity against the numpy
oracle (all four ZooModel modes, ragged + empty chunks), shape-bucketed
compile counts, one-time weight staging, registry dispatch through the
executor, and cost-model calibration from the live backend."""
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import make_task, pretrain_model
from repro.core.task import TaskSpec
from repro.engine import MorphingSession
from repro.pipeline import (Dag, HardwareProfile, InferSpec, JaxBackend,
                            Node, NumpyBackend, OpProfile, PipelineExecutor,
                            calibrate, choose_device)
from repro.pipeline.backend import _next_pow2
from repro.pipeline.batcher import BatcherStats

_FAMILY_FOR_MODE = {"linear": "gauss", "radial": "ring", "relu": "sparse",
                    "proj1d": "stripe"}


def _model_for_mode(mode, dim=8, seed=0):
    rng = np.random.default_rng(seed)
    src = make_task(rng, _FAMILY_FOR_MODE[mode], n=120, dim=dim, classes=3)
    zm = pretrain_model(src, width=12, seed=seed, name=f"zm-{mode}",
                        mode=mode)
    assert zm.mode == mode
    return zm


def _spec_for(zm, version, **kw):
    model = SimpleNamespace(zoo_model=zm, features=zm.features,
                            head=lambda F: np.asarray(F).mean(axis=1))
    defaults = dict(kind="embed", task="t", col="x", out="f", table="tab",
                    version=version, model=model, batch_size=16,
                    share=None, stats=BatcherStats())
    defaults.update(kw)
    return InferSpec(**defaults)


# -- jitted forward parity -------------------------------------------------

@pytest.mark.parametrize("mode", ["linear", "radial", "relu", "proj1d"])
@pytest.mark.parametrize("n", [133, 1, 0])
def test_jax_forward_matches_numpy_oracle(mode, n):
    zm = _model_for_mode(mode)
    jb = JaxBackend()
    spec = _spec_for(zm, f"{mode}@parity")
    rng = np.random.default_rng(1)
    X = rng.standard_normal((n, 8)).astype(np.float32)
    got = jb.run_infer(spec, {"x": X})["f"]
    want = zm.features(X)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("ncols", [4, 8, 12])
def test_jax_forward_pads_or_slices_feature_dim(ncols):
    """ZooModel.features slices wide inputs / zero-pads narrow ones; the
    staged path must replicate that host-side."""
    zm = _model_for_mode("linear")
    jb = JaxBackend()
    spec = _spec_for(zm, f"linear@dim{ncols}")
    X = np.random.default_rng(2).standard_normal((37, ncols)) \
        .astype(np.float32)
    np.testing.assert_allclose(jb.run_infer(spec, {"x": X})["f"],
                               zm.features(X), atol=1e-5)


def test_jax_predict_fuses_score_head():
    zm = _model_for_mode("relu")
    jb = JaxBackend()
    spec = _spec_for(zm, "relu@pred", kind="predict")
    X = np.random.default_rng(3).standard_normal((77, 8)).astype(np.float32)
    got = jb.run_infer(spec, {"x": X})["f"]
    np.testing.assert_allclose(got, zm.features(X).mean(axis=1), atol=1e-5)


# -- shape bucketing -------------------------------------------------------

def test_bucketing_compile_count_is_log_n():
    """Many distinct ragged chunk lengths must share O(log n) compiled
    shapes (pad to next power of two, slice on return)."""
    zm = _model_for_mode("linear")
    jb = JaxBackend(min_bucket=32)
    spec = _spec_for(zm, "linear@buckets")
    compiled = []
    jb.on_compile = lambda version, key: compiled.append(key)
    rng = np.random.default_rng(4)
    sizes = [3, 7, 17, 33, 65, 100, 129, 200, 257, 400, 511, 600]
    for n in sizes:
        X = rng.standard_normal((n, 8)).astype(np.float32)
        out = jb.run_infer(spec, {"x": X})["f"]
        assert out.shape == (n, 12)
    # buckets: 32, 64, 128, 256, 512, 1024 -> <= 6 despite 12 ragged sizes
    assert jb.compile_count <= 6
    assert len(compiled) == jb.compile_count
    assert all(b >= 32 and b == _next_pow2(b) for _, b in compiled)


def test_query_compile_count_and_single_staging():
    """Acceptance: a 6k-row / 256-row-chunk query stays <= 6 compiles and
    stages weights exactly once per resolved task."""
    rng = np.random.default_rng(5)
    src = make_task(rng, "gauss", n=120, dim=8, classes=3)
    zoo = [pretrain_model(src, width=12, seed=1, name="m0")]
    sess = MorphingSession(zoo=zoo, backend="jax", chunk_rows=256,
                           enable_share=False)
    sess.create_task(TaskSpec("sent", "series", ("P", "N")))
    sess.registry._resolution["sent"] = 0
    n = 6000
    sess.register_table("reviews", {
        "gender": rng.integers(0, 2, n),
        "len": rng.integers(1, 200, n),
        "emb": rng.standard_normal((n, 8)).astype(np.float32)})
    sess.resolve_task("sent", np.zeros((4, 8), np.float32),
                      np.zeros(4, np.int64))
    jb = next(iter({id(b): b for b in sess.backends.values()}.values()))
    assert isinstance(jb, JaxBackend)
    assert jb.stage_count == 1            # staged at resolve, before queries
    res = sess.sql("SELECT gender, AVG(sent(emb)) FROM reviews "
                   "WHERE len > 20 GROUP BY gender")
    assert res.report.compile_count <= 6
    # the flavour names interpret mode: these tests hold JAX to the CPU
    assert set(res.report.backend_of.values()) == {jb.name}
    assert jb.name == ("jax-interpret" if jb.interpret else "jax")
    assert jb.stage_count == 1            # still once: no per-chunk staging
    res2 = sess.sql("SELECT gender, AVG(sent(emb)) FROM reviews "
                    "WHERE len > 20 GROUP BY gender")
    assert res2.report.compile_count == 0  # warm: every bucket reused
    assert jb.stage_count == 1


def test_stage_is_idempotent_per_version():
    zm = _model_for_mode("linear")
    jb = JaxBackend()
    s1 = jb.stage("m@1.0", zm)
    s2 = jb.stage("m@1.0", zm)
    assert s1 is s2 and jb.stage_count == 1
    jb.stage("m@2.0", zm)
    assert jb.stage_count == 2


# -- registry dispatch + session parity ------------------------------------

def test_session_backend_parity_end_to_end():
    rng = np.random.default_rng(6)
    src = make_task(rng, "ring", n=120, dim=8, classes=3)
    zoo = [pretrain_model(src, width=12, seed=2, name="m0")]
    n = 500
    table = {"gender": rng.integers(0, 2, n),
             "len": rng.integers(1, 200, n),
             "emb": rng.standard_normal((n, 8)).astype(np.float32)}
    scores = {}
    for backend in ("numpy", "jax"):
        sess = MorphingSession(zoo=zoo, backend=backend, chunk_rows=64)
        sess.create_task(TaskSpec("sent", "series", ("P", "N")))
        sess.registry._resolution["sent"] = 0
        sess.register_table("reviews",
                            {k: v.copy() for k, v in table.items()})
        sess.resolve_task("sent", np.zeros((4, 8), np.float32),
                          np.zeros(4, np.int64))
        res = sess.sql("SELECT gender, AVG(sent(emb)) FROM reviews "
                       "WHERE len > 20 GROUP BY gender")
        scores[backend] = res.rows["mean__score"]
    np.testing.assert_allclose(scores["numpy"], scores["jax"], atol=1e-5)


def test_executor_without_registry_uses_host_fallback():
    """Nodes lowered with an InferSpec still run through node.fn (the
    singleton numpy backend) when no registry is supplied."""
    zm = _model_for_mode("linear")
    spec = _spec_for(zm, "linear@fallback")
    from repro.pipeline.backend import default_host_backend
    node = Node("embed", "embed",
                fn=lambda b: default_host_backend().run_infer(spec, b),
                device="tpu")
    node.meta["infer"] = spec
    d = Dag()
    d.add(Node("src", "scan"))
    d.add(node, deps=("src",))
    X = np.random.default_rng(7).standard_normal((40, 8)).astype(np.float32)
    ex = PipelineExecutor(d)                     # no backends
    out = ex.execute({"src": {"x": X}})["embed"]
    np.testing.assert_allclose(out["f"], zm.features(X), atol=1e-6)
    assert ex.stats.backend_of["embed"] == "fn"


def test_exec_stats_accumulate_under_concurrency():
    """op_seconds/calls_of are read-modify-written from pool threads; the
    lock must not lose increments."""
    d = Dag()
    d.add(Node("src", "scan"))
    node = Node("op", "predict", fn=lambda b: b)
    d.add(node, deps=("src",))
    ex = PipelineExecutor(d)
    n_threads, n_calls = 8, 50

    def hammer():
        for _ in range(n_calls):
            ex._run_node(node, [{}])

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ex.stats.calls_of["op"] == n_threads * n_calls
    assert ex.stats.op_seconds["op"] >= 0.0


# -- calibration -----------------------------------------------------------

def test_calibrate_measures_numpy_backend():
    hwp = calibrate(NumpyBackend(), "host", rows=(64, 512), repeats=1)
    assert hwp.measured and hwp.name == "host"
    assert hwp.flops_per_s > 0 and np.isfinite(hwp.flops_per_s)
    assert hwp.mem_bw > 0
    assert hwp.launch_latency_s >= 0.0


def test_calibrate_measures_jax_backend_and_link():
    jb = JaxBackend()
    hwp = calibrate(jb, "tpu", rows=(64, 256), repeats=1)
    assert hwp.measured
    assert hwp.flops_per_s > 0
    assert np.isfinite(hwp.link_bw) and hwp.link_bw > 0


def test_calibrated_profiles_drive_placement():
    p = OpProfile(flops_per_row=2e6, bytes_per_row=4096, model_bytes=4e6)
    fast_tpu = {"tpu": HardwareProfile("tpu", 1e15, 1e12, link_bw=1e12,
                                       launch_latency_s=1e-7,
                                       measured=True)}
    slow_tpu = {"tpu": HardwareProfile("tpu", 1e3, 1e3, link_bw=1e3,
                                       launch_latency_s=1.0,
                                       measured=True)}
    assert choose_device(p, 65536, hw=fast_tpu) == "tpu"
    assert choose_device(p, 65536, hw=slow_tpu) == "host"


def test_session_calibrate_populates_hw():
    rng = np.random.default_rng(8)
    src = make_task(rng, "gauss", n=120, dim=8, classes=3)
    zoo = [pretrain_model(src, width=12, seed=1, name="m0")]
    sess = MorphingSession(zoo=zoo, backend="numpy")
    hw = sess.calibrate(rows=(64, 256), repeats=1)
    assert set(hw) == set(sess.backends)
    assert all(p.measured for p in hw.values())
    assert sess.hw is hw


def test_jax_predict_respects_custom_head():
    """A non-mean head must not be silently replaced by the fused mean
    head: features run on device, the custom head on host."""
    zm = _model_for_mode("linear")
    jb = JaxBackend()
    spec = _spec_for(zm, "linear@customhead", kind="predict")
    spec.model.head = lambda F: np.asarray(F).max(axis=1)
    spec.model.head_kind = "max"
    X = np.random.default_rng(9).standard_normal((50, 8)).astype(np.float32)
    got = jb.run_infer(spec, {"x": X})["f"]
    np.testing.assert_allclose(got, zm.features(X).max(axis=1), atol=1e-5)


def test_session_calibrate_dedupes_shared_backend(monkeypatch):
    """backend='jax' maps host+tpu to one instance: measure it once."""
    import repro.engine.session as sess_mod
    rng = np.random.default_rng(10)
    src = make_task(rng, "gauss", n=120, dim=8, classes=3)
    zoo = [pretrain_model(src, width=12, seed=1, name="m0")]
    sess = MorphingSession(zoo=zoo, backend="numpy")
    calls = []

    def fake_calibrate(b, dev, **kw):
        calls.append(dev)
        return HardwareProfile(dev, 1e9, 1e8, measured=True)

    monkeypatch.setattr(sess_mod, "calibrate", fake_calibrate)
    hw = sess.calibrate()
    assert len(calls) == 1                 # one shared instance: one pass
    assert set(hw) == set(sess.backends)
    assert {p.name for p in hw.values()} == set(sess.backends)
