"""Bring-up smoke test: the served PREDICT path on a TPU chip.

Drives the engine through its public surface, as a user would: a model
zoo and a fitted ``ModelSelector``, a decoupled-store session, a
200,000-row ``reviews`` table, ``CREATE TASK`` resolved with a partial
load, one head-delta fine-tune, a few dozen concurrent ``PREDICT``
requests through a ``MorphingServer``, then one ``GROUP BY`` query. Every
answer is checked against the same session run on the numpy backend in
this process.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip mesh path only

With ``--chips 4`` the session spans a 4-device mesh
(``EngineConfig(device_count=4)``) and its answers are compared with a
single-device jax session and with the numpy reference.

The script fails, and prints no result, unless JAX finds a TPU. Earlier
lines report the device, the placement, set-up and compile seconds apart
from the seconds spent answering, the requests and rows answered, and the
largest difference from the reference. The last line of standard output
is one JSON object: ``{"ok": true, "device": {...}}``.

JAX's persistent compilation cache is on (``repro.device.
enable_compile_cache``): ``JAX_COMPILATION_CACHE_DIR`` where set,
otherwise ``<checkout>/.jax_cache``, so a second run compiles less.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core import (ModelSelector, TaskFeaturizer, build_tasks,  # noqa: E402
                        build_zoo, make_task, transfer_matrix)
from repro.device import enable_compile_cache  # noqa: E402
from repro.engine import (EngineConfig, MorphingServer,  # noqa: E402
                          MorphingSession)
from repro.pipeline.backend import JaxBackend, MeshJaxBackend  # noqa: E402
from repro.pipeline.cost import choose_device  # noqa: E402

# the selector resolves this seed's task to a linear trunk, so the served
# lane runs the fused_embed Pallas kernel (the other modes are plain jnp);
# its score leads the runner-up by 0.56%, far above what float32 rounding
# in the NMF can move
SEED = 4
N_ROWS = 200_000
IN_DIM = 16                  # reviews.emb: float32 x 16, as in the demo
CLIENTS = 8
REQUESTS_PER_CLIENT = 6
# Every answer (a score, or an average of scores) must agree with the
# numpy float32 oracle within this absolute bound, the one the repo's CPU
# parity tests use. Measured on a TPU v5 lite: the staged forward's
# matmuls run at Precision.HIGHEST (pipeline/backend.py,
# kernels/fused_embed.py) and land within 4e-7 of float64, where the
# default precision (one bf16 pass) is off by 1.5e-2. What is left is the
# chip's tanh, up to 4.4e-5 from numpy's per feature; a score averages
# the features over the trunk's width, and over the zoo's 16 trunks the
# largest score difference measured was 8.7e-6.
TOLERANCE = 1e-5
GROUP_SQL = ("SELECT gender, AVG(sentiment(emb)) FROM reviews "
             "WHERE len > 20 GROUP BY gender")


class SmokeFailure(RuntimeError):
    pass


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spends getting executables (compiling, or reading
    them back from the persistent cache) and persistent-cache hits,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def build_inputs(rows: int):
    """Zoo, fitted selector, table, resolution sample and fine-tune head,
    all generated from SEED."""
    zoo = build_zoo(16, seed=SEED)
    history = build_tasks(32, seed=SEED + 1)
    V = transfer_matrix(zoo, history)
    fz = TaskFeaturizer()
    feats = np.stack([fz.features(t.X, t.y) for t in history])
    sel = ModelSelector(k=6, n_anchors=3).fit_offline(V, feats, zoo=zoo)
    rng = np.random.default_rng(SEED)
    table = {"gender": rng.integers(0, 2, rows),
             "len": rng.integers(1, 200, rows),
             "emb": rng.standard_normal((rows, IN_DIM)).astype(np.float32)}
    sample = make_task(np.random.default_rng(SEED + 3), "gauss", n=128,
                       dim=IN_DIM, classes=3)
    return zoo, sel, table, sample


def statements():
    tasks = ("sentiment", "sentiment_ft0")
    return [f"PREDICT emb USING TASK {tasks[(c + i) % 2]} FROM reviews "
            f"WHERE len > {20 + 10 * (i % 4)}"
            for c in range(CLIENTS) for i in range(REQUESTS_PER_CLIENT)]


def build_session(cfg: EngineConfig, root: Path, inputs) -> MorphingSession:
    zoo, sel, table, sample = inputs
    sess = MorphingSession(selector=sel, zoo=zoo, root=root, config=cfg)
    sess.register_table("reviews", table)
    sess.sql("CREATE TASK sentiment (INPUT=Series, OUTPUT IN ('POS','NEG'), "
             "TYPE='Classification');")
    # partial load: the trunk slice is keyed to the sample's width, which
    # matches reviews.emb
    sess.resolve_task("sentiment", sample.X, sample.y, mode="partial")
    base = sess.models["sentiment"]
    w = np.abs(np.random.default_rng(SEED + 2)
               .standard_normal(base.head_dim)).astype(np.float32)
    ft_id = f"{base.model_id}-ft0"
    sess.register_finetune(ft_id, base.model_id, {"head/w": w / w.sum()})
    sess.sql("CREATE TASK sentiment_ft0 (INPUT=Series, "
             "OUTPUT IN ('POS','NEG'), TYPE='Classification');")
    sess.resolve_task("sentiment_ft0", sample.X, sample.y, model_id=ft_id)
    return sess


def serve(sess: MorphingSession, stmts):
    """Answer ``stmts`` from CLIENTS threads through one MorphingServer;
    returns (scores per statement, server stats, the server)."""
    server = MorphingServer(session=sess, max_wait_s=0.005)
    scores = [None] * len(stmts)
    errors = []

    def client(c: int) -> None:
        try:
            for i in range(c, len(stmts), CLIENTS):
                scores[i] = server.predict(stmts[i], timeout=600.0).scores
        except Exception as e:          # surfaced after the join
            errors.append(e)

    with server:
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = server.stats()
    if errors:
        raise errors[0]
    return scores, st, server


def run_path(sess: MorphingSession, stmts):
    """The served path, then the analytics query."""
    scores, st, server = serve(sess, stmts)
    groups = sess.sql(GROUP_SQL)
    return scores, st, server, groups


def check_device_lane(server: MorphingServer, platform: str,
                      devices: int) -> JaxBackend:
    """The trunk lane runs on the accelerator annotation, through a
    compiled (not interpreted) jax backend whose staged weights live on
    ``devices`` devices of ``platform``. Lanes and staged models have no
    public accessor, so this check reads them directly."""
    sess = server.session
    lanes = list(server._lanes.values())
    require(len(lanes) == 1, f"expected one shared trunk lane, got "
            f"{[ln.key for ln in lanes]}")
    lane = lanes[0]
    require(lane.device == "tpu",
            f"served lane runs on {lane.device!r}, not 'tpu'")
    backend = sess.backends[lane.device]
    require(isinstance(backend, JaxBackend),
            f"lane backend is {type(backend).__name__}, not a JaxBackend")
    require(backend.interpret is (platform != "tpu"),
            f"lane backend interpret={backend.interpret} on {platform}")
    require(sess.device_count == devices and (
        devices == 1 or isinstance(backend, MeshJaxBackend)),
        f"pool spans {sess.device_count} device(s), expected {devices}")
    staged = backend._staged[lane.spec.version]
    require(staged.mode == "linear",
            f"served trunk is {staged.mode!r}, not the Pallas kernel path")
    for w in staged.weights:
        devs = w.sharding.device_set
        require(len(devs) == devices and w.sharding.is_fully_replicated,
                f"staged weight on {len(devs)} device(s), expected "
                f"{devices}, replicated")
        require({d.platform for d in devs} == {platform},
                f"staged weight on {sorted(d.platform for d in devs)}")
    require(backend.stage_count > 0 and backend.compile_count > 0,
            f"trunk not run: stage_count={backend.stage_count} "
            f"compile_count={backend.compile_count}")
    return backend


def max_diff(scores, ref_scores, groups, ref_groups) -> float:
    worst = 0.0
    for i, (a, b) in enumerate(zip(scores, ref_scores)):
        require(a.shape == b.shape and np.isfinite(a).all(),
                f"answer {i}: shape {a.shape} vs {b.shape}, or not finite")
        worst = max(worst, float(np.abs(a - b).max(initial=0.0)))
    for col in ref_groups.rows:
        a = np.asarray(groups.rows[col], np.float64)
        b = np.asarray(ref_groups.rows[col], np.float64)
        require(a.shape == b.shape, f"GROUP BY column {col}: shape")
        worst = max(worst, float(np.abs(a - b).max(initial=0.0)))
    return worst


def run(chips: int = 1, rows: int = N_ROWS) -> dict:
    """Run the smoke on whatever platform JAX found; ``main`` refuses
    anything but a TPU before calling this."""
    import jax
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    dev0 = jax.devices()[0]
    platform = dev0.platform
    print(f"device: platform={platform} kind={dev0.device_kind} "
          f"count={len(jax.devices())}")
    print(f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    inputs = build_inputs(rows)
    stmts = statements()
    pinned = EngineConfig(model_store="decoupled", devices=("tpu",),
                          device_count=chips)
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        tmp = Path(tmp)
        sess = build_session(pinned, tmp / "device", inputs)
        setup_s = time.perf_counter() - t0
        setup_compile_s = clock.seconds
        t1 = time.perf_counter()
        scores, st, server, groups = run_path(sess, stmts)
        answer_s = time.perf_counter() - t1
        answer_compile_s = clock.seconds - setup_compile_s
        backend = check_device_lane(server, platform, chips)
        planned = choose_device(sess.models["sentiment"].profile,
                                server.nrows_hint, ("host", "tpu"), sess.hw)
        print(f"placement pinned to 'tpu' by EngineConfig(devices=('tpu',))"
              f"; the cost model alone would place the trunk on "
              f"{planned!r}")
        ln = inputs[2]["len"]
        want_rows = sum(int((ln > int(s.rsplit(">", 1)[1])).sum())
                        for s in stmts)
        require(st.requests == len(stmts) and st.rows == want_rows,
                f"server answered {st.requests} requests / {st.rows} rows, "
                f"expected {len(stmts)} / {want_rows}")
        require(st.embed_rows > 0, "no row went through the trunk")
        # the GROUP BY's trunk (embed) runs on the chip; its head and the
        # relational operators are host work by design
        rep = groups.report
        require(rep.device_of.get("embed") == "tpu"
                and rep.backend_of.get("embed") == backend.name,
                f"GROUP BY placed {rep.device_of} on {rep.backend_of}")
        print(f"lane: device=tpu backend={backend.name} "
              f"interpret={backend.interpret} trunk="
              f"{sess.models['sentiment'].model_id} (linear, fused_embed) "
              f"devices={sess.device_count} stage_count="
              f"{backend.stage_count} compile_count={backend.compile_count}")
        print(f"served: {st.requests} requests, {st.rows} rows "
              f"({st.embed_rows} through the trunk, share hit rate "
              f"{st.share_hit_rate:.4f}); GROUP BY {groups.report.rows_in} "
              f"rows in")
        print(f"set-up seconds: {setup_s:.3f} (compile {setup_compile_s:.3f})"
              f"; answer seconds: {answer_s:.3f} (compile "
              f"{answer_compile_s:.3f}); persistent-cache hits: "
              f"{clock.cache_hits}")

        refs = {"numpy": EngineConfig(model_store="decoupled",
                                      backend="numpy")}
        if chips > 1:
            refs["single-device jax"] = EngineConfig(
                model_store="decoupled", devices=("tpu",))
        for name, cfg in refs.items():
            t2 = time.perf_counter()
            ref = build_session(cfg, tmp / name.replace(" ", "-"), inputs)
            ref_scores, ref_st, _, ref_groups = run_path(ref, stmts)
            require(ref_st.rows == want_rows, f"{name} reference rows")
            d = max_diff(scores, ref_scores, groups, ref_groups)
            print(f"largest |difference| from the {name} reference: {d:.3e}"
                  f" (tolerance {TOLERANCE:.0e}; reference ran in "
                  f"{time.perf_counter() - t2:.3f} s)")
            require(d <= TOLERANCE,
                    f"{name} reference differs by {d:.3e} > {TOLERANCE}")
    return {"ok": True, "device": {"platform": platform,
                                   "kind": dev0.device_kind,
                                   "count": len(jax.devices())}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip mesh path and what it "
                         "is compared with")
    args = ap.parse_args(argv)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: jax found platform {devs[0].platform!r} "
              f"({len(devs)} device(s)), not a TPU", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips}, but jax found "
              f"{len(devs)} TPU device(s)", file=sys.stderr)
        return 1
    try:
        result = run(chips=args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
