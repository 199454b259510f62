"""Mesh-parallel trunk embed lanes: serving throughput vs device count.

One ``MorphingServer`` front-end, same concurrent ``PREDICT`` workload,
two backend pools: ``devices=1`` (the parity-exact single-device jit
path) and ``devices=2`` (the ``MeshJaxBackend`` pool — trunk weights
staged once per mesh, embed batches split over the ``("data",)`` axis
with ``shard_map``). The share cache is disabled so the timed window
measures the trunk forward itself, not cache hits; "warm" means
post-compile (every shape bucket is visited by the warmup pass).

Run directly for machine-readable output::

    PYTHONPATH=src:. python benchmarks/bench_sharding.py \
        --json BENCH_sharding.json

Simulated host devices come from ``--xla_force_host_platform_device_
count`` which must be set *before* jax first initializes — this module
sets it at import time when jax is not yet loaded (standalone runs, the
CI leg); under ``benchmarks/run.py`` after a bench that already touched
jax it degrades to however many devices exist and records that.

The >=1.6x speedup target is asserted only where it is physically
meaningful: ``os.cpu_count() >= 2`` (two simulated devices on one core
time-slice a single ALU) *and* the mesh actually formed with 2 devices.
``speedup_asserted`` in the JSON records whether the gate was armed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

DEVICE_COUNT = 2


def _ensure_host_devices(n: int) -> None:
    """Ask XLA for ``n`` simulated host devices — a no-op when jax is
    already imported (device topology is fixed at first import) or when
    the caller pinned XLA_FLAGS themselves."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


_ensure_host_devices(DEVICE_COUNT)

import jax                                               # noqa: E402
import numpy as np                                       # noqa: E402
from concurrent.futures import ThreadPoolExecutor        # noqa: E402

from benchmarks.common import emit_value                 # noqa: E402
from repro.core import make_task, pretrain_model         # noqa: E402
from repro.core.task import TaskSpec                     # noqa: E402
from repro.device import enable_compile_cache            # noqa: E402
from repro.engine import MorphingServer, MorphingSession  # noqa: E402

N_ROWS = 4000
N_REQUESTS = 32
CONCURRENCY = 8
# wide trunk: the embed stage must carry the cost the mesh is splitting
TRUNK_WIDTH = 160
TARGET_SPEEDUP = 1.6
MIN_REQUESTS_FOR_ASSERT = 16
REPEATS = 3


def _setup(n_rows: int, dim: int = 16):
    rng = np.random.default_rng(3)
    src = make_task(rng, "gauss", n=160, dim=dim, classes=3)
    zoo = [pretrain_model(src, width=TRUNK_WIDTH, seed=1,
                          name="shard-m0")]
    rng = np.random.default_rng(0)
    table = {"len": rng.integers(1, 200, n_rows),
             "emb": rng.standard_normal((n_rows, dim)).astype(np.float32)}
    sample = make_task(rng, "gauss", n=128, dim=dim, classes=3)
    return zoo, table, sample


def _make_server(zoo, table, sample, devices: int) -> MorphingServer:
    sess = MorphingSession(zoo=zoo, model_store="decoupled",
                           backend="jax", device_count=devices,
                           enable_share=False)   # measure the trunk, not
    #                                            # the cache
    sess.register_table("reviews", {k: v.copy() for k, v in table.items()})
    sess.create_task(TaskSpec("sent", "series", ("P", "N")))
    sess.registry._resolution["sent"] = 0   # single-model zoo: no selector
    sess.resolve_task("sent", sample.X, sample.y)
    return MorphingServer(session=sess, max_wait_s=0.002)


def _statements(n_requests: int):
    # varied predicates: each request selects a different row window —
    # and thus a different shape bucket mix — as concurrent clients would
    return [f"PREDICT emb USING TASK sent FROM reviews WHERE len > "
            f"{20 + (i % 16)}" for i in range(n_requests)]


def _rows_served(sess, stmts) -> int:
    lens = {s: int((sess.tables["reviews"]["len"]
                    > int(s.rsplit(">", 1)[1])).sum()) for s in set(stmts)}
    return sum(lens[s] for s in stmts)


def _bench(server: MorphingServer, stmts, concurrency: int):
    """Best-of-REPEATS wall over the statement set; the warmup pass runs
    every statement once so each shape bucket is compiled before the
    timed window, and telemetry is re-based per repeat."""
    def one(stmt):
        return server.predict(stmt, timeout=120.0)

    with ThreadPoolExecutor(concurrency) as pool:
        list(pool.map(one, stmts))               # warm: all buckets
        best, best_stats, p95s, outs = float("inf"), None, [], None
        for _ in range(REPEATS):
            server.reset_telemetry()
            t0 = time.perf_counter()
            got = list(pool.map(one, stmts))
            wall = time.perf_counter() - t0
            rep = server.stats()
            p95s.append(rep.p95_latency_s)
            if wall < best:
                best, best_stats, outs = wall, rep, got
        best_stats.p95_latency_s = float(np.median(p95s))
    return best, outs, best_stats


def run(n_rows: int = N_ROWS, n_requests: int = N_REQUESTS,
        concurrency: int = CONCURRENCY,
        json_path: str = "BENCH_sharding.json") -> dict:
    zoo, table, sample = _setup(n_rows)
    stmts = _statements(n_requests)
    cpus = os.cpu_count() or 1

    per_devices = {}
    outs_by_devices = {}
    for devices in (1, DEVICE_COUNT):
        # the pool refuses more devices than exist: under run.py, after
        # another bench fixed jax's topology, the mesh leg runs on what
        # there is and records it as devices_effective
        server = _make_server(zoo, table, sample,
                              min(devices, jax.device_count()))
        rows_total = _rows_served(server.session, stmts)
        with server:
            wall, outs, st = _bench(server, stmts, concurrency)
        backend = server.session.backends["tpu"]
        eff = server.devices
        lane_rows = [lane.batch_rows for lane in server._lanes.values()]
        per_devices[devices] = {
            "devices_effective": eff,
            "wall_s": wall,
            "rows_per_s_warm": rows_total / wall,
            "p95_latency_ms": st.p95_latency_s * 1e3,
            "mesh_rows_per_s": st.mesh_rows_per_s,
            "lane_batch_rows": max(lane_rows),
            "stage_count": backend.stage_count,
        }
        outs_by_devices[devices] = outs
        emit_value(f"sharding.devices{devices}_rows_per_s",
                   rows_total / wall,
                   f"mesh={eff} lane_rows={max(lane_rows)}")
        emit_value(f"sharding.devices{devices}_p95_latency_ms",
                   st.p95_latency_s * 1e3, "post-warmup window")

    # serving answers are device-count invariant (pool.map keeps order)
    for a, b in zip(outs_by_devices[1], outs_by_devices[DEVICE_COUNT]):
        np.testing.assert_allclose(a.scores, b.scores, atol=1e-5)

    one_d, mesh_d = per_devices[1], per_devices[DEVICE_COUNT]
    speedup = mesh_d["rows_per_s_warm"] / one_d["rows_per_s_warm"]
    mesh_formed = mesh_d["devices_effective"] == DEVICE_COUNT
    asserted = (mesh_formed and cpus >= DEVICE_COUNT
                and n_requests >= MIN_REQUESTS_FOR_ASSERT)
    emit_value("sharding.speedup_mesh_vs_single", speedup,
               f"x warm, asserted={asserted} (cpus={cpus})")

    # trunk weights staged once per pool, not once per device (compile
    # telemetry parity is proven deterministically in
    # tests/test_sharding.py — coalesced serving batch sizes are
    # scheduler-timing dependent, so compile counts are not benchable)
    assert mesh_d["stage_count"] == one_d["stage_count"] == 1

    result = {
        "rows_table": n_rows, "requests": n_requests,
        "concurrency": concurrency, "trunk_width": TRUNK_WIDTH,
        "host_cpu_count": cpus,
        "devices_1": one_d,
        "devices_2": mesh_d,
        "speedup_mesh_vs_single": speedup,
        "target_speedup": TARGET_SPEEDUP,
        "speedup_asserted": asserted,
    }
    if asserted:
        assert speedup >= TARGET_SPEEDUP, (
            f"mesh serving {speedup:.2f}x < {TARGET_SPEEDUP}x target at "
            f"{DEVICE_COUNT} devices, concurrency {concurrency} "
            f"({cpus} cpus)")
    if json_path:
        Path(json_path).write_text(json.dumps(result, indent=2,
                                              sort_keys=True))
        print(f"# wrote {json_path}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=N_ROWS)
    ap.add_argument("--requests", type=int, default=N_REQUESTS)
    ap.add_argument("--concurrency", type=int, default=CONCURRENCY)
    ap.add_argument("--json", default="BENCH_sharding.json",
                    help="output path ('' disables)")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    run(n_rows=args.rows, n_requests=args.requests,
        concurrency=args.concurrency, json_path=args.json)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
