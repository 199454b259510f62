"""Storage tier: compressed delta fleet, tensor-page dedup, and the
paper's Fig. 9 BLOB / decoupled / API comparison.

The headline leg stores a K=16 fine-tune fleet (one shared trunk, each
variant perturbing ~10% of every layer's entries) twice: once with raw
dense deltas and once with ``compress_deltas=True`` +
``dedup_pages=True``. The compressed store must hold the fleet in
<= 1/2 the bytes (``TARGET_REDUCTION``), and a cold resolve of every
variant — fresh ``Catalog`` + ``DecoupledStore`` per repeat, so the
layer-tensor cache starts empty — must reproduce the uncompressed
answers within the per-layer quantization bound the catalog declares.
``cold_resolve_p95_latency_ms`` is the gated tail metric: decompression
must not turn the byte saving into a latency regression.

A dedup leg saves four byte-identical trunks under distinct model ids
into one page store and checks the content-hashed pages collapse them
to ~one copy. The Fig. 9 leg keeps the original storage-format
comparison (all-in-one BLOB vs layer tables vs latency-bound API).

Run directly for machine-readable output::

    PYTHONPATH=src:. python benchmarks/bench_storage.py \
        --json BENCH_storage.json
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path

import numpy as np

from benchmarks.common import emit_value, timeit
from repro.device import enable_compile_cache
from repro.storage import (ApiModelRegistry, BlobStore, Catalog,
                           DecoupledStore)

K_FLEET = 16
N_LAYERS = 6
DIM = 128
TOUCH_FRAC = 0.10          # fraction of each layer a fine-tune perturbs
N_DUP_TRUNKS = 4
REPEATS = 3
TARGET_REDUCTION = 2.0     # x fewer stored bytes, compressed fleet
DEDUP_TARGET = 2.0         # x fewer stored bytes, duplicate trunks


def _trunk_params(layers: int, d: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    return {f"layer_{i:02d}": {
        "w": rng.standard_normal((d, d)).astype(np.float32),
        "b": rng.standard_normal(d).astype(np.float32)}
        for i in range(layers)}


def _finetune(trunk, frac: float, seed: int):
    """Perturb ``frac`` of every layer's weight entries (sparse additive
    update, the regime where the delta encodings win)."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, sub in trunk.items():
        w = sub["w"].copy()
        idx = rng.choice(w.size, size=max(1, int(w.size * frac)),
                         replace=False)
        w.ravel()[idx] += (0.01 * rng.standard_normal(idx.size)
                           .astype(np.float32))
        out[name] = {"w": w, "b": sub["b"]}
    return out


def _save_fleet(root: Path, trunk, fts, **store_kw) -> DecoupledStore:
    ds = DecoupledStore(root / "store", Catalog(root / "cat"), **store_kw)
    ds.save("trunk", {"arch": "mlp"}, trunk)
    for i, ft in enumerate(fts):
        ds.save(f"ft{i:02d}", {"arch": "mlp"}, ft, base_model="trunk")
    return ds


def _cold_reader(root: Path) -> DecoupledStore:
    """Fresh catalog + store over the existing directory: empty layer
    cache, so every load pays the full disk resolve."""
    return DecoupledStore(root / "store", Catalog(root / "cat"))


def _cold_resolve_ms(root: Path, model_ids, repeats: int):
    """Per-model cold-load walls; a fresh store per repeat."""
    samples = []
    for _ in range(repeats):
        ds = _cold_reader(root)
        for mid in model_ids:
            t0 = time.perf_counter()
            ds.load(mid)
            samples.append((time.perf_counter() - t0) * 1e3)
    return samples


def _fleet_leg(td: Path, k: int, layers: int, dim: int,
               repeats: int) -> dict:
    trunk = _trunk_params(layers, dim)
    fts = [_finetune(trunk, TOUCH_FRAC, seed=100 + i) for i in range(k)]
    fleet_ids = [f"ft{i:02d}" for i in range(k)]

    ds_u = _save_fleet(td / "raw", trunk, fts)
    ds_c = _save_fleet(td / "cmp", trunk, fts,
                       compress_deltas=True, dedup_pages=True)

    mb_u = ds_u.disk_footprint() / 1e6
    mb_c = ds_c.disk_footprint() / 1e6
    reduction = mb_u / mb_c
    emit_value("storage.fleet_uncompressed_mb", mb_u,
               f"trunk + {k} dense deltas")
    emit_value("storage.fleet_compressed_mb", mb_c,
               "quant/sparse deltas + paged trunk")
    emit_value("storage.fleet_reduction", reduction,
               f"x fewer stored bytes, target {TARGET_REDUCTION}x")
    assert reduction >= TARGET_REDUCTION, (
        f"compressed fleet {reduction:.2f}x < {TARGET_REDUCTION}x target")

    # parity: cold compressed reads match raw reads within the bound
    # each layer *declares* in the catalog (plus float-compose ulp slack)
    rd_u, rd_c = _cold_reader(td / "raw"), _cold_reader(td / "cmp")
    max_err = max_bound = 0.0
    for mid in fleet_ids:
        bound = max((li.bound for li in
                     rd_c.catalog.get_layers(mid)), default=0.0)
        _, flat_u = rd_u.load(mid)
        _, flat_c = rd_c.load(mid)
        for name, ref in flat_u.items():
            got = flat_c[name]
            slack = 4 * np.finfo(np.float32).eps * float(
                np.max(np.abs(ref)))
            err = float(np.max(np.abs(got.astype(np.float64)
                                      - ref.astype(np.float64))))
            assert err <= bound + slack + 1e-12, (
                f"{mid}:{name} err {err:.3e} > bound {bound:.3e}")
            max_err, max_bound = max(max_err, err), max(max_bound, bound)

    cold_u = _cold_resolve_ms(td / "raw", fleet_ids, repeats)
    cold_c = _cold_resolve_ms(td / "cmp", fleet_ids, repeats)
    p95 = lambda xs: float(np.percentile(xs, 95))
    emit_value("storage.cold_resolve_p95_latency_ms", p95(cold_c),
               f"compressed, {len(cold_c)} cold loads")
    emit_value("storage.uncompressed_cold_resolve_p95_latency_ms",
               p95(cold_u), f"{len(cold_u)} cold loads")

    st = ds_c.stats
    return {
        "k": k, "layers": layers, "dim": dim, "touch_frac": TOUCH_FRAC,
        "uncompressed_mb": mb_u, "compressed_mb": mb_c,
        "reduction_x": reduction, "target_reduction_x": TARGET_REDUCTION,
        "compressed_delta_mb": st.compressed_delta_bytes / 1e6,
        "dedup_pages": st.dedup_pages,
        "dedup_bytes_saved_mb": st.dedup_bytes_saved / 1e6,
        "parity_max_abs_err": max_err,
        "parity_declared_bound": max_bound,
        "cold_resolve": {
            "compressed": {
                "cold_resolve_p95_latency_ms": p95(cold_c),
                "mean_ms": float(np.mean(cold_c))},
            "uncompressed": {
                "cold_resolve_p95_latency_ms": p95(cold_u),
                "mean_ms": float(np.mean(cold_u))},
        },
    }


def _dedup_leg(td: Path, layers: int, dim: int) -> dict:
    """N byte-identical trunks under distinct ids: content-hashed pages
    must collapse them to ~one stored copy."""
    trunk = _trunk_params(layers, dim, seed=7)
    ds = DecoupledStore(td / "dup" / "store", Catalog(td / "dup" / "cat"),
                        dedup_pages=True)
    for i in range(N_DUP_TRUNKS):
        ds.save(f"twin{i}", {"arch": "mlp"}, trunk)
    ds_raw = DecoupledStore(td / "dupraw" / "store",
                            Catalog(td / "dupraw" / "cat"))
    for i in range(N_DUP_TRUNKS):
        ds_raw.save(f"twin{i}", {"arch": "mlp"}, trunk)

    mb_dup = ds.disk_footprint() / 1e6
    mb_raw = ds_raw.disk_footprint() / 1e6
    ratio = mb_raw / mb_dup
    emit_value("storage.dedup_reduction", ratio,
               f"{N_DUP_TRUNKS} identical trunks -> ~1 page set")
    assert ratio >= DEDUP_TARGET, (
        f"dedup {ratio:.2f}x < {DEDUP_TARGET}x for identical trunks")
    # parity + GC: pages survive a delete of one twin, vacuum stays a
    # no-op while references remain
    _, flat = _cold_reader(td / "dup").load("twin0")
    for name, sub in ((n, s) for n, s in trunk.items()):
        np.testing.assert_array_equal(flat[f"{name}/w"], sub["w"])
    ds.delete(f"twin{N_DUP_TRUNKS - 1}")
    removed, _ = ds.vacuum()
    assert removed == 0, "vacuum collected pages still referenced"
    _, flat2 = ds.load("twin0")
    np.testing.assert_array_equal(flat2["layer_00/w"],
                                  trunk["layer_00"]["w"])
    return {
        "models": N_DUP_TRUNKS,
        "dedup_mb": mb_dup, "raw_mb": mb_raw, "reduction_x": ratio,
        "dedup_pages": ds.stats.dedup_pages,
        "dedup_bytes_saved_mb": ds.stats.dedup_bytes_saved / 1e6,
        "vacuum_removed_after_delete": removed,
    }


def _fig9_leg(td: Path, layers: int, dim: int) -> dict:
    """Paper Fig. 9: storage / load / access for BLOB vs decoupled vs
    API-based model storage."""
    cat = Catalog(td / "f9cat")
    blob = BlobStore(td / "f9blob", cat)
    dec = DecoupledStore(td / "f9dec", cat)
    params = _trunk_params(layers, dim, seed=0)

    blob.save("m", {"arch": "mlp"}, params)
    dec.save("m-dec", {"arch": "mlp"}, params)
    ft = {k: dict(v) for k, v in params.items()}
    ft["layer_00"]["w"] = ft["layer_00"]["w"] + 1
    dec.save("m-ft", {"arch": "mlp"}, ft, base_model="m-dec")

    blob_mb = (td / "f9blob" / "m.blob").stat().st_size / 1e6
    dec_mb = dec.stored_bytes("m-dec") / 1e6
    ft_mb = dec.stored_bytes("m-ft") / 1e6
    emit_value("storage.blob_mb", blob_mb, "all-in-one")
    emit_value("storage.finetune_delta_mb", ft_mb,
               "1 layer changed (Fig 9a)")

    t_blob = timeit(lambda: blob.load("m", template=params))
    t_partial = timeit(lambda: dec.load(
        "m-ft", layer_filter=lambda n: n.startswith("layer_00")))

    api = ApiModelRegistry(cat)
    api.register("remote", lambda x: np.asarray(x) * 2, latency_s=0.03)
    rng = np.random.default_rng(0)
    t_api = timeit(lambda: api.invoke("remote", rng.standard_normal(4),
                                      rng), repeats=1, warmup=0)
    return {
        "blob_mb": blob_mb, "decoupled_mb": dec_mb,
        "finetune_delta_mb": ft_mb,
        "load_blob_us": t_blob * 1e6,
        "load_partial_1layer_us": t_partial * 1e6,
        "api_invoke_us": max(t_api, 0.03) * 1e6,
    }


def run(k: int = K_FLEET, layers: int = N_LAYERS, dim: int = DIM,
        repeats: int = REPEATS,
        json_path: str = "BENCH_storage.json") -> dict:
    with tempfile.TemporaryDirectory() as tds:
        td = Path(tds)
        fleet = _fleet_leg(td, k, layers, dim, repeats)
        dedup = _dedup_leg(td, layers, dim)
        fig9 = _fig9_leg(td, layers, dim)
    result = {"fleet": fleet, "dedup": dedup, "fig9": fig9}
    if json_path:
        Path(json_path).write_text(json.dumps(result, indent=2,
                                              sort_keys=True))
        print(f"# wrote {json_path}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fleet", type=int, default=K_FLEET)
    ap.add_argument("--layers", type=int, default=N_LAYERS)
    ap.add_argument("--dim", type=int, default=DIM)
    ap.add_argument("--repeats", type=int, default=REPEATS)
    ap.add_argument("--json", default="BENCH_storage.json",
                    help="output path ('' disables the JSON artifact)")
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI smoke")
    args = ap.parse_args(argv)
    if args.smoke:
        args.fleet, args.dim, args.repeats = 6, 48, 1
    run(k=args.fleet, layers=args.layers, dim=args.dim,
        repeats=args.repeats, json_path=args.json)
    return 0


if __name__ == "__main__":
    enable_compile_cache()
    raise SystemExit(main())
