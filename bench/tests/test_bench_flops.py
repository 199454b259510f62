"""The benchmark's operation and byte counts against XLA's own count of
the plain jnp forward, at each configuration's shapes, on the CPU."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import spec as specs  # noqa: E402

ROWS = 4096


def _configs():
    """Every configuration file under bench/configs, in use or not."""
    return [(p.stem, specs.load_json(p))
            for p in sorted((BENCH / "configs").glob("*.json"))]


def _cost(fn, *shapes):
    import jax
    import jax.numpy as jnp
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    return cost[0] if isinstance(cost, list) else cost


@pytest.mark.parametrize("name,cfg", _configs(), ids=lambda v: v
                         if isinstance(v, str) else "")
def test_flops_per_row_match_xla(name, cfg):
    """The trunk's forward, as the reference writes it, costs
    ``flops_per_row`` operations a row and ``width`` transcendentals."""
    import jax.numpy as jnp
    tm = specs.trunk_module(cfg)
    d, k = cfg["in_dim"], cfg["width"]
    if cfg["mode"] == "linear":
        def fwd(x, w):
            import jax
            return jnp.tanh(jnp.dot(x, w,
                                    precision=jax.lax.Precision.HIGHEST))
        cost = _cost(fwd, (ROWS, d), (d, k))
    else:
        def fwd(x, c):
            return jnp.exp(-((x[:, None, :] - c[None]) ** 2).sum(-1) * 0.5)
        cost = _cost(fwd, (ROWS, d), (k, d))
    assert cost["flops"] == pytest.approx(ROWS * tm.flops_per_row(cfg))
    assert cost["transcendentals"] == pytest.approx(ROWS * k)


def test_fused_embed_work_matches_its_shapes():
    """Operations and bytes of one fused_embed call: x [N, D] and w [D, K]
    read once, [N, K] written once, 2 N D K multiply-add operations."""
    cfg = dict(_configs())["zoo-linear"]
    tm = specs.trunk_module(cfg)
    n, d, k = 131072, cfg["in_dim"], cfg["width"]
    flops, nbytes = tm.fused_embed_work(n, d, k)
    assert flops == 2 * n * d * k
    x, w, out = n * d * 4, d * k * 4, n * k * 4
    assert nbytes == x + w + out
    # far below the v5e's ridge (197e12 / 819e9 ~ 240 FLOP/B): the
    # kernel is memory-bound at these widths
    assert flops / nbytes < 10
