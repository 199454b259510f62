"""Zoo trunks: how they are made, the plain reference, the work counts.

A zoo trunk is the engine's ``ZooModel`` (``src/repro/core/zoo.py``): a
``linear`` trunk is ``tanh(X W)``, a ``radial`` trunk is an RBF to a set
of centres, ``exp(-|x - c|^2 / (2 sigma^2))``. Its head is the stored
mean readout, ``F @ w`` with ``w = 1 / width``.

Everything here is the benchmark's own and imports nothing of the
program, so that a later change to the program cannot move the
yardstick:

- :func:`build` draws the source task and "pretrains" the trunk from the
  seed. It is a copy of ``make_task`` and ``pretrain_model`` for the two
  families the configurations use.
- :func:`reference_scores` is the plain float32 ``jax.numpy`` forward
  and head, run in blocks of rows. ``precision="high"`` (three bfloat16
  passes, emulated so that it reads the same on every platform) and
  ``dtype="bfloat16"`` give the lower-precision controls.
- :func:`flops_per_row` and :func:`fused_embed_work` count the
  operations and bytes from the shapes.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import numpy as np

_FAMILY_MODE = {"gauss": "linear", "ring": "radial"}


@dataclass
class Trunk:
    mode: str                        # linear | radial
    W: np.ndarray                    # [in_dim, width] (linear)
    centers: Optional[np.ndarray]    # [width, in_dim] (radial)
    sigma: float
    head_w: np.ndarray               # [width]

    @property
    def in_dim(self) -> int:
        return int(self.W.shape[0])

    @property
    def width(self) -> int:
        return int(self.head_w.shape[0])


def make_task(rng: np.random.Generator, family: str, *, n: int, dim: int,
              classes: int, noise: float) -> Tuple[np.ndarray, np.ndarray]:
    """Source task rows and labels (copy of ``core.zoo.make_task``)."""
    n_test = max(60, n // 3)
    total = n + n_test
    rot = np.linalg.qr(rng.standard_normal((dim, dim)))[0]
    y = rng.integers(0, classes, size=total)
    if family == "gauss":
        cents = rng.standard_normal((classes, dim)) * 2.0
        X = cents[y] + rng.standard_normal((total, dim)) * noise * 2
    elif family == "ring":
        r = 1.0 + y * 1.2 + rng.standard_normal(total) * noise
        theta = rng.uniform(0, 2 * np.pi, total)
        base = np.stack([r * np.cos(theta), r * np.sin(theta)], axis=1)
        pad = rng.standard_normal((total, dim - 2)) * noise
        X = np.concatenate([base, pad], axis=1)
    else:
        raise ValueError(f"unknown task family {family!r}")
    X = (X @ rot).astype(np.float32)
    return X[:n], y[:n]


def pretrain(X: np.ndarray, y: np.ndarray, *, mode: str, width: int,
             noise: float, seed: int) -> Trunk:
    """Trunk weights from a source task (copy of
    ``core.zoo.pretrain_model``), with the mean head."""
    rng = np.random.default_rng(seed)
    dim = X.shape[1]
    classes = np.unique(y)
    head_w = np.full(width, 1.0 / width, np.float32)
    if mode == "radial":
        per = max(2, width // max(len(classes), 1))
        cs = []
        for c in classes:
            pts = X[y == c]
            cs.append(pts[rng.choice(len(pts), size=min(per, len(pts)),
                                     replace=False)])
        centers = np.concatenate(cs)[:width]
        centers = centers + noise * rng.standard_normal(centers.shape)
        sigma = float(np.median(np.linalg.norm(X - X.mean(0), axis=1))) + 1e-3
        if len(centers) != width:
            raise ValueError(f"{len(centers)} centres drawn, need {width}")
        return Trunk("radial", np.eye(dim, dtype=np.float32),
                     centers.astype(np.float32), sigma, head_w)
    cents = np.stack([X[y == c].mean(axis=0) for c in classes])
    scatter = (cents - cents.mean(0)).T @ (cents - cents.mean(0))
    scatter += 0.05 * np.cov(X.T)
    _, vecs = np.linalg.eigh(scatter)
    top = vecs[:, ::-1][:, :min(width, dim)]
    fill = rng.standard_normal((dim, max(0, width - top.shape[1]))) \
        * (0.15 / np.sqrt(dim))
    W = np.concatenate([top, fill], axis=1)
    W = W + noise * rng.standard_normal(W.shape) / np.sqrt(dim)
    return Trunk(mode, W.astype(np.float32), None, 1.0, head_w)


def build(cfg: dict, seed: int) -> Tuple[Trunk, np.ndarray, np.ndarray]:
    """(trunk, sample rows, sample labels) for a configuration, all from
    ``seed``. The sample is the source task itself, which is what a
    ``resolve_task`` call is handed."""
    rng = np.random.default_rng([seed, 0x7A00])
    X, y = make_task(rng, cfg["task_family"], n=cfg["task_rows"],
                     dim=cfg["in_dim"], classes=cfg["task_classes"],
                     noise=cfg["task_noise"])
    mode = _FAMILY_MODE[cfg["task_family"]]
    if mode != cfg["mode"]:
        raise ValueError(f"family {cfg['task_family']} pretrains {mode}, "
                         f"config says {cfg['mode']}")
    trunk = pretrain(X, y, mode=mode, width=cfg["width"],
                     noise=cfg["pretrain_noise"],
                     seed=int(rng.integers(1 << 31)))
    return trunk, X, y


def store_layers(trunk: Trunk, name: str) -> Tuple[dict, dict]:
    """(arch, layer tables) in the decoupled store's layout: what the
    engine itself writes when it resolves a zoo model."""
    arch = {"name": name, "mode": trunk.mode, "sigma": float(trunk.sigma),
            "source_family": {"linear": "gauss", "radial": "ring"}[trunk.mode],
            "in_dim": trunk.in_dim, "out_dim": trunk.width}
    params = {"trunk/W": trunk.W, "head/w": trunk.head_w}
    if trunk.centers is not None:
        params["trunk/centers"] = trunk.centers
    return arch, params


# -- the plain reference --------------------------------------------------
def _split_bf16(a):
    """``a = hi + lo + rest`` with ``hi`` and ``lo`` bfloat16 values held
    in float32. ``reduce_precision`` rounds where a round trip through a
    bfloat16 array may be folded away by the compiler."""
    import jax
    hi = jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(a - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def forward(mode: str, X, W, centers, sigma: float, head_w, *,
            precision: str = "highest", dtype: str = "float32"):
    """Scores of a zoo trunk and its head, in plain ``jax.numpy``.

    ``precision="highest"`` is float32 at ``Precision.HIGHEST``, as the
    configuration states. ``"high"`` is three bfloat16 passes
    (``hi*hi + hi*lo + lo*hi``), written out so that the CPU computes
    what a TPU's ``Precision.HIGH`` does. ``dtype="bfloat16"`` runs the
    whole forward in bfloat16."""
    import jax
    import jax.numpy as jnp
    hp = jax.lax.Precision.HIGHEST
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    X = X.astype(dt)
    if mode == "radial":
        C = centers.astype(dt)
        d2 = ((X[:, None, :] - C[None]) ** 2).sum(-1)
        F = jnp.exp(-d2 * jnp.asarray(1.0 / (2.0 * sigma ** 2), dt))
    elif precision == "high":
        xh, xl = _split_bf16(X.astype(jnp.float32))
        wh, wl = _split_bf16(W.astype(jnp.float32))
        z = (jnp.dot(xh, wh, precision=hp) + jnp.dot(xh, wl, precision=hp)
             + jnp.dot(xl, wh, precision=hp))
        F = jnp.tanh(z)
    else:
        F = jnp.tanh(jnp.dot(X, W.astype(dt), precision=hp))
    return jnp.dot(F.astype(jnp.float32), head_w.astype(jnp.float32),
                   precision=hp)


def reference_scores(trunk: Trunk, X: np.ndarray, *, block: int = 65536,
                     precision: str = "highest",
                     dtype: str = "float32") -> np.ndarray:
    """Reference scores of ``X`` through the trunk's head, in blocks of
    ``block`` rows (one compiled shape; the last block is padded)."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda x, w, c, h: forward(
        trunk.mode, x, w, c, trunk.sigma, h, precision=precision,
        dtype=dtype))
    W = jnp.asarray(trunk.W)
    C = jnp.asarray(trunk.centers if trunk.centers is not None
                    else np.zeros((1, trunk.in_dim), np.float32))
    H = jnp.asarray(trunk.head_w)
    X = np.asarray(X, np.float32)
    out = np.empty(len(X), np.float32)
    for a in range(0, len(X), block):
        part = X[a:a + block]
        n = len(part)
        if n < block:
            part = np.concatenate(
                [part, np.zeros((block - n, X.shape[1]), np.float32)])
        out[a:a + n] = np.asarray(fn(part, W, C, H))[:n]
    return out


# -- work counts -----------------------------------------------------------
def flops_per_row(cfg: dict) -> float:
    """Operations of the trunk's forward for one row, as XLA counts them
    (``cost_analysis()['flops']``; the transcendentals apart)."""
    d, k = cfg["in_dim"], cfg["width"]
    if cfg["mode"] == "linear":
        return 2.0 * d * k                       # X @ W
    if cfg["mode"] == "radial":
        return 3.0 * d * k + k                   # diff, square, sum; scale
    raise ValueError(f"unknown trunk mode {cfg['mode']!r}")


def fused_embed_work(rows: int, in_dim: int, width: int,
                     itemsize: int = 4) -> Tuple[float, float]:
    """(operations, bytes) the ``fused_embed`` kernel needs for ``rows``
    rows: the projection's multiply-adds, and reading the rows and the
    weights once and writing the features once."""
    flops = 2.0 * rows * in_dim * width
    nbytes = float(itemsize * (rows * in_dim + in_dim * width + rows * width))
    return flops, nbytes
