"""Execution backends: make ``Node.device`` annotations real.

The planner (Eq. 10) annotates inference nodes with a device; this module
supplies the *executors* those annotations dispatch to. A backend owns
three responsibilities for embed/predict operators:

- **staging** — weights move to the execution device once per resolved
  task (``stage`` at ``MorphingSession.resolve_task``), never per chunk,
  which is exactly the amortization the cost model's TransCost term
  (Eq. 7) assumes;
- **compiled forward** — :class:`JaxBackend` compiles each resolved
  ``ZooModel`` forward pass (all four modes: linear/radial/relu/proj1d)
  plus the score head into ``jax.jit``-compiled functions. The linear
  mode routes through the fused normalize+project+tanh Pallas kernel
  (``repro.kernels.fused_embed``): compiled on a TPU, interpret mode
  only where JAX is held to the CPU (``repro.device.default_interpret``);
- **shape bucketing** — ragged chunk row counts are padded to the next
  power of two and sliced on return, so a whole query triggers at most
  O(log n) compilations instead of one per distinct chunk length.
  ``compile_count`` exposes the number of distinct compiled shapes (jit
  caches per input shape) and ``on_compile`` is a hook for tests.

``PipelineExecutor`` holds a registry ``{device annotation -> backend}``
and routes each node through it; nodes without a native backend
implementation fall back to their lowered host closure (``node.fn``).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from repro.core.zoo import adapt_input_width
from repro.pipeline import spans
from repro.pipeline.batcher import BatcherStats, WindowBatcher


@dataclass
class InferSpec:
    """Everything a backend needs to run one inference operator natively.

    Attached to ``Node.meta['infer']`` by plan lowering; ``kind`` is
    'embed' (features only, share-cached) or 'predict' (features + score
    head fused). ``stats`` is the shared per-task BatcherStats sink.
    """
    kind: str
    task: str
    col: str
    out: str
    table: str
    version: str
    model: Any                       # ResolvedModel (or shim): .features,
    #                                # .head, .zoo_model
    batch_size: int = 32
    share: Optional[Any] = None      # VectorShareCache
    stats: BatcherStats = field(default_factory=BatcherStats)


class ExecutionBackend:
    """Base backend: share-cache plumbing + node fallback dispatch."""

    name = "base"

    def __init__(self):
        # InferSpec.stats is shared across concurrent chunk runs of the
        # same node: accumulate under a lock (same race class as
        # ExecStats in the executor)
        self._stats_lock = threading.Lock()
        # chaos hook (duck-typed; see training.fault.FaultInjector):
        # fires at the top of run_infer when set, so tests and the
        # overload bench can inject errors/stalls without a flaky device
        self.fault_injector: Optional[Any] = None

    # -- staging ----------------------------------------------------------
    def stage(self, version: str, zoo_model) -> Any:
        """Move a resolved model's weights onto the execution device.
        Idempotent per version; called once at resolve time."""
        return zoo_model

    def unstage(self, version: str) -> bool:
        """Release staged device state for one trunk identity (the
        dispatch tier's scale-in path). Idempotent; returns True when
        something was actually evicted. Host backends keep no staged
        state, so the base implementation is a no-op."""
        return False

    # -- node dispatch ----------------------------------------------------
    def run_node(self, node, inputs: List[Any]) -> Any:
        spec = node.meta.get("infer") if node.meta else None
        if spec is not None and inputs:
            return self.run_infer(spec, inputs[0])
        if node.fn:
            return node.fn(*inputs)
        return inputs[0] if inputs else None

    def run_infer(self, spec: InferSpec, batch: Dict[str, np.ndarray]
                  ) -> Dict[str, np.ndarray]:
        fi = self.fault_injector
        if fi is not None:
            fi.on_infer(spec, len(batch.get(spec.col, ())))
        res = dict(batch)
        X = batch[spec.col]
        if spec.kind == "embed":
            if spec.share is not None and len(X):
                res[spec.out] = spec.share.get_or_embed(
                    spec.table, spec.col, np.asarray(X),
                    lambda A: self._features(spec, A),
                    version=spec.version)
            else:
                res[spec.out] = self._features(spec, X)
        else:  # full predict: features + score head
            res[spec.out] = self._predict(spec, X)
        return res

    def run_head(self, spec: InferSpec, F: np.ndarray) -> np.ndarray:
        """Head-only execution entry point: consume embeddings, produce
        scores in ``spec.batch_size``-row slices (the head stage's own
        Eq. 11 budget). Heads are O(rows * head_dim) host work (plan
        lowering keeps them as host closures too), so the base
        implementation is shared by every backend; stats land in
        ``spec.stats`` so serving telemetry can report head rows next to
        embed rows."""
        F = np.asarray(F, np.float32)
        if len(F) == 0:
            return np.zeros(0, np.float32)
        bs = max(1, spec.batch_size)
        t0 = time.perf_counter()
        outs = [np.asarray(spec.model.head(F[i:i + bs]))
                for i in range(0, len(F), bs)]
        dt = time.perf_counter() - t0
        st = spec.stats
        with self._stats_lock:
            st.batches += len(outs)
            st.rows += len(F)
            st.infer_seconds += dt
        return np.concatenate(outs) if len(outs) > 1 else outs[0]

    # -- to implement ------------------------------------------------------
    def _features(self, spec: InferSpec, X: np.ndarray) -> np.ndarray:
        raise NotImplementedError

    def _predict(self, spec: InferSpec, X: np.ndarray) -> np.ndarray:
        raise NotImplementedError


class NumpyBackend(ExecutionBackend):
    """Host reference path: the resolved model's numpy forward, batched in
    window-sized slices (paper §5.2 window-function batch inference).

    A columnar 2-D numeric input already *is* an aggregated window, so it
    runs as vectorized ``batch_size`` slices; ragged/object rows fall
    back to the row-at-a-time WindowBatcher (which owns the per-row
    tensor conversion the vectorized path skips)."""

    name = "numpy"

    def _batched(self, spec: InferSpec, X: np.ndarray,
                 fn: Callable[[np.ndarray], np.ndarray]) -> np.ndarray:
        if len(X) == 0:
            # empty chunk: keep the true output width so cross-chunk
            # concatenation stays shape-consistent
            return np.asarray(fn(X))
        Xa = np.asarray(X)
        if Xa.dtype != object and Xa.ndim >= 2:
            return self._batched_sliced(spec, Xa, fn)
        wb = WindowBatcher(fn, batch_size=spec.batch_size,
                           convert_workers=1)
        for i in range(len(X)):
            wb.add(i, X[i])
        res = wb.finish()
        st = spec.stats
        with self._stats_lock:
            st.batches += wb.stats.batches
            st.rows += wb.stats.rows
            st.infer_seconds += wb.stats.infer_seconds
            st.convert_seconds += wb.stats.convert_seconds
        return np.stack([np.asarray(res[i]) for i in range(len(X))])

    def _batched_sliced(self, spec: InferSpec, X: np.ndarray,
                        fn: Callable[[np.ndarray], np.ndarray]
                        ) -> np.ndarray:
        bs = max(1, spec.batch_size)
        t0 = time.perf_counter()
        outs = [np.asarray(fn(X[i:i + bs])) for i in range(0, len(X), bs)]
        dt = time.perf_counter() - t0
        st = spec.stats
        with self._stats_lock:
            st.batches += len(outs)
            st.rows += len(X)
            st.infer_seconds += dt
        return np.concatenate(outs) if len(outs) > 1 else outs[0]

    def _features(self, spec: InferSpec, X: np.ndarray) -> np.ndarray:
        return self._batched(spec, X, spec.model.features)

    def _predict(self, spec: InferSpec, X: np.ndarray) -> np.ndarray:
        return spec.model.head(self._batched(spec, X, spec.model.features))


@dataclass
class StagedModel:
    """One resolved model, staged: device-resident weights + jitted fns
    that take the batch and then the weights."""
    version: str
    mode: str
    in_dim: int
    out_dim: int
    features_fn: Callable            # [B, in_dim], *W -> [B, out_dim]
    predict_fn: Callable             # [B, in_dim], *W -> [B]
    weights: Tuple[Any, ...] = ()
    seen_shapes: Set[Tuple[str, int]] = field(default_factory=set)


def _next_pow2(n: int) -> int:
    return 1 << (max(n, 1) - 1).bit_length()


class JaxBackend(ExecutionBackend):
    """jit-compiled device path with shape bucketing + one-time staging.

    ``interpret`` is set by the platform (:func:`repro.device.
    default_interpret`): False on a TPU; True where JAX is held to the
    CPU, and then the reported flavour (``name``, which lands in
    ``QueryReport.backend_of``) says so. Whole chunks run as one device
    call — the bucketing supersedes host-side window batching, so
    ``batch_size`` annotations are telemetry-only on this backend.
    The matmuls of every trunk mode run at ``Precision.HIGHEST``, so
    the staged forward agrees with the float32 numpy oracle on the chip.
    """

    name = "jax"

    def __init__(self, *, min_bucket: int = 32, block_rows: int = 256):
        import jax  # deferred so numpy-only paths never pay the import

        from repro.device import default_interpret

        super().__init__()
        self._jax = jax
        self.interpret = default_interpret()
        if self.interpret:
            self.name = f"{type(self).name}-interpret"
        self.device_kind = jax.devices()[0].device_kind
        self.min_bucket = min_bucket
        self.block_rows = block_rows
        self._staged: Dict[str, StagedModel] = {}
        self._lock = threading.Lock()
        self.stage_count = 0             # actual device stagings performed
        self.on_compile: Optional[Callable[[str, Tuple[str, int]], None]] \
            = None

    # -- staging ----------------------------------------------------------
    def _put_weight(self, arr) -> Any:
        """Move one weight tensor onto the execution device(s). The mesh
        subclass overrides this to stage once per mesh with a replicated
        NamedSharding; the base class targets the default device."""
        jnp = self._jax.numpy
        return self._jax.device_put(jnp.asarray(arr, jnp.float32))

    def _raw_forward(self, zoo_model) -> Tuple[str, int, int,
                                               Callable, Tuple[Any, ...]]:
        """Build the uncompiled forward for one resolved model.

        Returns ``(mode, in_dim, out_dim, raw, weights)`` where
        ``raw(X, *weights)`` maps a [B, in_dim] batch to features and the
        weights are already device-resident (:meth:`_put_weight`).
        Weights are explicit arguments — not closure captures — so the
        mesh subclass can hand them to ``shard_map`` with replicated
        in_specs while the batch splits over the mesh, and so the
        compiled forward lowers from shapes alone.
        """
        jnp = self._jax.numpy
        from repro.kernels.fused_embed import fused_embed

        def dot(a, b):
            return jnp.dot(a, b, precision=self._jax.lax.Precision.HIGHEST)

        mode = zoo_model.mode
        W = self._put_weight(zoo_model.W)
        in_dim = int(zoo_model.W.shape[0])
        if mode == "radial":
            centers = self._put_weight(zoo_model.centers)
            inv_two_sig2 = 1.0 / (2.0 * float(zoo_model.sigma) ** 2)
            out_dim = int(zoo_model.centers.shape[0])

            def raw(X, centers):
                d2 = ((X[:, None, :] - centers[None]) ** 2).sum(-1)
                return jnp.exp(-d2 * inv_two_sig2)
            return mode, in_dim, out_dim, raw, (centers,)
        if mode == "relu":
            out_dim = int(zoo_model.W.shape[1])

            def raw(X, W):
                return jnp.maximum(dot(X, W), 0.0)
            return mode, in_dim, out_dim, raw, (W,)
        if mode == "proj1d":
            out_dim = 2 * int(zoo_model.W.shape[1])

            def raw(X, W):
                Z = dot(X, W)
                return jnp.tanh(jnp.concatenate([Z, Z ** 2 - 1.0], axis=1))
            return mode, in_dim, out_dim, raw, (W,)
        # linear -> fused normalize+project+tanh Pallas kernel
        out_dim = int(zoo_model.W.shape[1])
        interpret = self.interpret
        block_rows = self.block_rows

        def raw(X, W):
            return fused_embed(X, W, block_rows=block_rows,
                               interpret=interpret)
        return mode, in_dim, out_dim, raw, (W,)

    def _compile_forward(self, raw: Callable,
                         n_weights: int) -> Tuple[Callable, Callable]:
        """(features_fn, predict_fn) from the raw forward; both take
        ``(X, *weights)``. Overridden by the mesh subclass to split the
        batch axis across devices."""
        jax, jnp = self._jax, self._jax.numpy
        return (jax.jit(raw),
                jax.jit(lambda X, *w: raw(X, *w)
                        .astype(jnp.float32).mean(axis=1)))

    def stage(self, version: str, zoo_model) -> StagedModel:
        with self._lock:
            if version in self._staged:
                return self._staged[version]
        mode, in_dim, out_dim, raw, weights = self._raw_forward(zoo_model)
        features_fn, predict_fn = self._compile_forward(raw, len(weights))
        staged = StagedModel(
            version=version, mode=mode, in_dim=in_dim, out_dim=out_dim,
            features_fn=features_fn, predict_fn=predict_fn,
            weights=weights)
        with self._lock:
            if version not in self._staged:   # lost race: first stage wins
                self._staged[version] = staged
                self.stage_count += 1
        return self._staged[version]

    def unstage(self, version: str) -> bool:
        """Drop the staged weights + compiled functions for one version.
        A later request for the same version late-stages transparently
        through :meth:`_staged_for` (paying Eq. 7 again, by design —
        this is the dispatch tier's scale-in path)."""
        with self._lock:
            return self._staged.pop(version, None) is not None

    @property
    def compile_count(self) -> int:
        """Distinct compiled (fn, bucket) shapes across staged models —
        jit compiles exactly once per new input shape."""
        with self._lock:
            return sum(len(s.seen_shapes) for s in self._staged.values())

    # -- bucketed execution ------------------------------------------------
    def _staged_for(self, spec: InferSpec) -> StagedModel:
        staged = self._staged.get(spec.version)
        if staged is None:                    # not staged at resolve: late
            staged = self.stage(spec.version, spec.model.zoo_model)
        return staged

    def _bucket_for(self, n: int) -> int:
        """Padded row count for an n-row chunk. The mesh subclass rounds
        up to a multiple of the mesh size so every device gets an equal
        slice of the batch axis (a power-of-two bucket already is one
        for power-of-two meshes, keeping compile telemetry identical)."""
        return max(_next_pow2(n), self.min_bucket)

    def _bucketed(self, staged: StagedModel, fn_key: str, fn: Callable,
                  X: np.ndarray, out_shape: Tuple[int, ...]) -> np.ndarray:
        n = len(X)
        if n == 0:
            return np.zeros(out_shape, np.float32)
        bucket = self._bucket_for(n)
        with spans.span("backend.run_infer", bucket=bucket):
            spans.count("backend.rows", n)
            spans.count("backend.bucket_rows", bucket)
            with spans.span("backend.pad"):
                Xp = adapt_input_width(np.asarray(X, np.float32),
                                       staged.in_dim)
                if bucket == n:               # aligned chunk: no pad copy
                    Xb = np.ascontiguousarray(Xp)
                else:
                    Xb = np.zeros((bucket, staged.in_dim), np.float32)
                    Xb[:n] = Xp
            key = (fn_key, bucket)
            with self._lock:
                new_shape = key not in staged.seen_shapes
                if new_shape:
                    staged.seen_shapes.add(key)
            if new_shape:
                spans.count("backend.new_shapes", 1)
                if self.on_compile is not None:
                    self.on_compile(staged.version, key)
            # a new shape's first call traces and compiles before it runs
            with spans.span("backend.compile" if new_shape
                            else "backend.call"):
                y = fn(Xb, *staged.weights)
            with spans.span("backend.fetch"):
                out = np.asarray(y)
        return out[:n]

    def _features(self, spec: InferSpec, X: np.ndarray) -> np.ndarray:
        staged = self._staged_for(spec)
        t0 = time.perf_counter()
        out = self._bucketed(staged, "features", staged.features_fn, X,
                             (0, staged.out_dim))
        dt = time.perf_counter() - t0
        st = spec.stats
        with self._stats_lock:
            st.batches += 1 if len(X) else 0
            st.rows += len(X)
            st.infer_seconds += dt
        return out

    def _predict(self, spec: InferSpec, X: np.ndarray) -> np.ndarray:
        staged = self._staged_for(spec)
        t0 = time.perf_counter()
        # the staged predict_fn fuses the *mean* score head (what
        # ResolvedModel serves); a model carrying a custom head keeps
        # numpy-backend parity by running features on device + head on host
        if getattr(spec.model, "head_kind", "mean") == "mean":
            out = self._bucketed(staged, "predict", staged.predict_fn, X,
                                 (0,))
        else:
            F = self._bucketed(staged, "features", staged.features_fn, X,
                               (0, staged.out_dim))
            out = np.asarray(spec.model.head(F))
        dt = time.perf_counter() - t0
        st = spec.stats
        with self._stats_lock:
            st.batches += 1 if len(X) else 0
            st.rows += len(X)
            st.infer_seconds += dt
        return out

    # -- calibration hooks -------------------------------------------------
    def measure_link_bandwidth(self, nbytes: int = 8 << 20,
                               repeats: int = 3) -> float:
        """bytes/s of the host->device staging path (device_put)."""
        jax, jnp = self._jax, self._jax.numpy
        buf = np.ones(nbytes // 4, np.float32)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.device_put(buf).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        return buf.nbytes / max(best, 1e-9)


class MeshJaxBackend(JaxBackend):
    """Data-parallel jit path over a :class:`jax.sharding.Mesh`.

    Staging moves each trunk's weights onto the mesh *once*, replicated
    under the serving rule table (``repro.distributed.sharding``:
    ``serving_rules`` maps every weight axis to ``None`` and the batch
    axis to ``"data"``); the compiled forward wraps the raw per-device
    function in ``shard_map``, so an embed chunk's rows split evenly
    across the mesh and each device runs the same kernels (including the
    Pallas fused-embed path) on its local shard. Shape bucketing rounds
    chunk rows up to a mesh-size multiple — for power-of-two meshes the
    existing power-of-two buckets already qualify, so compile telemetry
    matches the single-device backend.
    """

    name = "jax-mesh"

    def __init__(self, mesh=None, *, device_count: Optional[int] = None,
                 min_bucket: int = 32, block_rows: int = 256):
        super().__init__(min_bucket=min_bucket, block_rows=block_rows)
        jax = self._jax
        if mesh is None:
            from repro.launch.mesh import make_serving_mesh
            n = (len(jax.devices()) if device_count is None
                 else int(device_count))
            mesh = make_serving_mesh(n)
        self.mesh = mesh
        self.device_count = int(np.prod(list(mesh.shape.values())))

    # -- mesh staging + compilation ---------------------------------------
    def _put_weight(self, arr) -> Any:
        from repro.distributed.sharding import serving_weight_sharding
        jnp = self._jax.numpy
        a = jnp.asarray(arr, jnp.float32)
        return self._jax.device_put(
            a, serving_weight_sharding(self.mesh, a.ndim))

    def _compile_forward(self, raw, n_weights):
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.distributed.sharding import (serving_batch_sharding,
                                                shard_map)
        jax, jnp = self._jax, self._jax.numpy
        # batch rows split over "data"; weights replicated on every
        # device (they were staged that way); the helper leaves the
        # replication check off because the Pallas fused-embed call
        # defeats it
        sharded = shard_map(
            raw, mesh=self.mesh,
            in_specs=(P("data"),) + (P(),) * n_weights,
            out_specs=P("data"))
        in_shardings = ((serving_batch_sharding(self.mesh),)
                        + (NamedSharding(self.mesh, P()),) * n_weights)
        features_fn = jax.jit(sharded, in_shardings=in_shardings)
        predict_fn = jax.jit(
            lambda X, *w: sharded(X, *w).astype(jnp.float32).mean(axis=1),
            in_shardings=in_shardings)
        return features_fn, predict_fn

    def _bucket_for(self, n: int) -> int:
        b = max(_next_pow2(n), self.min_bucket)
        nd = self.device_count
        return -(-b // nd) * nd

    # -- calibration hooks -------------------------------------------------
    def per_device_probe(self) -> JaxBackend:
        """A fresh single-device backend of the same flavour, so
        ``cost.calibrate`` can report the per-device rate next to the
        mesh-aggregate rate it measures through this backend."""
        return JaxBackend(min_bucket=self.min_bucket,
                          block_rows=self.block_rows)


_HOST_BACKEND: Optional[NumpyBackend] = None


def default_host_backend() -> NumpyBackend:
    """Singleton numpy backend used by lowered ``node.fn`` closures so
    executors constructed without a registry keep working."""
    global _HOST_BACKEND
    if _HOST_BACKEND is None:
        _HOST_BACKEND = NumpyBackend()
    return _HOST_BACKEND


class BackendPool(Dict[str, ExecutionBackend]):
    """Placement-aware ``{device annotation -> backend}`` pool.

    A drop-in replacement for the plain registry dict ``make_backends``
    used to return (same mapping protocol, so planner/session/server
    lookups are untouched) that additionally owns the *mesh dimension*
    of placement: ``device_count`` is how many devices the accelerator
    annotation actually spans, and ``mesh`` is the live
    ``jax.sharding.Mesh`` when it spans more than one. Single-device
    pools (``device_count == 1``) carry no mesh and hold exactly the
    backends the old registry built — the parity-exact fallback path.
    """

    def __init__(self, mapping: Dict[str, ExecutionBackend], *,
                 kind: str = "auto", device_count: int = 1, mesh=None):
        super().__init__(mapping)
        self.kind = kind
        self.device_count = int(device_count)
        self.mesh = mesh

    def backend_for(self, device: str) -> ExecutionBackend:
        return self.get(device) or default_host_backend()

    def distinct(self) -> List[ExecutionBackend]:
        return list({id(b): b for b in self.values()}.values())

    def set_fault_injector(self, injector: Optional[Any]) -> None:
        """Thread a chaos hook (``training.fault.FaultInjector`` or
        ``None`` to clear) through every distinct backend in the pool."""
        for b in self.distinct():
            b.fault_injector = injector


def _mesh_jax_backend(device_count: int) -> Tuple[Optional[JaxBackend],
                                                  int, Any]:
    """(backend, device count, mesh) for an accelerator slot.

    One device is the plain single-device :class:`JaxBackend`; more
    span a :class:`MeshJaxBackend` (simulated host devices count, via
    ``xla_force_host_platform_device_count``). Asking for more devices
    than jax exposes raises (:func:`repro.launch.mesh.make_serving_mesh`).
    """
    n = int(device_count)
    if n == 1:
        return JaxBackend(), 1, None
    b = MeshJaxBackend(device_count=n)
    return b, b.device_count, b.mesh


def make_backends(kind: str = "auto",
                  devices: Tuple[str, ...] = ("host", "tpu"),
                  device_count: int = 1) -> BackendPool:
    """Build the placement-aware backend pool.

    'auto'  -> host: numpy, tpu: jax
    'numpy' -> every device runs the host numpy path
    'jax'   -> every device runs the jitted path (CPU = interpret kernels)

    A jax backend that cannot be built raises: the pool never serves
    the ``"tpu"`` annotation with numpy behind the caller's back.
    ``device_count > 1`` asks for a mesh: the jax-backed annotations are
    served by one :class:`MeshJaxBackend` spanning exactly that many
    devices (more than jax exposes is an error). The numpy path has no
    devices to span, so a pure-numpy pool always reports
    ``device_count == 1``.
    """
    np_b = NumpyBackend()
    if kind == "numpy":
        return BackendPool({d: np_b for d in devices}, kind=kind)
    if kind == "jax":
        jb, n, mesh = _mesh_jax_backend(device_count)
        return BackendPool({d: jb for d in devices}, kind=kind,
                           device_count=n, mesh=mesh)
    if kind != "auto":
        raise ValueError(f"unknown backend kind {kind!r}")
    reg: Dict[str, ExecutionBackend] = {}
    n_eff, mesh = 1, None
    for d in devices:
        if d == "tpu":
            reg[d], n_eff, mesh = _mesh_jax_backend(device_count)
        else:
            reg[d] = np_b
    return BackendPool(reg, kind=kind, device_count=n_eff, mesh=mesh)
