"""Execution backends: where a packed-predict engine actually runs.

PR 8's tentpole refactor (DESIGN.md §12): `ServingEngine` used to *be*
the single-device path — placement was an assumption, not a layer.  This
module makes it pluggable.  An execution backend owns the three
placement-sensitive steps of serving:

  * ``place(model)``   — pin/shard the restored model's leaves,
  * ``pack(model)``    — build the pack-once class-word artifact in the
    layout its own ``predict`` consumes,
  * ``predict(model, class_words, images)`` — the jitted
    encode -> pack -> XOR+popcount -> argmax request path.

Two implementations ship:

:class:`DeviceExecution`
    The existing single-device path, optionally pinned to one device
    (`jax.device_put` commits the leaves; the jitted predict follows).

:class:`ShardedExecution`
    D-partitioned packed predict under ``shard_map``, the inference twin
    of the PR 5 sharded training path and built from the same two
    decision points: ``distributed.sharding.model_axis_for`` partitions
    the trailing-D state, and ``EncoderBase.dynamic_generator`` routes
    generator-backed encoders through ``encode_slice`` so ``uhd_dynamic``
    Gray-codes only its own D-slice.  Every shard encodes, centers, and
    packs its slice locally and computes the partial score
    ``d_local - 2*popcount_local``; **one psum** of the (B, C) int32
    partials is the entire cross-device traffic of a request, because
    ``sum_k (d_k - 2*pc_k) = d - 2*popcount_total`` exactly (integers,
    order-free).  Pad bits of each shard's last word are zero in both
    operands and cancel in the XOR, so labels are bit-identical to the
    single-device engine even when ``d_local % 32 != 0``.  Row-centering
    is exact too: the per-row sum is psum'd and divided by the same
    ``cfg.d`` the single-device mean uses (exact small integers in
    float32 either way).

:func:`plan_executions` turns a fleet request — N replicas over a device
list — into concrete backends: contiguous device groups, sharded when
the group has several devices and D divides, pinned single-device
otherwise.  The replica pool (`repro.serving.pool`) runs one engine per
returned backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import encoding, hdc_model, metrics, registry, unary
from repro.core.hdc_model import HDCModel
from repro.distributed.sharding import ShardingRules, model_axis_for, model_mesh
from repro.obs.profiler import span

_IMPLS = ("jnp", "pallas")
_PLATFORMS = ("cpu", "gpu", "tpu")
PLACEMENTS = ("auto", "device", "sharded")


def resolve_impl(impl: str = "auto", platform: str | None = None) -> str:
    """Packed-similarity implementation for this platform.

    "auto" -> "pallas" on TPU (native kernel), "jnp" elsewhere.
    Explicit names are honoured exactly; `platform` is validated even
    then, so a typo'd platform cannot slip through just because an impl
    was pinned.  Errors list the valid choices.
    """
    if platform is not None and platform not in _PLATFORMS:
        raise ValueError(
            f"unknown platform {platform!r}; valid: {', '.join(_PLATFORMS)}"
        )
    if impl == "auto":
        platform = platform or jax.default_backend()
        return "pallas" if platform == "tpu" else "jnp"
    if impl not in _IMPLS:
        raise ValueError(
            f"unknown packed-similarity impl {impl!r}; "
            f"valid: auto, {', '.join(_IMPLS)}"
        )
    return impl


class DeviceExecution:
    """Single-device placement: the engine's original execution path.

    ``device=None`` leaves placement to JAX (the default device) —
    byte-for-byte the pre-refactor behavior; an explicit device commits
    the model there and the jitted predict follows its operands.
    """

    placement = "device"

    def __init__(self, *, impl: str = "auto", device=None):
        self.impl = resolve_impl(impl)
        self.device = device

    def place(self, model: HDCModel) -> HDCModel:
        if self.device is None:
            return model
        return jax.device_put(model, self.device)

    def pack(self, model: HDCModel) -> jax.Array:
        return model.pack()

    def _inputs(self, images) -> jax.Array:
        # straight onto the pinned device: a detour through the default
        # device would cost every replica but the first an extra copy
        with span("hdc.engine.put"):
            if self.device is None:
                return jnp.asarray(images)
            return jax.device_put(images, self.device)

    def predict(self, model: HDCModel, class_words: jax.Array, images) -> jax.Array:
        return hdc_model.predict_packed(
            model, self._inputs(images), class_words, impl=self.impl
        )

    def search(
        self, model: HDCModel, class_words: jax.Array, images, k: int
    ) -> tuple[jax.Array, jax.Array]:
        """Scored top-k over the packed store (DESIGN.md §14): the k
        nearest rows per query, ascending (distance, index)."""
        return hdc_model.search_packed(
            model, self._inputs(images), class_words, k=k, impl=self.impl
        )

    def describe(self) -> dict:
        return {
            "placement": self.placement,
            "impl": self.impl,
            "device": str(self.device) if self.device is not None else None,
        }


def _centered_local(cfg, hv: jax.Array, axis: str) -> jax.Array:
    """Per-shard twin of `hdc_model._centered`: "row" centering needs the
    row mean over *global* D, so psum the local row sums and divide by
    the same cfg.d the single-device mean divides by — bit-identical
    float32 for the exact small integers involved."""
    if cfg.resolved_pack_center == "row":
        x = hv.astype(jnp.float32)
        total = jax.lax.psum(x.sum(-1, keepdims=True), axis)
        return x - total / cfg.d
    return hv


@functools.lru_cache(maxsize=32)
def _sharded_pack_fn(cfg, mesh: Mesh, rules: ShardingRules):
    """Jitted shard_map pack: each shard sign-packs its (C, d_local)
    slice after globally-exact centering -> (C, n_shards * W_local)
    uint32, D-partitioned.  Per-shard word layout matches what the
    sharded predict packs queries into, so XOR pads cancel."""

    axis = model_axis_for(mesh, cfg.d, rules=rules)
    enc = registry.get_encoder(cfg.encoder)
    like = HDCModel(
        cfg=cfg,
        codebooks=enc.codebook_specs(cfg),
        class_sums=jax.ShapeDtypeStruct((cfg.n_classes, cfg.d), jnp.int32),
        n_seen=jax.ShapeDtypeStruct((2,), hdc_model._NSEEN_DTYPE),
    )
    mspecs = jax.tree_util.tree_map(
        lambda ns: ns.spec, like.shardings(mesh, rules=rules)
    )

    def step(m: HDCModel) -> jax.Array:
        return unary.pack_hypervector(_centered_local(cfg, m.class_hvs, axis))

    fn = jax.shard_map(
        step, mesh=mesh, in_specs=(mspecs,), out_specs=P(None, axis),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=32)
def _sharded_predict_fn(cfg, mesh: Mesh, impl: str, rules: ShardingRules):
    """Jitted shard_map packed predict (see module docstring).

    Every shard: quantize (replicated images) -> encode its D-slice
    (generator encoders re-aim via `encode_slice`; table encoders read
    their pre-sliced codebook) -> center/pack -> partial XOR+popcount
    score -> **one psum** -> argmax, replicated.
    """

    axis = model_axis_for(mesh, cfg.d, rules=rules)
    n_shards = mesh.shape[axis]
    d_local = cfg.d // n_shards
    enc = registry.get_encoder(cfg.encoder)
    like = HDCModel(
        cfg=cfg,
        codebooks=enc.codebook_specs(cfg),
        class_sums=jax.ShapeDtypeStruct((cfg.n_classes, cfg.d), jnp.int32),
        n_seen=jax.ShapeDtypeStruct((2,), hdc_model._NSEEN_DTYPE),
    )
    mspecs = jax.tree_util.tree_map(
        lambda ns: ns.spec, like.shardings(mesh, rules=rules)
    )

    def step(m: HDCModel, images: jax.Array, class_words: jax.Array) -> jax.Array:
        x_q = encoding.quantize_images(images, cfg.levels, cfg.max_intensity)
        point_offset = None
        if enc.dynamic_generator:
            # each shard Gray-codes only the Sobol points of its D-slice
            point_offset = jax.lax.axis_index(axis) * d_local
        q = enc.encode_slice(
            cfg, m.codebooks, x_q,
            backend=cfg.backend, d=d_local, point_offset=point_offset,
        )
        if cfg.binarize_query:
            q = encoding.binarize(q).astype(jnp.int32)
        qw = unary.pack_hypervector(_centered_local(cfg, q, axis))
        sim_local = hdc_model._packed_similarity(qw, class_words, d_local, impl)
        sim = jax.lax.psum(sim_local, axis)
        return metrics.classify(sim.astype(jnp.float32))

    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=(mspecs, P(), P(None, axis)),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=32)
def _sharded_search_fn(cfg, mesh: Mesh, impl: str, k: int, rules: ShardingRules):
    """Jitted shard_map packed top-k search (DESIGN.md §14).

    Identical front half to `_sharded_predict_fn` — every shard encodes,
    centers, and packs its own D-slice — but the reduction carries
    *distances*: each shard derives its partial popcount from the
    partial score as ``(d_local - sim_local) // 2`` (exact: the score is
    d_local - 2*pc by construction, so the difference is even), and
    **one psum** of the (B, C) int32 partials yields the exact global
    Hamming distances, because distances are plain integer sums over D
    slices (order-free; each shard's pad bits are zero in both operands
    and cancel in its local XOR).  The pinned (distance, index) top-k
    then runs on the replicated global matrix, so results are
    bit-identical to the single-device oracle — including ties and
    ``d_local % 32 != 0``.
    """

    axis = model_axis_for(mesh, cfg.d, rules=rules)
    n_shards = mesh.shape[axis]
    d_local = cfg.d // n_shards
    enc = registry.get_encoder(cfg.encoder)
    like = HDCModel(
        cfg=cfg,
        codebooks=enc.codebook_specs(cfg),
        class_sums=jax.ShapeDtypeStruct((cfg.n_classes, cfg.d), jnp.int32),
        n_seen=jax.ShapeDtypeStruct((2,), hdc_model._NSEEN_DTYPE),
    )
    mspecs = jax.tree_util.tree_map(
        lambda ns: ns.spec, like.shardings(mesh, rules=rules)
    )

    def step(m: HDCModel, images: jax.Array, class_words: jax.Array):
        from repro.kernels import ref as kref  # pure jnp; always importable

        x_q = encoding.quantize_images(images, cfg.levels, cfg.max_intensity)
        point_offset = None
        if enc.dynamic_generator:
            point_offset = jax.lax.axis_index(axis) * d_local
        q = enc.encode_slice(
            cfg, m.codebooks, x_q,
            backend=cfg.backend, d=d_local, point_offset=point_offset,
        )
        if cfg.binarize_query:
            q = encoding.binarize(q).astype(jnp.int32)
        qw = unary.pack_hypervector(_centered_local(cfg, q, axis))
        sim_local = hdc_model._packed_similarity(qw, class_words, d_local, impl)
        dist_local = (d_local - sim_local) // 2  # exact partial popcount
        dist = jax.lax.psum(dist_local, axis)
        return kref.topk_pinned(dist, k)

    fn = jax.shard_map(
        step, mesh=mesh,
        in_specs=(mspecs, P(), P(None, axis)),
        out_specs=(P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


class ShardedExecution:
    """D-partitioned packed predict over a ``("model",)`` mesh."""

    placement = "sharded"

    def __init__(self, mesh: Mesh | None = None, *, devices=None,
                 impl: str = "auto", rules: ShardingRules | None = None):
        if mesh is not None and devices is not None:
            raise ValueError("pass mesh or devices, not both")
        self.rules = rules or ShardingRules()
        self.mesh = mesh if mesh is not None else model_mesh(devices, rules=self.rules)
        self.impl = resolve_impl(impl)

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.rules.model_axis])

    def _axis(self, d: int) -> str:
        axis = model_axis_for(self.mesh, d, rules=self.rules)
        if axis is None:
            raise ValueError(
                f"cannot shard D={d} over mesh {dict(self.mesh.shape)}: the "
                f"{self.rules.model_axis!r} axis must be present and divide D"
            )
        return axis

    def place(self, model: HDCModel) -> HDCModel:
        self._axis(model.cfg.d)  # loud, not graceful: sharding was requested
        return model.shard(self.mesh, rules=self.rules)

    def pack(self, model: HDCModel) -> jax.Array:
        self._axis(model.cfg.d)
        return _sharded_pack_fn(model.cfg, self.mesh, self.rules)(model)

    def predict(self, model: HDCModel, class_words: jax.Array, images) -> jax.Array:
        fn = _sharded_predict_fn(model.cfg, self.mesh, self.impl, self.rules)
        with span("hdc.engine.put"):
            images = jnp.asarray(images)
        return fn(model, images, class_words)

    def search(
        self, model: HDCModel, class_words: jax.Array, images, k: int
    ) -> tuple[jax.Array, jax.Array]:
        """One-psum exact sharded top-k (see `_sharded_search_fn`)."""
        fn = _sharded_search_fn(
            model.cfg, self.mesh, self.impl, int(k), self.rules
        )
        with span("hdc.engine.put"):
            images = jnp.asarray(images)
        return fn(model, images, class_words)

    def describe(self) -> dict:
        return {
            "placement": self.placement,
            "impl": self.impl,
            "n_shards": self.n_shards,
            "devices": [str(dev) for dev in self.mesh.devices.flat],
        }


def _device_groups(devices: list, replicas: int) -> list[list]:
    """Contiguous near-even device groups, one per replica.  More
    replicas than devices cycles single devices (CPU oversubscription is
    how the tests and the forced-host-device CI mesh run)."""
    n = len(devices)
    if replicas > n:
        return [[devices[i % n]] for i in range(replicas)]
    base, extra = divmod(n, replicas)
    groups, at = [], 0
    for i in range(replicas):
        size = base + (1 if i < extra else 0)
        groups.append(list(devices[at:at + size]))
        at += size
    return groups


def plan_executions(
    d: int,
    *,
    replicas: int = 1,
    placement: str = "auto",
    impl: str = "auto",
    devices=None,
) -> list:
    """Fleet plan: N execution backends over a device list.

    ``placement``:
      * ``"auto"``    — one replica keeps the classic unpinned
        single-device path; several replicas split the devices into
        contiguous groups, sharding a group when it has more than one
        device and D divides, pinning to its first device otherwise.
      * ``"device"``  — every replica pins one device (round-robin).
      * ``"sharded"`` — every replica shards its whole group; refuses
        loudly when D does not divide the group.
    """
    if placement not in PLACEMENTS:
        raise ValueError(
            f"unknown placement {placement!r}; valid: {', '.join(PLACEMENTS)}"
        )
    replicas = int(replicas)
    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    devs = list(devices) if devices is not None else list(jax.devices())
    if placement == "auto" and replicas == 1:
        return [DeviceExecution(impl=impl)]
    if placement == "device":
        return [
            DeviceExecution(impl=impl, device=devs[i % len(devs)])
            for i in range(replicas)
        ]
    groups = _device_groups(devs, replicas)
    execs = []
    for group in groups:
        if placement == "sharded":
            if d % len(group):
                raise ValueError(
                    f"placement='sharded': D={d} does not divide over a "
                    f"{len(group)}-device group; adjust --replicas or D"
                )
            execs.append(ShardedExecution(devices=group, impl=impl))
        elif len(group) > 1 and d % len(group) == 0:
            execs.append(ShardedExecution(devices=group, impl=impl))
        else:
            execs.append(DeviceExecution(impl=impl, device=group[0]))
    return execs
