"""`HDCModel`: the one state object of the HDC stack.

The seed threaded a loose ``(cfg, dict-of-codebooks, class_hvs)``
triple through every call site.  `HDCModel` bundles the three into a
single pytree-registered dataclass:

  * **jit-stable** — registered with ``jax.tree_util``; the config is
    static aux data, so ``jax.jit(partial_fit)(model, x, y)`` retraces
    only when the config changes;
  * **streaming-native** — the model carries the *raw* per-class
    accumulator (``class_sums``) and applies the binarization policy
    lazily (``class_hvs`` property), so ``partial_fit`` over batches is
    bit-identical to one ``fit`` over the concatenation;
  * **checkpointable** — ``save``/``load`` round-trip through
    :mod:`repro.checkpoint.manager` (atomic, async-capable, elastic),
    with the config embedded in the manifest;
  * **shardable** — ``shardings(mesh)`` mirrors the model with
    ``NamedSharding`` leaves (D axis over the "model" mesh axis when it
    divides), consumed by ``shard`` and by elastic checkpoint restore.

Module-level ``fit`` / ``partial_fit`` / ``predict`` are the pure jitted
functions; the methods are thin conveniences over them.  Encoding
dispatch goes through :mod:`repro.core.registry` — the model never
branches on encoder or backend names.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from pathlib import Path
from typing import Any, Iterable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import encoding, metrics, registry, unary
from repro.core.model import HDCConfig
from repro.obs.profiler import span


# ---------------------------------------------------------------------------
# n_seen: a (2,) uint32 [hi, lo] split counter.  jnp canonicalizes int64 to
# int32 unless the global x64 flag is flipped (which would change dtype
# promotion everywhere), so a plain scalar would wrap negative after ~2.1B
# streamed examples — corrupting every n_seen-derived statistic and the
# checkpoint round-trip.  Two uint32 words with an explicit carry are exact
# to 2**64 under any jax config.
# ---------------------------------------------------------------------------

_NSEEN_DTYPE = jnp.uint32


def _nseen_array(n) -> jax.Array:
    """Normalize a count into the (2,) uint32 [hi, lo] representation.

    Accepts python ints (any size below 2**64), () scalars (legacy
    checkpoints / call sites), or an existing (2,) split counter.
    """
    if isinstance(n, (jax.Array, np.ndarray)):
        a = jnp.asarray(n)
        if a.shape == (2,):
            return a.astype(_NSEEN_DTYPE)
        if a.shape == ():
            n = int(a)
        else:
            raise ValueError(f"n_seen must be a scalar or (2,) counter, got {a.shape}")
    n = int(n)
    if not 0 <= n < 1 << 64:
        raise ValueError(f"n_seen must be in [0, 2**64), got {n}")
    return jnp.asarray([n >> 32, n & 0xFFFFFFFF], _NSEEN_DTYPE)


def _nseen_add(ns: jax.Array, count: int) -> jax.Array:
    """ns + count with an explicit carry (count is a static batch size)."""
    lo = ns[1] + jnp.uint32(count & 0xFFFFFFFF)
    carry = (lo < ns[1]).astype(_NSEEN_DTYPE)  # uint32 add wrapped
    return jnp.stack([ns[0] + jnp.uint32(count >> 32) + carry, lo])


def _nseen_int(ns) -> int:
    hi, lo = np.asarray(ns)
    return (int(hi) << 32) | int(lo)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class HDCModel:
    """Config + codebooks + class-HV state, as one pytree.

    ``class_sums`` is the raw int32 accumulator of bundled class
    hypervectors; ``n_seen`` counts accumulated examples.  The
    inference-time class HVs (binarized per ``cfg.class_binarize``)
    are derived, never stored — see ``class_hvs``.
    """

    cfg: HDCConfig
    codebooks: dict[str, jax.Array]
    class_sums: jax.Array  # (C, D) int32 raw bundling accumulator
    n_seen: jax.Array  # (2,) uint32 [hi, lo] split example counter (see above)

    # -- pytree protocol -------------------------------------------------

    def tree_flatten(self):
        return (self.codebooks, self.class_sums, self.n_seen), self.cfg

    @classmethod
    def tree_unflatten(cls, cfg, children):
        codebooks, class_sums, n_seen = children
        return cls(cfg=cfg, codebooks=codebooks, class_sums=class_sums, n_seen=n_seen)

    def replace(self, **kw) -> "HDCModel":
        return dataclasses.replace(self, **kw)

    # -- construction ----------------------------------------------------

    @classmethod
    def create(cls, cfg: HDCConfig) -> "HDCModel":
        """Fresh untrained model: codebooks built, accumulator zeroed."""
        enc = registry.get_encoder(cfg.encoder)
        return cls.from_parts(cfg, enc.build_codebooks(cfg))

    @classmethod
    def from_parts(
        cls,
        cfg: HDCConfig,
        codebooks: dict[str, jax.Array],
        class_sums: jax.Array | None = None,
        n_seen: jax.Array | int = 0,
    ) -> "HDCModel":
        """Assemble from pre-built pieces (dry-runs, conversions).

        The codebook layout is validated against the encoder named in
        the config: pairing e.g. a ``uhd`` threshold table with a
        ``uhd_dynamic`` config would not fail until predict time — and
        then with garbage labels, not an error — so the mismatch is
        rejected loudly here.
        """
        expected = set(registry.get_encoder(cfg.encoder).codebook_specs(cfg))
        if set(codebooks) != expected:
            raise ValueError(
                f"codebook layout {sorted(codebooks)} does not match encoder "
                f"{cfg.encoder!r} (expects {sorted(expected)}); state saved "
                "under another encoder must be migrated with "
                "HDCModel.convert, not re-labelled"
            )
        if class_sums is None:
            class_sums = jnp.zeros((cfg.n_classes, cfg.d), jnp.int32)
        return cls(
            cfg=cfg,
            codebooks=codebooks,
            class_sums=class_sums,
            n_seen=_nseen_array(n_seen),
        )

    # -- derived state ---------------------------------------------------

    @property
    def class_hvs(self) -> jax.Array:
        """Inference-time class hypervectors per the binarization policy."""
        if self.cfg.resolved_class_binarize == "sign":
            return encoding.binarize(self.class_sums).astype(jnp.int32)
        return self.class_sums

    @property
    def encoder(self) -> registry.EncoderBase:
        return registry.get_encoder(self.cfg.encoder)

    @property
    def n_examples(self) -> int:
        """Total examples accumulated, as a python int (exact to 2**64).

        Host-side view of the ``n_seen`` split counter; inside a traced
        function use ``n_seen`` itself (the (2,) uint32 array).
        """
        return _nseen_int(self.n_seen)

    def pack(self) -> jax.Array:
        """Class HVs binarized (per `pack_center`) and packed 32 dims/word.

        Returns (C, n_words(D)) uint32 — the pack-once serving artifact:
        XOR + popcount against these words is the paper's entire
        inference datapath (see `predict_packed` / repro.serving).
        """
        return unary.pack_hypervector(_centered(self.cfg, self.class_hvs))

    def pack_queries(self, q: jax.Array) -> jax.Array:
        """Encoded query HVs (B, D) -> packed sign bits (B, n_words(D)),
        under the same centering policy as `pack` — hamming between the
        two packings is the serving similarity."""
        return unary.pack_hypervector(_centered(self.cfg, q))

    # -- core ops (delegate to the jitted module functions) --------------

    def encode(self, images: jax.Array, *, backend: str | None = None) -> jax.Array:
        """Raw images (B, H) -> non-binary hypervectors (B, D) int32."""
        cfg = self.cfg
        x_q = encoding.quantize_images(
            jnp.asarray(images), cfg.levels, cfg.max_intensity
        )
        return self.encoder.encode(
            cfg, self.codebooks, x_q, backend=backend or cfg.backend
        )

    def fit(self, images: jax.Array, labels: jax.Array) -> "HDCModel":
        """Single-pass training on this data alone (accumulator reset)."""
        labels = jnp.asarray(labels)
        encoding.validate_labels(labels, self.cfg.n_classes)
        return fit(self, jnp.asarray(images), labels)

    def partial_fit(
        self, images: jax.Array, labels: jax.Array, *, donate: bool = False
    ) -> "HDCModel":
        """Streaming training: accumulate one batch into the class sums.

        Labels are validated on the host before tracing (out-of-range
        labels raise instead of being silently dropped — see
        ``encoding.bundle_by_class`` for the jitted contract).  With
        ``donate=True`` this model's ``class_sums``/``n_seen`` buffers
        are donated to XLA and updated in place — no (C, D) re-allocation
        per step; the codebooks are never donated (they are shared,
        read-only state).  The donor model must not be used afterwards.
        """
        images, labels = jnp.asarray(images), jnp.asarray(labels)
        encoding.validate_labels(labels, self.cfg.n_classes)
        if not donate:
            return partial_fit(self, images, labels)
        sums, ns = _partial_fit_donated(
            _stateless(self), self.class_sums, self.n_seen, images, labels
        )
        return self.replace(class_sums=sums, n_seen=ns)

    def fit_batches(self, batches: Iterable[tuple[Any, Any]]) -> "HDCModel":
        """Memory-bounded fit over an iterator of (images, labels) —
        identical semantics to `fit` on the concatenated data.  The
        streaming state is donated between steps, so the (C, D)
        accumulator is updated in place instead of re-allocated per
        batch (this model's own buffers are untouched: the stream
        starts from fresh zeros, as `reset` gives)."""
        with span("hdc.fit.reset"):
            sums, n_seen = _zeroed(self.class_sums, self.n_seen)
        view = _stateless(self)  # once per stream, not per step
        for images, labels in batches:
            # the step's host work: label check and donated dispatch; it
            # also holds the wait while the device's queue of programs
            # is full
            with span("hdc.fit.step"):
                labels = jnp.asarray(labels)
                encoding.validate_labels(labels, self.cfg.n_classes)
                sums, n_seen = _partial_fit_donated(
                    view, sums, n_seen, jnp.asarray(images), labels
                )
        return self.replace(class_sums=sums, n_seen=n_seen)

    def retrain_batches(
        self, batches: Iterable[tuple[Any, Any]], *, epochs: int = 1
    ) -> tuple["HDCModel", jax.Array]:
        """Mispredict-driven retraining epochs from this model's class
        sums (DESIGN.md §15).  Per batch, in order: encode each image
        once, label it as `predict_packed` would serve (packed Hamming
        against this step's starting sums, lowest class on ties), then
        add every mispredicted image to its true class and subtract it
        from the predicted one, exactly in int32.  Epoch e + 1 starts
        from epoch e's sums; ``n_seen`` is unchanged.

        Returns the retrained model and the (epochs * steps,) int32
        mispredict count of every step, on the device: nothing is read
        back per step.  ``batches`` is iterated once per epoch (an
        iterator is listed first); all labels are range-checked once,
        before any step.  The sums are copied once and then donated
        between steps; this model's buffers are untouched."""
        if epochs < 1:
            raise ValueError(f"epochs must be >= 1, got {epochs}")
        batches = [(jnp.asarray(x), jnp.asarray(y)) for x, y in batches]
        encoding.validate_label_batches([y for _, y in batches], self.cfg.n_classes)
        with span("hdc.retrain.reset"):
            sums = jnp.copy(self.class_sums)
        view = _stateless(self)
        wrong = []
        for _ in range(epochs):
            for images, labels in batches:
                # the step's host work: the served class words of the
                # step's starting sums (`pack`, op by op as serving
                # builds them) and the donated dispatch
                with span("hdc.retrain.step"):
                    class_words = self.replace(class_sums=sums).pack()
                    sums, n_wrong = _retrain_step_donated(
                        view, sums, class_words, images, labels
                    )
                    wrong.append(n_wrong)
        counts = jnp.stack(wrong) if wrong else jnp.zeros((0,), jnp.int32)
        return self.replace(class_sums=sums), counts

    def reset(self) -> "HDCModel":
        """Drop accumulated class state (codebooks are kept)."""
        sums, n_seen = _zeroed(self.class_sums, self.n_seen)
        return self.replace(class_sums=sums, n_seen=n_seen)

    def convert(self, encoder: str) -> "HDCModel":
        """Re-encoder this model within its family, keeping class state.

        Encoders that declare the same ``family`` produce bit-identical
        hypervectors from the same config (e.g. ``uhd`` regenerates its
        threshold table from the very Sobol stream ``uhd_dynamic``
        re-derives per tile), so the accumulated ``class_sums`` remain
        exactly valid under the new encoder — only the codebooks are
        rebuilt (cheap, deterministic from the config).  The canonical
        use: train/checkpoint with the table datapath, serve table-free
        with the ~1000x smaller ``uhd_dynamic`` codebook.

        Cross-family conversion is refused: different families encode
        differently, so carried-over class sums would silently
        mis-predict.
        """
        cur = self.encoder
        new = registry.get_encoder(encoder)
        if (cur.family or cur.name) != (new.family or new.name):
            raise ValueError(
                f"cannot convert encoder {cur.name!r} (family "
                f"{cur.family or cur.name!r}) to {new.name!r} (family "
                f"{new.family or new.name!r}): class sums only transfer "
                "between encoders with bit-identical encode semantics"
            )
        # backend names are per-encoder; the old one may not exist here
        cfg = dataclasses.replace(
            self.cfg, encoder=encoder, backend="auto",
            use_kernels=None, encode_impl=None,
        )
        return HDCModel.from_parts(
            cfg, new.build_codebooks(cfg), self.class_sums, self.n_seen
        )

    def predict(self, images: jax.Array) -> jax.Array:
        """Classify images -> (B,) int32 predicted labels."""
        return predict(self, jnp.asarray(images))

    def evaluate(
        self, images: Any, labels: Any, batch_size: int = 1024
    ) -> float:
        """Test accuracy, evaluated in batches."""
        n = len(images)
        correct = 0
        for i in range(0, n, batch_size):
            pred = self.predict(jnp.asarray(images[i : i + batch_size]))
            correct += int((pred == jnp.asarray(labels[i : i + batch_size])).sum())
        return correct / n

    # -- persistence (repro.checkpoint.manager) --------------------------

    def _state_tree(self) -> dict[str, Any]:
        return {
            "codebooks": self.codebooks,
            "class_sums": self.class_sums,
            "n_seen": self.n_seen,
        }

    def save(
        self, path: str | Path, *, step: int = 0, blocking: bool = True, keep_n: int = 3
    ) -> None:
        """Atomic checkpoint under `path` (one step directory).

        The config rides in the manifest, so `load` needs only the path.
        """
        from repro.checkpoint.manager import CheckpointManager

        mgr = CheckpointManager(path, keep_n=keep_n)
        raw_cfg = dataclasses.asdict(self.cfg)
        # the deprecated aliases are already folded into `backend`; keeping
        # them in the manifest would re-warn on every future load
        raw_cfg.pop("use_kernels", None)
        raw_cfg.pop("encode_impl", None)
        mgr.save(
            step,
            self._state_tree(),
            blocking=blocking,
            extra={"hdc_config": raw_cfg},
        )

    def save_shard(
        self,
        path: str | Path,
        *,
        step: int = 0,
        process_index: int,
        process_count: int,
        keep_n: int = 3,
    ) -> None:
        """Write this host's slice of a multi-host checkpoint.

        Arrays with a trailing D axis (``class_sums`` and D-wide
        codebooks such as the uHD threshold table) are written as
        per-host shard files holding this host's D-slice; replicated
        leaves (``n_seen``, the tiny ``uhd_dynamic`` direction matrix)
        are written by host 0 alone, which also stages the manifest.
        Nothing becomes visible to readers until — after every host has
        called this (the inter-host barrier is the caller's) — host 0
        publishes atomically with
        ``CheckpointManager(path).finalize_shards(step)``.
        ``HDCModel.load`` then restores the stitched checkpoint
        bit-identically, on any device count.

        In this single-process repro the method is also the simulation
        hook: call it once per virtual host from one process (each call
        slices this model's full arrays) and then finalize.
        """
        from repro.checkpoint.manager import CheckpointManager, _flatten_with_paths

        d = self.cfg.d
        if d % process_count:
            raise ValueError(
                f"d={d} does not divide over {process_count} checkpoint shards"
            )
        chunk = d // process_count
        sl = slice(process_index * chunk, (process_index + 1) * chunk)

        def local(leaf):
            shape = tuple(getattr(leaf, "shape", ()))
            if shape and shape[-1] == d:
                return leaf[..., sl]
            return leaf

        state = jax.tree_util.tree_map(local, self._state_tree())
        flat, _ = _flatten_with_paths(self._state_tree())
        shard_axes = {
            key: np.ndim(leaf) - 1
            for key, leaf in flat
            if np.ndim(leaf) and tuple(np.shape(leaf))[-1] == d
        }
        raw_cfg = dataclasses.asdict(self.cfg)
        raw_cfg.pop("use_kernels", None)
        raw_cfg.pop("encode_impl", None)
        CheckpointManager(path, keep_n=keep_n).save_shard(
            step,
            state,
            process_index=process_index,
            process_count=process_count,
            shard_axes=shard_axes,
            extra={"hdc_config": raw_cfg},
        )

    @classmethod
    def load(
        cls,
        path: str | Path,
        *,
        step: int | None = None,
        mesh: Mesh | None = None,
    ) -> "HDCModel":
        """Restore a saved model; with `mesh`, arrays land pre-sharded
        (elastic restore onto a different device count is supported)."""
        from repro.checkpoint.manager import CheckpointManager

        mgr = CheckpointManager(path)
        if step is None:
            step = mgr.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints under {path}")
        raw = mgr.extra(step).get("hdc_config")
        if raw is None:
            raise ValueError(f"checkpoint step {step} has no hdc_config manifest")
        raw.pop("use_kernels", None)  # older manifests may carry the aliases
        raw.pop("encode_impl", None)
        cfg = HDCConfig(**raw)
        # abstract template: restore needs only structure + shapes, so the
        # codebooks (host-side Sobol generation for uHD) are never built
        # legacy checkpoints stored n_seen as a () int32 scalar; restore
        # with the shape actually on disk, then normalize to the split
        # counter (HDCModel.from_parts / _nseen_array)
        nseen_shape = tuple(
            mgr.leaf_meta(step).get("n_seen", {}).get("shape", (2,))
        )
        like = cls(
            cfg=cfg,
            codebooks=registry.get_encoder(cfg.encoder).codebook_specs(cfg),
            class_sums=jax.ShapeDtypeStruct((cfg.n_classes, cfg.d), jnp.int32),
            n_seen=(
                jax.ShapeDtypeStruct((), jnp.int32)
                if nseen_shape == ()
                else jax.ShapeDtypeStruct((2,), _NSEEN_DTYPE)
            ),
        )
        shardings = like.shardings(mesh)._state_tree() if mesh is not None else None
        state = mgr.restore(step, like._state_tree(), shardings=shardings)
        state["n_seen"] = _nseen_array(state["n_seen"])
        return cls(cfg=cfg, **state)

    # -- distribution ----------------------------------------------------

    def shardings(self, mesh: Mesh, *, rules=None) -> "HDCModel":
        """Mirror of this model with NamedSharding leaves.

        Arrays whose trailing axis is D shard over the "model" mesh axis
        (when present and dividing — the same graceful-fallback contract
        as repro.distributed.sharding); everything else replicates.
        """
        from repro.distributed.sharding import ShardingRules, model_axis_for

        rules = rules or ShardingRules()
        axis = model_axis_for(mesh, self.cfg.d, rules=rules)

        def spec(leaf) -> NamedSharding:
            shape = tuple(getattr(leaf, "shape", ()))
            if axis and shape and shape[-1] == self.cfg.d:
                return NamedSharding(mesh, P(*([None] * (len(shape) - 1)), axis))
            return NamedSharding(mesh, P())

        return jax.tree_util.tree_map(spec, self)

    def shard(self, mesh: Mesh, *, rules=None) -> "HDCModel":
        """device_put every leaf per `shardings(mesh)`."""
        return jax.device_put(self, self.shardings(mesh, rules=rules))


# ---------------------------------------------------------------------------
# Pure jitted training/inference functions (cfg rides statically in the
# model's treedef — retrace only on config change)
# ---------------------------------------------------------------------------


def _encode(model: HDCModel, images: jax.Array) -> jax.Array:
    cfg = model.cfg
    x_q = encoding.quantize_images(images, cfg.levels, cfg.max_intensity)
    enc = registry.get_encoder(cfg.encoder)
    return enc.encode(cfg, model.codebooks, x_q, backend=cfg.backend)


def _fit_sums(model: HDCModel, images: jax.Array, labels: jax.Array) -> jax.Array:
    """One batch -> (C, D) int32 class sums via the encoder's fit_bundle
    dispatch: fused encode+bundle when the resolved backend registers it
    (the (B, D) hypervector batch never materializes), bit-identical
    encode-then-bundle_by_class otherwise (DESIGN.md §9)."""
    cfg = model.cfg
    x_q = encoding.quantize_images(images, cfg.levels, cfg.max_intensity)
    enc = registry.get_encoder(cfg.encoder)
    return enc.fit_bundle(cfg, model.codebooks, x_q, labels, backend=cfg.backend)


def _partial_fit(model: HDCModel, images: jax.Array, labels: jax.Array) -> HDCModel:
    return model.replace(
        class_sums=model.class_sums + _fit_sums(model, images, labels),
        n_seen=_nseen_add(model.n_seen, labels.shape[0]),
    )


partial_fit = jax.jit(_partial_fit)
partial_fit.__doc__ = "Accumulate one batch of bundled class sums into the model."


@jax.jit
def _zeroed(class_sums: jax.Array, n_seen: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fresh training state shaped like the given one, in one dispatch."""
    return jnp.zeros_like(class_sums), jnp.zeros_like(n_seen)


def _stateless(model: HDCModel) -> HDCModel:
    """The model with its mutable training state swapped for None (no
    leaves, so no device work) — passed *un-donated* alongside the
    donated state so the shared, read-only codebooks are never
    invalidated by donation."""
    return model.replace(class_sums=None, n_seen=None)


@functools.partial(jax.jit, donate_argnums=(1, 2))
def _partial_fit_donated(
    stateless: HDCModel,
    class_sums: jax.Array,
    n_seen: jax.Array,
    images: jax.Array,
    labels: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """partial_fit with the training state donated: XLA aliases the
    (C, D) accumulator input to its output, so streaming training
    updates in place instead of re-allocating every step."""
    model = stateless.replace(class_sums=class_sums, n_seen=n_seen)
    out = _partial_fit(model, images, labels)
    return out.class_sums, out.n_seen


@functools.partial(jax.jit, donate_argnums=(1,))
def _retrain_step_donated(
    stateless: HDCModel,
    class_sums: jax.Array,
    class_words: jax.Array,
    images: jax.Array,
    labels: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """One retraining step (DESIGN.md §15) with the class sums donated.

    The batch is encoded once through the registered backend; its
    packed queries are scored against `class_words` (the step's
    starting sums as `HDCModel.pack` serves them) by the backend's
    top-1, and the same hypervectors are folded into the sums by
    `encoding.retrain_update`.  Returns the new sums and the step's
    mispredict count."""
    cfg = stateless.cfg
    hv = _encode(stateless, images)
    q = encoding.binarize(hv).astype(jnp.int32) if cfg.binarize_query else hv
    enc = registry.get_encoder(cfg.encoder)
    idx, _ = enc.topk(stateless.pack_queries(q), class_words, cfg.d, 1,
                      backend=cfg.backend)
    predicted = idx[:, 0]
    update = encoding.retrain_update(hv, labels, predicted, cfg.n_classes,
                                     cfg.n_features)
    return class_sums + update, jnp.sum(predicted != labels, dtype=jnp.int32)


def _fit(model: HDCModel, images: jax.Array, labels: jax.Array) -> HDCModel:
    return model.replace(
        class_sums=_fit_sums(model, images, labels),
        n_seen=_nseen_array(labels.shape[0]),
    )


fit = jax.jit(_fit)
fit.__doc__ = "Single-pass training from scratch: reset, encode, bundle."


# ---------------------------------------------------------------------------
# Multi-host training: shard_map with explicit batch-axis psum (DESIGN.md §9)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=16)
def _sharded_partial_fit_fn(cfg: HDCConfig, mesh: Mesh, rules):
    """Build (and cache, keyed by config/mesh/rules) the jitted shard_map
    partial_fit step.  See `partial_fit_sharded` for the semantics."""

    from repro.distributed.sharding import model_axis_for

    batch_axes = rules.batch_axes(mesh)
    bsz = math.prod(mesh.shape[a] for a in batch_axes) if batch_axes else 1
    model_axis = model_axis_for(mesh, cfg.d, rules=rules)
    d_local = cfg.d // (mesh.shape[model_axis] if model_axis else 1)
    enc = registry.get_encoder(cfg.encoder)

    like = HDCModel(
        cfg=cfg,
        codebooks=enc.codebook_specs(cfg),
        class_sums=jax.ShapeDtypeStruct((cfg.n_classes, cfg.d), jnp.int32),
        n_seen=jax.ShapeDtypeStruct((2,), _NSEEN_DTYPE),
    )
    mspecs = jax.tree_util.tree_map(lambda ns: ns.spec, like.shardings(mesh, rules=rules))
    bspec = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)

    def step(m: HDCModel, images: jax.Array, labels: jax.Array) -> HDCModel:
        x_q = encoding.quantize_images(images, cfg.levels, cfg.max_intensity)
        point_offset = None
        if model_axis is not None and enc.dynamic_generator:
            # each shard Gray-codes only the Sobol points of its D-slice
            point_offset = jax.lax.axis_index(model_axis) * d_local
        sums = enc.fit_bundle(
            cfg, m.codebooks, x_q, labels,
            backend=cfg.backend, d=d_local, point_offset=point_offset,
        )
        if batch_axes:
            sums = jax.lax.psum(sums, batch_axes)
        # the global batch is static (local rows x batch-mesh size), so the
        # counter add needs no collective and stays replicated
        return m.replace(
            class_sums=m.class_sums + sums,
            n_seen=_nseen_add(m.n_seen, labels.shape[0] * bsz),
        )

    fn = jax.shard_map(
        step,
        mesh=mesh,
        in_specs=(mspecs, P(bspec, None), P(bspec)),
        out_specs=mspecs,
        check_vma=False,
    )
    return jax.jit(fn), bsz


def partial_fit_sharded(
    model: HDCModel,
    images: jax.Array,
    labels: jax.Array,
    *,
    mesh: Mesh,
    rules=None,
) -> HDCModel:
    """The true multi-host `partial_fit`: shard_map with explicit
    collectives instead of GSPMD inference.

    The image batch shards over the ``("pod", "data")`` mesh axes; every
    device computes the (C, D_local) class sums of its shard through the
    fused ``fit_bundle`` datapath and the partial sums reduce with **one
    explicit psum of (C, D_local)** — the entire cross-device traffic of
    a training step.  When the ``"model"`` axis divides D, the class
    sums (and any D-wide codebook, e.g. the uHD threshold table) are
    D-partitioned; the ``uhd_dynamic`` generator then runs *per
    D-slice*: each device Gray-codes only the Sobol points
    ``[skip + offset, skip + offset + D_local)`` of its slice, with the
    tiny (H, 32) direction matrix replicated — pure compute
    partitioning.  All arithmetic is integer, so the result is
    bit-identical to single-device ``partial_fit`` on the gathered
    batch.
    """
    from repro.distributed.sharding import ShardingRules

    rules = rules or ShardingRules()
    images, labels = jnp.asarray(images), jnp.asarray(labels)
    encoding.validate_labels(labels, model.cfg.n_classes)
    fn, bsz = _sharded_partial_fit_fn(model.cfg, mesh, rules)
    if images.shape[0] % bsz:
        raise ValueError(
            f"global batch {images.shape[0]} must divide the {bsz}-way "
            f"batch mesh axes {rules.batch_axes(mesh)}"
        )
    return fn(model, images, labels)


def _centered(cfg: HDCConfig, hv: jax.Array) -> jax.Array:
    """Apply the packed-inference centering policy before sign-packing.

    "row" subtracts each hypervector's own mean over D (float32; the
    sums involved stay well inside float32's exact-integer range for
    repro-scale D/H/n).  Sign bits of the result are the packed
    representation — see HDCConfig.pack_center.
    """
    if cfg.resolved_pack_center == "row":
        x = hv.astype(jnp.float32)
        return x - x.mean(-1, keepdims=True)
    return hv


def _packed_similarity(
    q_words: jax.Array, c_words: jax.Array, d: int, impl: str
) -> jax.Array:
    """XOR+popcount scores (B, C) int32 via the named implementation.

    "jnp" is the pure-JAX packed path (runs everywhere); "pallas" is the
    fused kernel (native on TPU, interpret mode elsewhere).  Both are
    bit-exact realizations of d - 2*popcount(q ^ c).
    """
    if impl == "pallas":
        from repro.kernels import ops  # local import: kernels are optional

        return ops.hamming_packed(q_words, c_words, d)
    if impl == "jnp":
        return metrics.hamming_similarity_packed(q_words, c_words, d)
    raise ValueError(f"unknown packed-similarity impl {impl!r}")


def _packed_topk(
    q_words: jax.Array, c_words: jax.Array, d: int, k: int, impl: str
) -> tuple[jax.Array, jax.Array]:
    """Scored top-k over packed rows via the named implementation.

    (B, W) x (C, W) uint32 -> ((B, k) int32 indices, (B, k) int32
    Hamming distances), each row ascending by (distance, index) with
    the **lowest index winning ties** (DESIGN.md §14).  "jnp" is the
    tiled pure-JAX scan; "pallas" the streaming kernel — both
    bit-identical to `repro.kernels.ref.hamming_topk_oracle`.
    """
    if impl == "pallas":
        from repro.kernels import ops  # local import: kernels are optional

        return ops.hamming_topk(q_words, c_words, d, k)
    if impl == "jnp":
        from repro.kernels import ref as kref  # pure jnp; always importable

        return kref.hamming_topk(q_words, c_words, d, k)
    raise ValueError(f"unknown packed top-k impl {impl!r}")


@jax.jit
def predict(model: HDCModel, images: jax.Array) -> jax.Array:
    """Encode queries, score against class HVs, argmax."""
    cfg = model.cfg
    q = _encode(model, images)
    if cfg.binarize_query:
        q = encoding.binarize(q).astype(jnp.int32)
    class_hvs = model.class_hvs
    if cfg.similarity == "hamming":
        qw = model.pack_queries(q)
        cw = model.pack()
        sim = _packed_similarity(qw, cw, cfg.d, "jnp").astype(jnp.float32)
    else:
        sim = metrics.SIMILARITIES[cfg.similarity](q, class_hvs)
    return metrics.classify(sim)


@functools.partial(jax.jit, static_argnames=("impl",))
def predict_packed(
    model: HDCModel,
    images: jax.Array,
    class_words: jax.Array,
    *,
    impl: str = "jnp",
) -> jax.Array:
    """Serving fast path: encode -> pack -> XOR+popcount -> nearest class.

    `class_words` is the pack-once artifact from :meth:`HDCModel.pack`,
    so per-request work never touches the (C, D) class sums.  Expressed
    as the k=1 case of the scored top-k primitive (DESIGN.md §14):
    max similarity = min Hamming distance, and the pinned
    lowest-index-wins tie-break is exactly `jnp.argmax`'s
    first-occurrence contract — so labels are bit-identical to
    `predict` with ``similarity="hamming"`` (same `pack_queries`:
    encode, optional binarize, centering, sign bits).
    """
    indices, _ = search_packed(model, images, class_words, k=1, impl=impl)
    return indices[:, 0]


@functools.partial(jax.jit, static_argnames=("k", "impl"))
def search_packed(
    model: HDCModel,
    images: jax.Array,
    item_words: jax.Array,
    *,
    k: int,
    impl: str = "jnp",
) -> tuple[jax.Array, jax.Array]:
    """Associative-memory search: encode queries, scan packed rows,
    return the k nearest per query (DESIGN.md §14).

    `item_words` is any (C, W) packed store — the model's class words
    from :meth:`HDCModel.pack`, or an `ItemMemory`'s rows — and must be
    packed over the same d = ``cfg.d``.  Returns ((B, k) int32 row
    indices, (B, k) int32 Hamming distances), each row ascending by
    (distance, index), lowest index winning ties; bit-identical to the
    full-argsort oracle on every impl.  ``k=1`` recovers
    :func:`predict_packed`'s labels exactly.
    """
    cfg = model.cfg
    q = _encode(model, images)
    if cfg.binarize_query:
        q = encoding.binarize(q).astype(jnp.int32)
    qw = model.pack_queries(q)
    return _packed_topk(qw, item_words, cfg.d, k, impl)


def train_and_eval(
    cfg: HDCConfig,
    train_images: np.ndarray,
    train_labels: np.ndarray,
    test_images: np.ndarray,
    test_labels: np.ndarray,
    batch_size: int = 2048,
) -> float:
    """Convenience end-to-end: create, fit (streamed), evaluate."""
    model = HDCModel.create(cfg)

    def batches():
        for i in range(0, len(train_images), batch_size):
            yield train_images[i : i + batch_size], train_labels[i : i + batch_size]

    return model.fit_batches(batches()).evaluate(test_images, test_labels)


def baseline_iterative_search(
    base_cfg: HDCConfig,
    train_images: np.ndarray,
    train_labels: np.ndarray,
    test_images: np.ndarray,
    test_labels: np.ndarray,
    iterations: int,
    batch_size: int = 2048,
) -> list[float]:
    """The paper's baseline protocol: regenerate pseudo-random P/L per
    iteration i, retrain, record test accuracy (Table IV / Fig. 6(a)).
    """
    accs = []
    for i in range(iterations):
        # Backend names are per-encoder: switching to the baseline
        # encoder resets datapath selection to "auto".
        cfg = dataclasses.replace(
            base_cfg, encoder="baseline", seed=i, backend="auto",
            use_kernels=None, encode_impl=None,
        )
        accs.append(
            train_and_eval(
                cfg, train_images, train_labels, test_images, test_labels, batch_size
            )
        )
    return accs
