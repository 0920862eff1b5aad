"""`ServingEngine`: the pack-once packed-hamming inference unit.

The paper's serving story (contributions 3/4): once class hypervectors
are binarized, classification is XOR + popcount over uint32 words.  The
engine does all the expensive work exactly once at load time —

  * restore an `HDCModel` from a checkpoint step,
  * place it per its execution backend (single device, or D-sharded
    over a ``("model",)`` mesh — see :mod:`repro.serving.execution`),
  * binarize + bit-pack the (C, D) class sums into uint32 words in the
    backend's own layout,

— and after that every request batch runs one jitted
``encode -> pack -> XOR+popcount -> argmax`` call.  *Where* that call
runs is the execution backend's business: the engine itself is
placement-agnostic — PR 8 split the old baked-in single-device
assumption into the pluggable :class:`~repro.serving.execution`
layer, so the same engine fronts one chip or a D-sharded device group
bit-identically.  The similarity implementation is picked per platform:
the fused Pallas kernel natively on TPU, the pure-JAX packed path
elsewhere.  Both are bit-exact, and tests pin the engine's labels to
``HDCModel.predict`` with ``similarity="hamming"`` for every registered
uHD backend — including under sharding.

Engines are immutable once built — hot reload (`repro.serving.registry`)
builds a fresh engine from a newer step and swaps the reference, so an
in-flight batch on the old engine is never disturbed.  The execution
backend is reused across reloads: placement survives promotion.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.hdc_model import HDCModel
from repro.obs.profiler import span
from repro.serving.execution import DeviceExecution, resolve_impl

__all__ = ["ServingEngine", "resolve_impl"]


class ServingEngine:
    """One loaded model, packed for inference, behind a jitted predict."""

    def __init__(
        self,
        model: HDCModel,
        *,
        batch_size: int = 64,
        impl: str = "auto",
        step: int | None = None,
        source: str | Path | None = None,
        execution=None,
    ):
        self.execution = execution if execution is not None else DeviceExecution(impl=impl)
        self.model = self.execution.place(model)
        self.batch_size = int(batch_size)
        self.impl = self.execution.impl
        self.step = step
        self.source = Path(source) if source is not None else None
        # pack ONCE at load: uint32 class words in the execution
        # backend's layout — per-request work never touches the int32
        # class sums again
        self.class_words = jax.block_until_ready(self.execution.pack(self.model))

    @classmethod
    def from_checkpoint(
        cls,
        path: str | Path,
        *,
        step: int | None = None,
        batch_size: int = 64,
        impl: str = "auto",
        execution=None,
    ) -> "ServingEngine":
        """Load a checkpointed `HDCModel` (latest step by default) and
        pack it for serving.  `step` pins an exact step — the hot-reload
        path uses this to load the step it decided to promote; it also
        passes the old engine's `execution` so placement survives."""
        from repro.checkpoint.manager import CheckpointManager

        if step is None:
            step = CheckpointManager(path).latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints under {path}")
        model = HDCModel.load(path, step=step)
        return cls(
            model, batch_size=batch_size, impl=impl, step=step, source=path,
            execution=execution,
        )

    # -- inference --------------------------------------------------------

    def predict(self, images) -> np.ndarray:
        """(B, H) raw images -> (B,) int32 labels (host numpy).

        Shape-polymorphic but retraces per distinct B — the batcher
        always sends `batch_size` rows so steady-state traffic compiles
        exactly once.
        """
        labels = self.execution.predict(self.model, self.class_words, images)
        with span("hdc.engine.read"):  # waits for the device, then copies
            return np.asarray(labels)

    def search(self, images, k: int) -> tuple[np.ndarray, np.ndarray]:
        """(B, H) raw images -> ((B, k) int32 row indices, (B, k) int32
        Hamming distances), each row ascending by (distance, index)
        with lowest index winning ties (DESIGN.md §14).

        The store searched is the engine's pack-once class-word matrix
        — the same artifact `predict` argmaxes over — so ``k=1``
        indices equal `predict`'s labels bit-for-bit.  Retraces per
        distinct (B, k); the batcher coalesces only same-k blocks so
        steady-state traffic compiles once per served k.
        """
        idx, dist = self.execution.search(
            self.model, self.class_words, images, int(k)
        )
        with span("hdc.engine.read"):  # waits for the device, then copies
            return np.asarray(idx), np.asarray(dist)

    def warmup(self) -> "ServingEngine":
        """Compile the static-shape serving path before taking traffic."""
        dummy = jnp.zeros((self.batch_size, self.model.cfg.n_features), jnp.float32)
        jax.block_until_ready(
            self.execution.predict(self.model, self.class_words, dummy)
        )
        return self

    def describe(self) -> dict:
        cfg = self.model.cfg
        return {
            "encoder": cfg.encoder,
            "d": cfg.d,
            "n_classes": cfg.n_classes,
            "impl": self.impl,
            "placement": self.execution.placement,
            "execution": self.execution.describe(),
            "batch_size": self.batch_size,
            "step": self.step,
            "source": str(self.source) if self.source else None,
            "n_seen": self.model.n_examples,
            "packed_bytes": int(self.class_words.size * 4),
            # resident encoder state: the whole point of uhd_dynamic is
            # that this is O(H*32) instead of the O(H*D) table
            "codebook_bytes": int(
                sum(v.size * v.dtype.itemsize for v in self.model.codebooks.values())
            ),
        }
