"""Item memory: a mutable store of packed hypervectors with scored
nearest-neighbor search (DESIGN.md §14).

The canonical HDC workload beyond classification: stash binarized
hypervectors 32 dims/word (~1 KB each at D=8192 — a million rows is
~1 GB) and answer "which stored rows are Hamming-nearest to this
query?" through the same streaming top-k datapath that backs
`predict_packed`.  Rows live on the host as one contiguous uint32
array; `search` moves them to the device lazily and caches the
placement until the next mutation, so the steady-state cost of a query
is exactly one packed scan.

Indices returned by `search` are *current positions* in the store —
`delete` compacts, so positions shift left past the deleted rows (the
usual numpy-delete semantics).  Callers needing stable external ids
should keep their own id column alongside.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import unary
from repro.core.hdc_model import _packed_topk
from repro.obs.profiler import span


def _default_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


class ItemMemory:
    """Append/delete/search over packed ±1 hypervector rows.

    ``d`` is the hypervector dimensionality (need not be a multiple of
    32; pad bits are zeroed by the packers and cancel in the XOR).
    ``impl`` picks the scan datapath — "jnp" (tiled pure-JAX scan) or
    "pallas" (streaming kernel); default is platform-auto.  Both are
    bit-identical to the full-argsort oracle.
    """

    def __init__(self, d: int, *, impl: str | None = None):
        if d < 1:
            raise ValueError(f"d must be positive, got {d}")
        self.d = int(d)
        self.n_words = unary.n_words(self.d)
        self.impl = impl or _default_impl()
        self._rows = np.zeros((0, self.n_words), np.uint32)
        self._dev: jax.Array | None = None  # device cache of _rows

    def __len__(self) -> int:
        return self._rows.shape[0]

    @property
    def nbytes(self) -> int:
        return self._rows.nbytes

    def add(self, hvs) -> np.ndarray:
        """Append ±1 (or sign-of-sum) hypervectors; (n, d) -> the n new
        row positions.  Sign-packs exactly like `HDCModel.pack`: bit =
        (hv >= 0), pad bits zero."""
        hvs = jnp.asarray(hvs)
        if hvs.ndim == 1:
            hvs = hvs[None]
        if hvs.shape[-1] != self.d:
            raise ValueError(
                f"expected hypervectors of d={self.d}, got {hvs.shape[-1]}"
            )
        return self.add_packed(unary.pack_hypervector(hvs))

    def add_packed(self, words) -> np.ndarray:
        """Append already-packed rows; (n, n_words) uint32 -> positions."""
        words = np.asarray(words, np.uint32)
        if words.ndim == 1:
            words = words[None]
        if words.shape[-1] != self.n_words:
            raise ValueError(
                f"expected {self.n_words} words per row, got {words.shape[-1]}"
            )
        start = len(self)
        self._rows = np.concatenate([self._rows, words], axis=0)
        self._dev = None
        return np.arange(start, len(self), dtype=np.int32)

    def delete(self, indices) -> None:
        """Remove rows by current position; later rows shift left."""
        idx = np.atleast_1d(np.asarray(indices, np.int64))
        n = len(self)
        if idx.size and (idx.min() < -n or idx.max() >= n):
            raise IndexError(f"row index out of range for store of {n}")
        self._rows = np.delete(self._rows, idx, axis=0)
        self._dev = None

    def _device_rows(self) -> jax.Array:
        if self._dev is None:
            with span("hdc.store.upload"):  # the whole store, after any mutation
                self._dev = jnp.asarray(self._rows)
        return self._dev

    def search(self, queries, k: int) -> tuple[np.ndarray, np.ndarray]:
        """k nearest stored rows per query, pinned lowest-index ties.

        ``queries`` is either (B, d) raw ±1 hypervectors (sign-packed
        here) or (B, n_words) uint32 already-packed rows.  Returns
        ((B, k) int32 positions, (B, k) int32 Hamming distances), each
        row ascending by (distance, index).
        """
        k = int(k)
        if not 1 <= k <= len(self):
            raise ValueError(
                f"k must be in [1, {len(self)}] for a store of {len(self)} "
                f"rows, got {k}"
            )
        q = jnp.asarray(queries)
        if q.ndim == 1:
            q = q[None]
        if q.dtype == jnp.uint32 and q.shape[-1] == self.n_words:
            qw = q
        elif q.shape[-1] == self.d:
            qw = unary.pack_hypervector(q)
        else:
            raise ValueError(
                f"queries must be (B, {self.d}) hypervectors or "
                f"(B, {self.n_words}) packed uint32 rows, got {q.shape}"
            )
        idx, dist = _packed_topk(qw, self._device_rows(), self.d, k, self.impl)
        return np.asarray(idx), np.asarray(dist)
