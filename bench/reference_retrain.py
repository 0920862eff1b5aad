"""Plain reference of uHD retraining, written from its definition
(DESIGN.md §15).

It takes from `bench.reference` only that module's definitions of the
datapath (thresholds, encode, class sums, packing, distances) and
nothing of the program.  Given the run's training images and labels,
it replays in plain `jax.numpy`, block by block on the device:

  * the single-pass class sums S_0 (`Reference.class_sums`);
  * one retraining epoch from S_0 in steps of ``batch`` images, in
    order.  Step t: H = encode(x_t); each image's label is the nearest
    class word (Hamming, lowest index on ties) of ``pack(S_{t-1})``,
    the class words as served; w_i = [label_i != y_i];
    S_t = S_{t-1} + sum_i w_i (e_{y_i} - e_{label_i}) H_i^T in int32;
    the step's count is sum_i w_i.

``Reference(cfg, seed, bits)`` sets the precision: the control runs
`step` with one bit fewer.
"""

from __future__ import annotations

import numpy as np

from bench.reference import Reference, _bundle, _distances, _pack_queries


def step(ref: Reference, sums, class_words, x, y):
    """One step from sums S_{t-1} whose served class words are given:
    (S_t, the step's mispredict count)."""
    import jax.numpy as jnp

    c = ref.cfg["n_classes"]
    hv = ref.encode(x)
    label = jnp.argmin(_distances(_pack_queries(hv), class_words), axis=1).astype(jnp.int32)
    wrong = label != y
    folded = jnp.where(wrong[:, None], hv, 0)
    return sums + _bundle(folded, y, c) - _bundle(folded, label, c), jnp.sum(wrong)


def epoch(ref: Reference, sums, x, y, batch: int):
    """One epoch from `sums`: the final sums and the (steps,) counts."""
    counts = []
    for i in range(0, x.shape[0], batch):
        sums, wrong = step(ref, sums, Reference.pack(sums), x[i : i + batch], y[i : i + batch])
        counts.append(wrong)
    return sums, np.asarray([int(w) for w in counts], np.int64)


def replay(cfg: dict, seed: int, x, y, batch: int):
    """The single-pass sums of (x, y), then one retraining epoch from
    them: (its final sums, its per-step mispredict counts)."""
    ref = Reference(cfg, seed)
    return epoch(ref, ref.class_sums(x, y, block=batch), x, y, batch)
