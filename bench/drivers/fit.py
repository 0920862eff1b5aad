"""Driver ``fit``: `HDCModel.fit_batches` from a fresh model, epoch after
epoch over a training set made on the device from the seed.

Traffic keys: ``n_train`` images, ``batch`` images per step (the last
step takes the remainder), ``trace_seconds``.

End-to-end: ``fit_images_per_s`` = images folded into class sums over
the window (whole epochs).  Checked: every epoch's class sums and
example count against the reference's class sums of the same set.
Control: the jitted fit step adds the lower reference's class sums of
its batch.
"""

from __future__ import annotations

import numpy as np

E2E = "fit_images_per_s"


def setup(run) -> None:
    import jax

    from bench import inputs, preflight
    from repro.core import HDCModel, hdc_model

    c, t = run.cfg, run.traffic
    x, y = inputs.device_dataset(run.seed, t["n_train"], c["n_features"], c["n_classes"])
    jax.block_until_ready((x, y))
    b = t["batch"]
    batches = [(x[i : i + b], y[i : i + b]) for i in range(0, t["n_train"], b)]
    jax.block_until_ready(batches)
    run.mark("data")
    model = HDCModel.create(run.hdc_config())
    jax.block_until_ready(model.codebooks)
    run.mark("build")
    preflight.check_backend(run, model.cfg.encoder)
    preflight.native(run, hdc_model._partial_fit_donated.lower(
        hdc_model._stateless(model), model.class_sums, model.n_seen, *batches[0]),
        "fit step")
    jax.block_until_ready(model.fit_batches(batches).class_sums)  # every shape
    run.mark("warm")
    run.state.update(x=x, y=y, batches=batches, model=model)


def window(run) -> None:
    import jax

    model, batches = run.state["model"], run.state["batches"]
    n = run.traffic["n_train"]
    epochs = []
    with run.measure() as w:
        while True:
            m = model.fit_batches(batches)
            jax.block_until_ready(m.class_sums)
            epochs.append((m.class_sums, m.n_seen))
            w.tick(len(epochs) * n)
            if w.elapsed() >= run.seconds:
                break
    run.state["epochs"] = epochs
    images = len(epochs) * n
    run.e2e[E2E] = images / w.seconds
    run.attempted = images
    run.work.update(images=images, window_s=w.seconds,
                    traced_images=w.traced_work)


def release(run) -> None:
    run.state.pop("model", None)
    run.state.pop("batches", None)


def check(run):
    import jax.numpy as jnp

    from bench.harness import Check
    from bench.reference import Reference

    want = Reference(run.cfg, run.seed).class_sums(run.state["x"], run.state["y"])
    n = run.traffic["n_train"]
    sums_off = 0
    count_off = 0
    for sums, n_seen in run.state["epochs"]:
        sums_off += int(jnp.sum(sums != want))
        hi, lo = (int(v) for v in np.asarray(n_seen))
        count_off += abs(((hi << 32) | lo) - n)
    return [
        Check("class_sum_entries_differing", float(sums_off), 0.0),
        Check("examples_miscounted", float(count_off), 0.0),
    ]


def control(cfg: dict, traffic: dict, seed: int):
    import jax.numpy as jnp

    from bench.control import Stand, lower_reference
    from repro.core import hdc_model

    low = lower_reference(cfg, seed)

    def step(stateless, sums, n_seen, images, labels):
        n = jnp.uint32(labels.shape[0])
        lo = n_seen[1] + n
        hi = n_seen[0] + (lo < n_seen[1]).astype(n_seen.dtype)
        return sums + low.class_sums(images, labels), jnp.stack([hi, lo])

    real = hdc_model._partial_fit_donated
    return [(hdc_model, "_partial_fit_donated", Stand(real, step))]
