"""Driver ``retrain``: mispredict-driven retraining epochs
(`HDCModel.retrain_batches`) over class-conditioned training images
made on the device from the seed.

Set-up: the images; the single-pass model (`fit_batches` over the same
steps); one retraining epoch, which warms every shape.  Window:
retraining epochs, each from the single-pass sums, epoch after epoch,
so every epoch is the same work.

Traffic keys: ``n_train`` images, ``batch`` images per step (the last
step takes the remainder), ``trace_seconds``.  The configuration's
``noise_amplitude`` sets the images' noise.

End-to-end: ``fit_images_per_s`` = images retrained over (encoded,
labelled, folded in if wrong) in the window, whole epochs, over its
length.  Program counter: each epoch's per-step mispredict counts.
Checked against `bench/reference_retrain.py`: every epoch's class
sums, its step counts and its example count.  Control: the jitted
retrain step is replaced by the lower reference's step.
"""

from __future__ import annotations

import numpy as np

E2E = "fit_images_per_s"
STREAM_PROTOTYPES = 12
STREAM_IMAGES = 13


def class_conditioned(seed: int, n: int, h: int, c: int, amplitude: float):
    """(n, h) float32 integer intensities 0..255 and (n,) int32 labels,
    made on the device in one jitted call: a prototype of h uniform
    intensities per class, and each image clip(round(prototype[label] +
    noise), 0, 255) with noise uniform in [-amplitude, amplitude]."""
    import jax
    import jax.numpy as jnp

    from bench import inputs

    @jax.jit
    def make(kp, key):
        ky, kn = jax.random.split(key)
        proto = jax.random.randint(kp, (c, h), 0, 256, jnp.int32).astype(jnp.float32)
        y = jax.random.randint(ky, (n,), 0, c, jnp.int32)
        noise = jax.random.uniform(kn, (n, h), jnp.float32, -amplitude, amplitude)
        return jnp.clip(jnp.round(proto[y] + noise), 0.0, 255.0), y

    return make(inputs.jax_key(seed, STREAM_PROTOTYPES), inputs.jax_key(seed, STREAM_IMAGES))


def setup(run) -> None:
    import jax

    from bench import preflight
    from bench.harness import Refused
    from repro.core import HDCModel, hdc_model

    if not hasattr(HDCModel, "retrain_batches"):
        raise Refused("this program has no HDCModel.retrain_batches to drive")
    c, t = run.cfg, run.traffic
    x, y = class_conditioned(run.seed, t["n_train"], c["n_features"], c["n_classes"],
                             float(c["noise_amplitude"]))
    b = t["batch"]
    batches = [(x[i : i + b], y[i : i + b]) for i in range(0, t["n_train"], b)]
    jax.block_until_ready(batches)
    run.mark("data")
    model = HDCModel.create(run.hdc_config())
    jax.block_until_ready(model.codebooks)
    run.mark("build")
    preflight.check_backend(run, model.cfg.encoder)
    base = model.fit_batches(batches)
    jax.block_until_ready(base.class_sums)
    run.mark("fit")
    preflight.native(run, hdc_model._retrain_step_donated.lower(
        hdc_model._stateless(base), base.class_sums, base.pack(), *batches[0]),
        "retrain step")
    jax.block_until_ready(base.retrain_batches(batches))  # every shape
    run.mark("warm")
    run.state.update(x=x, y=y, batches=batches, base=base)


def window(run) -> None:
    import jax

    base, batches = run.state["base"], run.state["batches"]
    n = run.traffic["n_train"]
    epochs = []
    with run.measure() as w:
        while True:
            m, counts = base.retrain_batches(batches)
            jax.block_until_ready((m.class_sums, counts))
            epochs.append((m.class_sums, m.n_seen, counts))
            w.tick(len(epochs) * n)
            if w.elapsed() >= run.seconds:
                break
    run.state["epochs"] = epochs
    images = len(epochs) * n
    run.e2e[E2E] = images / w.seconds
    run.attempted = images
    run.counters["step_mispredicts"] = [np.asarray(cnt) for _, _, cnt in epochs]
    run.work.update(images=images, window_s=w.seconds, traced_images=w.traced_work)


def release(run) -> None:
    run.state.pop("base", None)
    run.state.pop("batches", None)


def check(run):
    import jax.numpy as jnp

    from bench import reference_retrain
    from bench.harness import Check

    s, n = run.state, run.traffic["n_train"]
    want, want_counts = reference_retrain.replay(run.cfg, run.seed, s["x"], s["y"],
                                                 run.traffic["batch"])
    sums_off = counts_off = count_off = 0
    for (sums, n_seen, _), counts in zip(s["epochs"], run.counters["step_mispredicts"]):
        sums_off += int(jnp.sum(sums != want))
        got = counts.astype(np.int64)
        if got.shape == want_counts.shape:
            counts_off += int(np.abs(got - want_counts).sum())
        else:  # a step left out or added: every count is off
            counts_off += int(got.sum() + want_counts.sum()) or 1
        hi, lo = (int(v) for v in np.asarray(n_seen))
        count_off += abs(((hi << 32) | lo) - n)
    run.note(f"reference: {int(want_counts.sum())} of {n} images mispredicted in one "
             f"epoch from the single-pass sums")
    return [
        Check("class_sum_entries_differing", float(sums_off), 0.0),
        Check("step_wrong_counts_differing", float(counts_off), 0.0),
        Check("examples_miscounted", float(count_off), 0.0),
    ]


def control(cfg: dict, traffic: dict, seed: int):
    from bench import reference_retrain
    from bench.control import Stand, lower_reference
    from repro.core import hdc_model

    low = lower_reference(cfg, seed)

    def step(stateless, sums, class_words, images, labels):
        return reference_retrain.step(low, sums, class_words, images, labels)

    real = hdc_model._retrain_step_donated
    return [(hdc_model, "_retrain_step_donated", Stand(real, step))]
