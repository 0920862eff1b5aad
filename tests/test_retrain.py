"""Mispredict-driven retraining, `HDCModel.retrain_batches` (DESIGN.md §15).

Checked against a plain numpy replay of the semantics: per batch, in
order, every image is encoded, labelled by packed Hamming against the
step's starting sums (lowest class on ties), and each mispredicted one
is added to its true class and subtracted from the predicted one.
Covers both uHD encoders over chained epochs with a ragged last batch,
a batch the model already gets right, a tie, the mini-batch rule that
every prediction of a step uses the step's starting sums, the
once-per-call label check, the step counts, the exact int8-digit
update, and the training CLI's ``--retrain-epochs``.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import HDCConfig, HDCModel, encoding, sobol
from repro.core import hdc_model as hm

H, C, D, B, LEVELS = 64, 4, 256, 32, 16


def _cfg(encoder="uhd_dynamic", **kw):
    return HDCConfig(n_features=H, n_classes=C, d=D, levels=LEVELS, encoder=encoder, **kw)


def _data(seed, n):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 256, (n, H)).astype(np.float32)
    y = rng.integers(0, C, n).astype(np.int32)
    return x, y


def _batches(x, y, b=B):
    return [(x[i : i + b], y[i : i + b]) for i in range(0, len(x), b)]


# ---------------------------------------------------------------------------
# the plain numpy replay
# ---------------------------------------------------------------------------


def _encode_np(cfg, x):
    """(n, H) integer intensities -> (n, D) int64 uHD hypervectors."""
    table = sobol.sobol_table_for_features(cfg.n_features, cfg.d, cfg.levels,
                                           seed=cfg.seed, skip=cfg.sobol_skip)
    xq = (x.astype(np.int64) * cfg.levels) // 255  # exact for integer intensities
    return 2 * (xq[:, :, None] >= table[None]).sum(1, dtype=np.int64) - cfg.n_features


def _bits_np(v):
    """Row-centred sign bits, in exact integers: D * v - sum_D v >= 0."""
    return v.shape[-1] * v - v.sum(-1, keepdims=True) >= 0


def _replay(cfg, sums, batches, epochs):
    sums = np.asarray(sums, np.int64).copy()
    wrong = []
    for _ in range(epochs):
        for x, y in batches:
            hv = _encode_np(cfg, x)
            dist = (_bits_np(hv)[:, None, :] != _bits_np(sums)[None, :, :]).sum(-1)
            pred = dist.argmin(1)  # first minimum: lowest class wins ties
            bad = pred != y
            np.add.at(sums, y[bad], hv[bad])
            np.subtract.at(sums, pred[bad], hv[bad])
            wrong.append(int(bad.sum()))
    return sums, wrong


# ---------------------------------------------------------------------------
# retrain_batches against the replay
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("encoder, backend, seed", [
    ("uhd", "auto", 0), ("uhd", "auto", 1), ("uhd", "auto", 2),
    ("uhd_dynamic", "auto", 0), ("uhd_dynamic", "auto", 1), ("uhd_dynamic", "auto", 2),
    ("uhd_dynamic", "pallas", 0),
])
def test_retrain_equals_the_numpy_replay(encoder, backend, seed):
    cfg = _cfg(encoder, backend=backend, seed=seed)
    x, y = _data(seed, 3 * B + 5)  # a ragged last batch of 5
    batches = _batches(x, y)
    base = HDCModel.create(cfg).fit_batches(batches)
    before = np.asarray(base.class_sums).copy()

    out, counts = base.retrain_batches(batches, epochs=2)

    want, wrong = _replay(cfg, before, batches, epochs=2)
    assert out.class_sums.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out.class_sums), want)
    assert counts.shape == (2 * len(batches),) and counts.dtype == jnp.int32
    assert np.asarray(counts).tolist() == wrong
    assert sum(wrong) > 0  # the replay really folded something
    assert out.n_examples == base.n_examples == len(x)
    np.testing.assert_array_equal(np.asarray(base.class_sums), before)  # not donated


def test_two_chained_calls_equal_one_call_of_two_epochs():
    cfg = _cfg()
    x, y = _data(7, 2 * B)
    batches = _batches(x, y)
    base = HDCModel.create(cfg).fit_batches(batches)
    one, c1 = base.retrain_batches(batches)
    two, c2 = one.retrain_batches(iter(batches))  # an iterator is listed first
    both, c12 = base.retrain_batches(batches, epochs=2)
    np.testing.assert_array_equal(np.asarray(two.class_sums), np.asarray(both.class_sums))
    assert np.concatenate([c1, c2]).tolist() == np.asarray(c12).tolist()


def test_a_batch_the_model_gets_right_leaves_the_sums_bit_identical():
    cfg = _cfg()
    x, y = _data(3, 4 * B)
    base = HDCModel.create(cfg).fit_batches(_batches(x, y))
    served = np.asarray(hm.predict_packed(base, jnp.asarray(x[:B]), base.pack()))
    out, counts = base.retrain_batches([(x[:B], served)])
    assert np.asarray(counts).tolist() == [0]
    np.testing.assert_array_equal(np.asarray(out.class_sums), np.asarray(base.class_sums))


def test_the_first_step_counts_the_labels_serving_would_get_wrong():
    cfg = _cfg()
    x, y = _data(4, 3 * B)
    batches = _batches(x, y)
    base = HDCModel.create(cfg).fit_batches(batches)
    served = np.asarray(hm.predict_packed(base, jnp.asarray(x[:B]), base.pack()))
    _, counts = base.retrain_batches(batches)
    assert int(counts[0]) == int((served != y[:B]).sum())


def test_a_tie_goes_to_the_lowest_class():
    """Equal sums pack to equal words: every image ties between the
    classes, is labelled 0, and counts as wrong unless its label is 0."""
    cfg = _cfg()
    x, _ = _data(5, 8)
    y = np.array([0, 1, 2, 3, 3, 2, 1, 0], np.int32)
    base = HDCModel.create(cfg)  # all-zero sums: every class word is the same
    out, counts = base.retrain_batches([(x, y)])
    hv = _encode_np(cfg, x)
    want = np.zeros((C, D), np.int64)
    for i in np.flatnonzero(y != 0):
        want[y[i]] += hv[i]
        want[0] -= hv[i]
    assert np.asarray(counts).tolist() == [6]
    np.testing.assert_array_equal(np.asarray(out.class_sums), want)


def test_every_prediction_of_a_step_uses_the_steps_starting_sums():
    """The same image twice in one batch: both copies are labelled from
    the starting sums (0, by the tie) and both are folded; per-image
    retraining would have labelled the second copy 1 and left it."""
    cfg = _cfg()
    x, _ = _data(6, 1)
    out, counts = HDCModel.create(cfg).retrain_batches(
        [(np.repeat(x, 2, 0), np.array([1, 1], np.int32))])
    hv = _encode_np(cfg, x)[0]
    assert np.asarray(counts).tolist() == [2]
    np.testing.assert_array_equal(np.asarray(out.class_sums[1]), 2 * hv)
    np.testing.assert_array_equal(np.asarray(out.class_sums[0]), -2 * hv)
    # the next step starts from those sums: the copy is now labelled 1
    _, counts = out.retrain_batches([(x, np.array([1], np.int32))])
    assert np.asarray(counts).tolist() == [0]


def test_out_of_range_labels_raise_once_per_call_before_any_step(monkeypatch):
    cfg = _cfg()
    x, y = _data(8, 3 * B)
    base = HDCModel.create(cfg).fit_batches(_batches(x, y))
    steps, reads = [], []
    real_step, real_range = hm._retrain_step_donated, encoding._label_range

    def step(*a):
        steps.append(1)
        return real_step(*a)

    def label_range(batches):
        reads.append(len(batches))
        return real_range(batches)

    monkeypatch.setattr(hm, "_retrain_step_donated", step)
    monkeypatch.setattr(encoding, "_label_range", label_range)
    bad = y.copy()
    bad[-1] = C
    with pytest.raises(ValueError, match=r"labels must be in \[0, 4\).*\[4\]"):
        base.retrain_batches(_batches(x, bad), epochs=2)
    assert steps == [] and reads == [3]
    bad[-1] = -1
    with pytest.raises(ValueError, match=r"out-of-range values \[-1\]"):
        base.retrain_batches(_batches(x, bad))
    reads.clear()
    base.retrain_batches(_batches(x, y), epochs=2)
    assert reads == [3] and len(steps) == 6  # one read for two epochs of three steps


def test_epochs_must_be_positive():
    base = HDCModel.create(_cfg())
    with pytest.raises(ValueError, match="epochs"):
        base.retrain_batches([], epochs=0)


def test_no_batches_leave_the_model_as_it_was():
    base = HDCModel.create(_cfg())
    out, counts = base.retrain_batches([])
    assert counts.shape == (0,)
    np.testing.assert_array_equal(np.asarray(out.class_sums), np.asarray(base.class_sums))


# ---------------------------------------------------------------------------
# the exact int8-digit update
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("max_abs, b", [(127, 64), (784, 2048), (20_000, 256)])
def test_retrain_update_is_exact(max_abs, b):
    rng = np.random.default_rng(max_abs)
    hv = rng.integers(-max_abs, max_abs + 1, (b, 96))
    hv[0] = -max_abs
    hv[1] = max_abs
    y = rng.integers(0, 10, b)
    pred = np.where(rng.random(b) < 0.3, rng.integers(0, 10, b), y)
    got = encoding.retrain_update(jnp.asarray(hv, jnp.int32), jnp.asarray(y, jnp.int32),
                                  jnp.asarray(pred, jnp.int32), 10, max_abs)
    want = np.zeros((10, 96), np.int64)
    np.add.at(want, y, hv)
    np.subtract.at(want, pred, hv)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# the training CLI
# ---------------------------------------------------------------------------


def test_cli_prints_each_retraining_epoch(capsys):
    from repro.launch import train_hdc

    rc = train_hdc.main(["--dataset", "synth_mnist", "--d", "256", "--n-train", "96",
                         "--n-test", "32", "--batch-size", "40", "--encoder", "uhd_dynamic",
                         "--retrain-epochs", "2"])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.startswith("retrain")]
    assert len(lines) == 2
    assert lines[0].startswith("retrain epoch 1/2: accuracy ")
    assert "mispredicted" in lines[1] and "of 96" in lines[1]
