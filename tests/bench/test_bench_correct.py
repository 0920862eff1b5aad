"""`correct` comes out false under the control and under each fault a
cell can have, driven through the whole run at a tiny size on the CPU
with the timed path broken underneath.

Control: the lower-precision reference in the timed entry's place
(`bench/control.py`, the same swap the chip runs use).  Faults: a fit
step that returns its state unchanged; a fit step that leaves out half
of its batch (counting it all); an answer altered where it is produced
(a search result, a label).  Every cell runs on one chip, so the
exchange fault has no place to be planted.
"""

import pytest

from bench_tiny import TINY, root_with_http

SEED = 2**40 + 3


@pytest.mark.parametrize("workload", sorted(TINY))
def test_control_fails_the_cells_comparison(workload, run_tiny, tmp_path):
    from bench import control

    root = root_with_http(tmp_path)
    with control.swapped(workload, SEED, root=root, overrides=TINY[workload]):
        out = run_tiny(workload, seed=SEED, root=root)
    assert not out["correct"]
    first = next(iter(out["checks"].values()))
    assert first["value"] > first["limit"], out["checks"]


def test_fit_step_returning_its_state_unchanged(run_tiny, monkeypatch):
    import jax

    from repro.core import hdc_model

    monkeypatch.setattr(hdc_model, "_partial_fit_donated",
                        jax.jit(lambda stateless, sums, ns, images, labels: (sums, ns)))
    out = run_tiny("fit_uhd_mnist60k")
    assert not out["correct"]
    assert out["checks"]["class_sum_entries_differing"]["value"] > 0


def test_fit_step_leaving_out_half_its_batch(run_tiny, monkeypatch):
    import jax

    from repro.core import hdc_model

    @jax.jit
    def half(stateless, sums, ns, images, labels):
        h = labels.shape[0] // 2
        model = stateless.replace(class_sums=sums, n_seen=ns)
        part = hdc_model._partial_fit(model, images[:h], labels[:h])
        return part.class_sums, hdc_model._nseen_add(ns, labels.shape[0])

    monkeypatch.setattr(hdc_model, "_partial_fit_donated", half)
    out = run_tiny("fit_uhd_mnist60k")
    assert not out["correct"]
    assert out["checks"]["examples_miscounted"]["value"] == 0  # the count is whole
    assert out["checks"]["class_sum_entries_differing"]["value"] > 0


def test_search_answer_altered(run_tiny, monkeypatch):
    import functools

    import jax

    from repro.core import hdc_model

    real = hdc_model.search_packed

    @functools.partial(jax.jit, static_argnames=("k", "impl"))
    def altered(*a, **kw):
        idx, dist = real(*a, **kw)
        return idx.at[-1, -1].add(1), dist

    monkeypatch.setattr(hdc_model, "search_packed", altered)
    out = run_tiny("search_dyn_store1m")
    assert not out["correct"]
    assert out["checks"]["topk_entries_differing"]["value"] > 0


def test_label_altered_where_it_is_produced(run_tiny, monkeypatch, tmp_path):
    from repro.serving import ServingEngine

    real = ServingEngine.predict

    def altered(self, images):
        labels = real(self, images).copy()
        labels[0] = (labels[0] + 1) % self.model.cfg.n_classes
        return labels

    monkeypatch.setattr(ServingEngine, "predict", altered)
    out = run_tiny("predict_dyn_http_poisson", root=root_with_http(tmp_path))
    assert not out["correct"]
    assert out["checks"]["labels_differing"]["value"] > 0
