"""`correct` comes out false under the control and under each fault a
cell can have, driven through the whole run at a tiny size on the CPU
with the timed path broken underneath.

Control: the lower-precision reference in the timed entry's place
(`bench/control.py`, the same swap the chip runs use).  Faults: a fit
step that returns its state unchanged; a fit step that leaves out half
of its batch (counting it all); an answer altered where it is produced
(a search result, a label).  No cell exchanges anything between
chips (the pool cell's router hands each block to one replica), so the
exchange fault has no place to be planted.
"""

import pytest

from bench_tiny import TINY, root_with_unlisted


@pytest.mark.parametrize("workload", sorted(TINY))
def test_control_fails_the_cells_comparison(workload, run_tiny, tmp_path):
    out = run_tiny(workload, root=root_with_unlisted(tmp_path), control=True)
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


@pytest.mark.parametrize("workload", ["predict_dyn_http_poisson", "predict_dyn_pool4_blocks"])
def test_label_altered_where_it_is_produced(workload, run_tiny, monkeypatch, tmp_path):
    from repro.serving import ServingEngine

    real = ServingEngine.predict

    def altered(self, images):
        labels = real(self, images).copy()
        labels[0] = (labels[0] + 1) % self.model.cfg.n_classes
        return labels

    monkeypatch.setattr(ServingEngine, "predict", altered)
    out = run_tiny(workload, root=root_with_unlisted(tmp_path), child=False)
    assert not out["correct"]
    assert out["checks"]["labels_differing"]["value"] > 0


@pytest.mark.parametrize("workload", ["predict_dyn_http_poisson", "predict_dyn_pool4_blocks"])
def test_request_shed_where_it_is_admitted(workload, run_tiny, monkeypatch, tmp_path):
    import itertools

    from repro.serving import batcher

    real = batcher.MicroBatcher.submit_block
    calls = itertools.count()

    def shedding(self, images, **kw):
        n = next(calls)
        if n >= 8 and n % 7 == 0:  # past the warm-up's 8 requests, every 7th
            raise batcher.QueueFull("shed by the test")
        return real(self, images, **kw)

    monkeypatch.setattr(batcher.MicroBatcher, "submit_block", shedding)
    out = run_tiny(workload, root=root_with_unlisted(tmp_path), child=False)
    assert not out["correct"]
    assert out["checks"]["requests_not_ok"]["value"] > 0
    assert out["checks"]["labels_differing"]["value"] == 0
    assert out["checks"]["requests_never_answered"]["value"] == 0
