"""The comparison that decides ``correct`` has to fail when the timed
path is broken underneath.  Each test skips the harness's look for a chip
(the CPU ``--check`` pass) and drives the rest of a run."""

import numpy as np

from bm_util import CELLS, ROOT, SERVE_CELL, check_cell

from benchmark import harness


def _train_model_class():
    return harness.load_module("models", "transformer_nmt", ROOT).TrainModel


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    cls = _train_model_class()
    real = cls.step

    def frozen(self, feed):
        before = {n: np.array(v, copy=True) for n, v in self.scope.items()}
        loss = real(self, feed)
        for n, v in before.items():
            self.scope.set_var(n, v)
        return loss
    monkeypatch.setattr(harness, "load_module", _loader({
        ("models", "transformer_nmt"): {"step": frozen}}))
    result = check_cell(CELLS[0])
    assert result["correct"] is False


def test_a_part_of_the_batch_left_out_is_not_correct(monkeypatch):
    cls = _train_model_class()
    real = cls.make_feed

    def half(self, batch):
        feed = real(self, batch)
        n = len(feed["src_word"]) // 2
        return {k: np.concatenate([v[:n], v[:n]]) for k, v in feed.items()}
    monkeypatch.setattr(harness, "load_module", _loader({
        ("models", "transformer_nmt"): {"make_feed": half}}))
    result = check_cell(CELLS[0])
    assert result["correct"] is False


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch,
                                                             serve_root):
    from paddle_tpu.serving import engine

    real = engine.GenerationEngine._complete

    def altered(self, slot, st):
        if len(st["generated"]) > 2:
            st["generated"][2] = (st["generated"][2] + 1) % \
                self.spec.vocab_size
        return real(self, slot, st)
    monkeypatch.setattr(engine.GenerationEngine, "_complete", altered)
    result = check_cell(SERVE_CELL, root=serve_root)
    assert result["correct"] is False


def test_the_unbroken_path_is_correct_with_the_same_patching(monkeypatch):
    monkeypatch.setattr(harness, "load_module", _loader({}))
    assert check_cell(CELLS[0])["correct"] is True


def _loader(patches):
    """``harness.load_module`` with methods of the built model replaced."""
    real = harness.load_module

    def load(kind, name, root=ROOT):
        mod = real(kind, name, root)
        for attr, fn in patches.get((kind, name), {}).items():
            setattr(mod.TrainModel, attr, fn)
        return mod
    return load
