"""A whole run with the timed path broken underneath reads not correct;
the same run unbroken reads correct. The faults an inference cell can
have: a token (LM) or an answer (CNN) altered where it is produced, and
a serving step that returns its state (the KV cache) unchanged."""
import jax
import jax.numpy as jnp
import pytest

from runs import run_tiny


@pytest.mark.parametrize("kind", ["lm", "cnn"])
def test_sound_run_is_correct(kind, monkeypatch):
    _, line = run_tiny(kind, monkeypatch)
    assert line["correct"], line["checked"]
    assert list(line)[-1] == "checked"
    assert line["attempted"] > 0 and line["failed"] == 0


def _alter_sampled_token(monkeypatch):
    import repro.serving.engine as engine

    make = engine.make_sampler

    def broken(temperature):
        sample = make(temperature)

        def alter(logits, key):
            toks, key = sample(logits, key)
            return (toks + 1) % logits.shape[-1], key
        return alter
    monkeypatch.setattr(engine, "make_sampler", broken)


def _alter_logits(monkeypatch):
    from repro.models.conv import SparseCNN

    apply = SparseCNN.apply

    def broken(self, params, x, **kw):
        y = apply(self, params, x, **kw)
        return y.at[0].set(jnp.roll(y[0], 1))
    monkeypatch.setattr(SparseCNN, "apply", broken)


def _cache_left_unchanged(monkeypatch):
    import repro.serving.engine as engine

    monkeypatch.setattr(engine, "merge_cache_slots",
                        lambda new, old, keep: old)


@pytest.mark.parametrize("kind,fault", [("lm", _alter_sampled_token),
                                        ("lm", _cache_left_unchanged),
                                        ("cnn", _alter_logits)])
def test_altered_output_is_caught(kind, fault, monkeypatch):
    fault(monkeypatch)
    jax.clear_caches()
    _, line = run_tiny(kind, monkeypatch)
    assert not line["correct"], line["checked"]
