"""`correct` comes out false when the served path is broken underneath:
planted faults, one per way the serving cell can go wrong on one chip."""
import pytest

import jax

from bench.tests.tiny import run_tiny


def state_unchanged(engine):
    """The decode step returns its cache unchanged: no K/V written."""
    step = jax.jit(engine._make_decode_step())

    def f(params, batch, caches, rids):
        return step(params, batch, caches, rids)[0], caches

    engine._decode_fn = f


def half_batch(engine):
    """Half of the batch left out: every other slot gets its input token
    back instead of a computed one."""
    orig = engine._decode_fn

    def f(params, batch, caches, rids):
        tok, caches = orig(params, batch, caches, rids)
        return tok.at[0::2].set(batch["tokens"][0::2, 0]), caches

    engine._decode_fn = f


def token_altered(engine):
    """Tokens altered where they are produced: each next id plus one."""
    orig, vocab = engine._decode_fn, engine.cfg.vocab_size

    def f(params, batch, caches, rids):
        tok, caches = orig(params, batch, caches, rids)
        return (tok + 1) % vocab, caches

    engine._decode_fn = f


def scatter_skipped(engine):
    """A finished prompt's rows never reach its pages."""
    engine._scatter_staging_to_pages = lambda req: None


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered, scatter_skipped],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(tmp_path, fault):
    out = run_tiny(tmp_path, fault=fault)
    assert out["correct"] is False
    c = out["checks"]
    assert c["mean_logit_gap"]["value"] > c["mean_logit_gap"]["limit"]
