"""The engine's staging -> pages scatter: one jit'd program per engine
whatever the prompt length, which donates the page pools it writes in
place and leaves the served tokens identical to the static-batch
reference (the parity fixture of `tests/test_engine.py`)."""
import numpy as np

import jax.numpy as jnp

from repro.core import kvcache as KV
from repro.launch.engine import Engine, Request
from repro.launch.serve import generate
from test_engine import ECFG, model_and_params  # noqa: F401  (fixture)

# prompt lengths across the residues mod the page size (8): one row,
# whole pages, a mid-page tail, and a prompt that fills S_max (32)
LENS = [(8, 3), (13, 4), (1, 5), (16, 2), (23, 6), (30, 2)]


def _requests(vocab):
    rng = np.random.default_rng(11)
    return [Request(rid=i, max_new=g,
                    prompt=rng.integers(0, vocab, s0).astype(np.int32))
            for i, (s0, g) in enumerate(LENS)]


def test_scatter_compiles_once_donates_pools_and_keeps_tokens(
        model_and_params):  # noqa: F811
    model, params = model_and_params
    engine = Engine(model, params, ECFG)
    scatter, donated = engine._scatter_staging_to_pages, []

    def watched(req):
        pools = [c[k] for c in [engine.caches["groups"]["p0"],
                                *engine.caches["tail"]]
                 for k in KV.QUANT_KEYS]
        staging = [engine._staging["groups"]["p0"][k] for k in KV.QUANT_KEYS]
        scatter(req)
        donated.append(all(p.is_deleted() for p in pools)
                       and not any(s.is_deleted() for s in staging))

    engine._scatter_staging_to_pages = watched
    engine.run(_requests(model.cfg.vocab_size))
    assert donated == [True] * len(LENS)
    assert engine._scatter_fn._cache_size() == 1
    for req in _requests(model.cfg.vocab_size):
        out = generate(model, params, jnp.asarray(req.prompt[None]),
                       req.max_new, ECFG.s_max)
        got = [r for r in engine.finished if r.rid == req.rid][0]
        assert np.array_equal(got.tokens(), np.asarray(out)[0]), req.rid
