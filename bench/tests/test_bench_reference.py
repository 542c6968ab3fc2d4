"""The float32 reference against the program: its full forward, and the
engine's chunked prefill, page scatter and paged decode (greedy tokens
judged by their reference logit gap), on test-sized Qwen3-like (qk-norm,
tied embedding) and Mistral-NeMo-like (q width below the hidden size,
untied head) configurations."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import check, model, spec

BASE = {"family": "dense_decoder",
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
        "max_position_embeddings": 64, "policy": "kv16_attn_f32",
        "correct": {}}
QWEN = dict(BASE, hidden_size=64, qk_norm=True, tie_word_embeddings=True)
NEMO = dict(BASE, hidden_size=80, qk_norm=False, tie_word_embeddings=False,
            rms_norm_eps=1e-5)
CONFS = pytest.mark.parametrize("conf", [QWEN, NEMO], ids=["qwen", "nemo"])
ref = spec.load_reference(BASE)


def _program(conf, policy):
    from repro.models import build_model
    cfg = spec.load_family(conf).model_config(conf, "t").replace(
        dtype="float32", policy=policy, remat="none")
    m = build_model(cfg)
    return cfg, m, model.make_weights(m, 2**32 + 3)


@CONFS
def test_reference_matches_program_forward(conf):
    _, m, w = _program(conf, "fp32")
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, 40),
                       jnp.int32)
    prog = m.train_logits(w, {"tokens": toks[None]})[0][0]
    dims = tuple(sorted(ref.model_dims(conf).items()))
    want = ref.logits(w, toks, dims=dims)
    err = float(jnp.max(jnp.abs(prog - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-5, err


@CONFS
def test_reference_agrees_with_engine(conf):
    """Greedy tokens of the paged engine under an fp16 KV cache sit at the
    reference argmax up to fp16 rounding of the cache."""
    from repro.launch.engine import Engine, EngineConfig, Request
    _, m, w = _program(conf, "kv16_attn_f32")
    eng = Engine(m, w, EngineConfig(page_size=8, n_pages=40, max_batch=4,
                                    max_pages_per_req=8, token_budget=24,
                                    prefill_chunk=16))
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, n, dtype=np.int32),
                    max_new=g) for i, (n, g) in
            enumerate([(5, 9), (21, 12), (33, 7), (16, 16), (40, 20)])]
    for r in reqs:
        eng.submit(r)
    while eng.waiting or any(eng.slots):
        eng.step(0.0)

    class T:
        def __init__(self, r):
            self.req, self.n_prompt, self.done, self.rid = r, r.n_prompt, \
                True, r.rid

    res = check.compare(w, conf, [T(r) for r in reqs])
    assert res["tokens"] == 64
    assert res["max_gap"] < 2e-3, res


def test_grid_rounding():
    x = jnp.asarray([0.2, 0.3, 0.74, 0.76, 1.25, 2.5, 3.5, 5.0, 7.0, -2.9])
    got = ref.round_to_grid(x, "fp4_e2m1")
    assert got.tolist() == [0.0, 0.5, 0.5, 1.0, 1.0, 2.0, 4.0, 4.0, 6.0,
                            -3.0]
    y = jnp.asarray([1.0 + 1 / 16, 1.0 + 3 / 16, 300.0, 1000.0, 2.0 ** -9])
    assert ref.round_to_grid(y, "fp8_e4m3").tolist() == [
        1.0, 1.25, 288.0, 448.0, 2.0 ** -9]
    q = ref.fake_quant(jnp.asarray([[6.0, 1.0, -0.4]]), "fp4_e2m1", -1)
    assert q.tolist() == [[6.0, 1.0, -0.5]]
