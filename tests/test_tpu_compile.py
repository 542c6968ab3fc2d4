"""Ahead-of-time compiles of the serving hot path for a TPU v5e.

Each kernel of the main serving path is lowered with ``interpret=False``
and compiled by Mosaic for a v5e chip that is described, not attached,
at the widths it serves (qwen3-4b, granite-moe); each compiled program
must hold the kernel (``tpu_custom_call``).  Nothing runs: these guard
what the chip's compiler refuses — unaligned block shapes, ops Mosaic
cannot lower, programs that do not fit — at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import kvcache as KV
from repro.core.packing import pack_fp4_axis
from repro.kernels import dpa_grouped_matmul as gm
from repro.kernels import dpa_matmul as dm
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels import quantize as qk

QWEN = get_config("qwen3-4b")
GRANITE = get_config("granite-moe-1b-a400m")
FP8 = jnp.float8_e4m3fn


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    """The engine's tensor-parallel mesh over the four chips: (1, 4)."""
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture(autouse=True)
def _x32():
    """Serving compiles with 32-bit defaults.  A test file that ran
    earlier in the same worker may have imported the DPA golden model,
    which turns x64 on for the whole process; Mosaic cannot lower the
    64-bit ops that would put in the kernels."""
    with jax.enable_x64(False):
        yield


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_kernels(fn, *args) -> int:
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def _qwen_pool(cfg, B, pool, ps, n_pages):
    """Shapes of the engine's paged cache: per-layer pools stacked."""
    one = jax.eval_shape(lambda: dict(
        KV.init_paged_kv_cache(pool, ps, cfg.n_kv_heads, cfg.hd,
                               fmt="fp8_e4m3"),
        block_table=KV.make_block_table(B, n_pages)))
    return {"groups": {"p0": jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((cfg.n_layers,) + x.shape, x.dtype),
        one)}, "tail": []}


def test_paged_decode_fp8_compiles(one_chip):
    """The engine's decode attention at qwen3-4b widths: batch 8, 32
    pages of 16 per request, 8 KV heads of 128, over an fp8 page pool."""
    B, n_pages, pool, ps = 8, 32, 1024, 16
    KVH, hd, H = QWEN.n_kv_heads, QWEN.hd, QWEN.n_heads
    args = [_sds(one_chip, (B, 1, H, hd), jnp.bfloat16),
            _sds(one_chip, (pool, ps, KVH, hd), FP8),
            _sds(one_chip, (pool, ps, KVH, 1), jnp.float32),
            _sds(one_chip, (pool, ps, KVH, hd), FP8),
            _sds(one_chip, (pool, ps, KVH, 1), jnp.float32),
            _sds(one_chip, (B, n_pages), jnp.int32),
            _sds(one_chip, (B,), jnp.int32)]
    n = _compiled_kernels(
        lambda *a: fa.paged_decode_attention(
            *a, fmt="fp8_e4m3", fmt_kv="fp8_e4m3", interpret=False), *args)
    assert n == 1


@pytest.mark.parametrize("m", [8, 16])
def test_fused_fp8_matmul_compiles(one_chip, m):
    """The fused fp8 linear at qwen3-4b's widest shape (2560 x 9728), at
    the decode batch (8 rows) and a prefill chunk (16 rows)."""
    K, N = QWEN.d_model, QWEN.d_ff
    n = _compiled_kernels(
        lambda x, w, s: dm.dpa_matmul_fused(
            x, w, s, fmt_x="fp8_e4m3", fmt_w="fp8_e4m3", bm=m,
            interpret=False),
        _sds(one_chip, (m, K), jnp.bfloat16), _sds(one_chip, (K, N), FP8),
        _sds(one_chip, (1, N), jnp.float32))
    assert n == 1


def test_quantize_rows_fp8_compiles(one_chip):
    n = _compiled_kernels(
        lambda x: qk.quantize_rows(x, fmt="fp8_e4m3", interpret=False),
        _sds(one_chip, (128, QWEN.d_model), jnp.bfloat16))
    assert n == 1


def test_grouped_fused_fp8_matmul_compiles(one_chip):
    """granite-moe's expert contraction at decode: 32 experts, batch 8
    at capacity 1, d 1024 -> d_ff 512."""
    E, K, N, M = GRANITE.n_experts, GRANITE.d_model, GRANITE.d_ff, 8
    n = _compiled_kernels(
        lambda x, w, s: gm.dpa_grouped_matmul_fused(
            x, w, s, fmt_x="fp8_e4m3", fmt_w="fp8_e4m3", bm=M,
            interpret=False),
        _sds(one_chip, (E, M, K), jnp.bfloat16),
        _sds(one_chip, (E, K, N), FP8), _sds(one_chip, (E, 1, N), jnp.float32))
    assert n == 1


def test_decode_step_compiles(one_chip, monkeypatch):
    """The model's whole decode step over the paged pool, as the engine
    jits it, at qwen3-4b width (depth cut to 2 layers) under
    w8a8_kv8_attn8: the seven fused fp8 linears of each layer and the
    paged fp8 decode are Mosaic kernels in the program."""
    from repro.models import build_model, serving_init

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = QWEN.replace(n_layers=2, policy="w8a8_kv8_attn8")
    model = build_model(cfg)

    def place(tree):
        return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype),
                            tree)

    params = place(jax.eval_shape(serving_init(model),
                                  jax.random.PRNGKey(0)))
    B = 8
    caches = place(_qwen_pool(cfg, B, 64, 16, 32))
    batch = place({"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                   "index": jax.ShapeDtypeStruct((B,), jnp.int32)})
    assert _compiled_kernels(model.decode_step, params, batch, caches) == 8


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_tp4_steps_compile(mesh4, monkeypatch, step):
    """The engine's steps on its (1, 4) tensor-parallel mesh, qwen3-4b
    width cut to 2 layers: weights replicated, the page pool sharded on
    "model".  XLA cannot partition a Mosaic kernel, so under the active
    TP mesh each device must run its own copy (`kernels.ops._run`) —
    the decode step's seven fused linears and the paged decode inside
    the sharded route, the prefill chunk's seven linears."""
    from repro.distributed import tp as TP
    from repro.distributed.sharding import cache_spec
    from repro.models import build_model, serving_init

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = QWEN.replace(n_layers=2, policy="w8a8_kv8_attn8")
    model = build_model(cfg)
    rep = NamedSharding(mesh4, P())

    def place(tree, shardings=None):
        shardings = shardings or jax.tree.map(lambda _: rep, tree)
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, shardings)

    params = place(jax.eval_shape(serving_init(model),
                                  jax.random.PRNGKey(0)))
    if step == "decode":
        B = 8
        pool = _qwen_pool(cfg, B, 64, 16, 32)
        caches = place(pool, cache_spec(pool, mesh4))
        batch = place({"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                       "index": jax.ShapeDtypeStruct((B,), jnp.int32)})
    else:
        caches = place(jax.eval_shape(lambda: model.init_caches(1, 512)))
        batch = place({"tokens": jax.ShapeDtypeStruct((1, 16), jnp.int32),
                       "index": jax.ShapeDtypeStruct((), jnp.int32)})
    with TP.activate(mesh4):
        n = _compiled_kernels(lambda *a: model.decode_step(*a), params,
                              batch, caches)
    assert n == (8 if step == "decode" else 7)


@pytest.mark.parametrize("kernel", ["paged_decode_attention",
                                    "dpa_matmul_fused",
                                    "dpa_matmul_prequant",
                                    "dpa_grouped_matmul_fused",
                                    "quantize_pack_rows"])
def test_packed_fp4_refuses_compiled_path(kernel):
    """Packed-fp4 operands do not lower through Mosaic; with
    interpret=False the kernels raise, naming themselves, instead of
    running interpreted or swapping to an XLA route."""
    codes = jnp.asarray(np.arange(16 * 64) % 16, jnp.uint8).reshape(16, 64)
    packed = pack_fp4_axis(codes, 0)
    sw = jnp.ones((1, 64), jnp.float32)
    x = jnp.ones((16, 16), jnp.float32)
    calls = {
        "paged_decode_attention": lambda: fa.paged_decode_attention(
            jnp.ones((1, 1, 2, 16)), jnp.zeros((4, 8, 1, 8), jnp.uint8),
            jnp.ones((4, 8, 1, 1)), jnp.zeros((4, 8, 1, 8), jnp.uint8),
            jnp.ones((4, 8, 1, 1)), jnp.zeros((1, 2), jnp.int32),
            jnp.zeros((1,), jnp.int32), fmt="fp8_e4m3", fmt_kv="fp4_e2m1",
            kv_packed=True, interpret=False),
        "dpa_matmul_fused": lambda: dm.dpa_matmul_fused(
            x, packed, sw, fmt_x="fp8_e4m3", fmt_w="fp4_e2m1", bm=16, bk=16,
            bn=64, pack_w=True, interpret=False),
        "dpa_matmul_prequant": lambda: dm.dpa_matmul_prequant(
            codes, packed, jnp.ones((16, 1)), sw, fmt_x="fp4_e2m1",
            fmt_w="fp4_e2m1", bm=16, bk=16, bn=64, pack_w=True,
            interpret=False),
        "dpa_grouped_matmul_fused": lambda: gm.dpa_grouped_matmul_fused(
            x[None], packed[None], sw[None], fmt_x="fp8_e4m3",
            fmt_w="fp4_e2m1", bm=16, bk=16, bn=64, pack_w=True,
            interpret=False),
        "quantize_pack_rows": lambda: qk.quantize_pack_rows(
            x, bm=16, interpret=False),
    }
    with pytest.raises(NotImplementedError, match=kernel):
        calls[kernel]()
