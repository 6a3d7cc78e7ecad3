"""Compile-only checks for the TPU: every Pallas kernel, and one whole
packed decode layer, lowered and compiled for a described (unattached)
TPU v5e at the published widths of the two MoE models this system
serves.  Nothing runs on a chip; a test fails where the chip's compiler
would refuse the program (tiling, casts Mosaic lacks, VMEM, memory).

The topology is described inside a module-scoped fixture, never while
this module is imported: only one process may load the TPU library, and
the test workers each import every test file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import dequant_matmul as DM
from repro.kernels import flash_attention as FA
from repro.kernels import ragged_attention as RA
from repro.quant.hqq import PAPER_SCHEMES

HBM_BYTES = 16 * 1024 ** 3  # one v5e chip
# (name, expert (d_model, d_ff)) and (name, (heads, kv heads, head_dim))
EXPERT_SHAPES = [("granite", (1024, 512)), ("mixtral", (4096, 14336))]
HEAD_SHAPES = [("granite", (16, 8, 64)), ("mixtral", (32, 8, 128))]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _packed_args(S, K, N, bits, lead=()):
    g = PAPER_SCHEMES[bits]["group_size"]
    G = K // g
    return ((S(lead + (G, g * bits // 8, N), jnp.uint8),
             S(lead + (G, 1, N), jnp.float32),
             S(lead + (G, 1, N), jnp.float32)), g)


def _specs(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)


@pytest.mark.parametrize("bits", [2, 4])
@pytest.mark.parametrize("model,shape", EXPERT_SHAPES)
@pytest.mark.parametrize("kernel", ["single", "batched", "slots"])
def test_dequant_matmul_kernels_compile(one_chip, kernel, model, shape,
                                        bits):
    """Decode-shaped (8 rows) dequant-matmul over an expert's gate/up
    matrix (d_model x d_ff) at each model's widths."""
    S = _specs(one_chip)
    K, N = shape
    if kernel == "single":
        (p, s, z), g = _packed_args(S, K, N, bits)
        c = _compile(lambda x, p, s, z: DM.dequant_matmul_pallas(
            x, p, s, z, bits=bits, group_size=g, bm=8, interpret=False),
            S((8, K), jnp.bfloat16), p, s, z)
    elif kernel == "batched":
        (p, s, z), g = _packed_args(S, K, N, bits, (4,))
        c = _compile(lambda x, p, s, z: DM.dequant_matmul_batched_pallas(
            x, p, s, z, bits=bits, group_size=g, bm=8, interpret=False),
            S((4, 8, K), jnp.bfloat16), p, s, z)
    else:
        (p, s, z), g = _packed_args(S, K, N, bits, (8,))
        c = _compile(lambda x, p, s, z, sl: DM.dequant_matmul_slots_pallas(
            x, p, s, z, sl, bits=bits, group_size=g, bm=8,
            interpret=False),
            S((4, 8, K), jnp.bfloat16), p, s, z, S((4,), jnp.int32))
    _assert_kernel(c)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("model,heads", HEAD_SHAPES)
def test_ragged_attention_kernel_compiles(one_chip, model, heads, dtype):
    """Paged decode attention (one query per row, 16-position pages)."""
    S = _specs(one_chip)
    H, Hkv, hd = heads
    B, P, ps, W = 4, 64, 16, 32
    c = _compile(
        lambda q, kp, vp, ppos, qpos, wr, wp, wf: RA.ragged_attention_pallas(
            q, kp, vp, ppos, qpos, wr, wp, wf, interpret=False),
        S((B, 1, H, hd), dtype), S((P, ps, Hkv, hd), dtype),
        S((P, ps, Hkv, hd), dtype), S((P, ps), jnp.int32),
        S((B, 1), jnp.int32), S((W,), jnp.int32), S((W,), jnp.int32),
        S((W, 3), jnp.int32))
    _assert_kernel(c)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("model,heads", HEAD_SHAPES)
def test_flash_attention_kernel_compiles(one_chip, model, heads, dtype):
    """Causal GQA prefill attention, two sequences of 256 positions."""
    S = _specs(one_chip)
    H, Hkv, hd = heads
    c = _compile(lambda q, k, v: FA.flash_attention_pallas(
        q, k, v, causal=True, interpret=False),
        S((2 * H, 256, hd), dtype), S((2 * Hkv, 256, hd), dtype),
        S((2 * Hkv, 256, hd), dtype))
    _assert_kernel(c)


def test_granite_packed_decode_layer_compiles_and_fits(one_chip):
    """One whole packed decode layer of granite-moe-1b-a400m (mixer over
    paged KV + MoE served from the full 24-layer 3-bit expert store and
    its buffer pool) for a 4-slot decode step, as one program; its
    arguments and temporaries fit one chip's HBM.  As the executor's
    layer programs do, it reads its layer out of the 24-layer stacked KV
    pool at a traced index and writes it back into the donated stack,
    which the compiler updates in place (aliased to the output)."""
    from repro.core import expert_pool as EP
    from repro.models import layers as L
    from repro.models import transformer as T

    cfg = get_config("granite-moe-1b-a400m")
    spec = cfg.offload
    B, pages, page, max_pages = 4, 64, 16, 16
    params = jax.eval_shape(lambda: T.init_model(jax.random.key(0), cfg))
    store = jax.eval_shape(lambda p: EP.build_store(p, cfg, spec), params)
    pstate = jax.eval_shape(lambda s: EP.init_pool_state(s, spec), store)
    lp = jax.eval_shape(lambda p: T.layer_params(p, cfg, 0), params)
    stack = jax.eval_shape(lambda: T.init_decode_state(
        cfg, B, 0, kv_pages=pages, kv_page=page,
        kv_max_pages=max_pages)["stack"][0])
    kind = cfg.block_pattern[0]

    def layer(p, x, stack, per, pos, pg, act, store, ps, lm):
        x, st, h2 = T.decode_block_packed_mixer(
            p, cfg, kind, x, T.stack_layer(stack, per), pos, pages=pg,
            active=act)
        x, ps, _ = T.decode_block_packed_moe(p, cfg, x, h2, store, ps, lm,
                                             active=act)
        return x, T.stack_set_layer(stack, per, st), ps

    sds = jax.ShapeDtypeStruct
    args = jax.tree.map(
        lambda a: sds(a.shape, a.dtype, sharding=one_chip),
        (lp, sds((B, 1, cfg.d_model), jnp.bfloat16), stack,
         sds((), jnp.int32), sds((B,), jnp.int32),
         sds((B, max_pages), jnp.int32), sds((B,), jnp.bool_), store,
         pstate, sds((), jnp.int32)))
    c = jax.jit(layer, donate_argnums=(2, 8)).lower(*args).compile()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    assert nbytes(stack) > 10 * 1024 ** 2  # 24 layers of K and V pages
    # both donated trees, the KV stack and the pool, are updated in place
    assert mem.alias_size_in_bytes >= nbytes(stack) + nbytes(pstate)


def test_mixtral_store_slab_and_prompt_chunk_fit(one_chip):
    """Mixtral-8x7B at its widths and four layers (``mixtral-offload.l4``):
    a store slab is one 4096 x 14336 expert matrix, and HQQ's program on
    it needs no device memory beyond its float32 input and its codes;
    the prompt chunk's MoE program at the served 2 tokens fits beside
    the whole store and the pool, while 4 tokens double its float32
    temporaries (why the configuration serves chunks of 2)."""
    from functools import partial

    from repro.core import expert_pool as EP
    from repro.core.offload_engine import quantize_for_offload
    from repro.models import moe as M
    from repro.models import transformer as T
    from repro.quant import hqq

    S = _specs(one_chip)
    for K, N in ((4096, 14336), (14336, 4096)):
        assert EP.SLAB_F32_BYTES // (K * N * 4) == 1
        c = _compile(partial(hqq._quantize_groups.__wrapped__, bits=2,
                             group_size=16, iters=20),
                     S((1, K // 16, 16, N), jnp.float32))
        mem = c.memory_analysis()
        assert mem.temp_size_in_bytes <= K * N  # codes and scales at most

    cfg = get_config("mixtral-offload").replace(
        n_layers=4, block_pattern=("attn+moe",), sliding_window=None)
    params = jax.eval_shape(lambda: T.init_model(jax.random.key(0), cfg))
    ex, store = jax.eval_shape(lambda p: (lambda r: (r[0], r[2]))(
        quantize_for_offload(p, cfg, cfg.offload, pack_experts=True)),
        params)
    pstate = jax.eval_shape(lambda s: EP.init_pool_state(s, cfg.offload),
                            store)
    lp = jax.eval_shape(lambda p: T.layer_params(p, cfg, 0), ex)

    def chunk_moe(p, x, h2, store, lm):  # the executor's packed_chunk_moe
        B, C, D = h2.shape
        y, _ = M.moe_apply_packed_stream(p["moe"], cfg, h2.reshape(B * C, D),
                                         store, lm, fused=True)
        return x + y.reshape(B, C, D)

    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))
    temps = {}
    for C in (2, 4):
        x = S((1, C, cfg.d_model), jnp.bfloat16)
        args = jax.tree.map(lambda a: S(a.shape, a.dtype),
                            (lp, x, x, store, S((), jnp.int32)))
        mem = _compile(chunk_moe, *args).memory_analysis()
        temps[C] = mem.temp_size_in_bytes
        if C == 2:
            assert mem.argument_size_in_bytes + temps[C] + nbytes(pstate) \
                < HBM_BYTES - 2 * 1024 ** 3
    assert temps[4] > 1.9 * temps[2]
