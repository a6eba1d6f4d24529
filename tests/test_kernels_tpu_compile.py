"""Every Pallas entry point of the main path, compiled by the chip's own
compiler at the widths chip_smoke.py runs (GPT-2-small: 12 heads, d_head
64; BERT-base s384), for a DESCRIBED v5e — no chip attached, nothing
runs. Interpret mode cannot see what Mosaic refuses (block shapes that
break the (8, 128) tiling rule, primitives with no TPU lowering), and
this sandbox cannot run the kernels; this file is where such a refusal
shows before chip time is spent. The kernel functions are called
themselves with ``interpret=False``: code that asks which backend it
lowers for sees the CPU here.
"""

import importlib
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest

fa = importlib.import_module("paddle_tpu.kernels.flash_attention")

HEADS, D_HEAD, SLOTS, BLOCK = 12, 64, 8, 16
BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def chip():
    """Sharding on device 0 of a described v5e 2x2; the persistent
    compilation cache is off around the module (an entry written for a
    described device cannot be read back without the chip, and warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or it cannot describe a v5e
        pytest.skip("cannot describe a v5e topology: %r" % (e,))
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _train(batch, seq, causal, key_bias=False, bias=None, dropout=0.0,
           lse=False):
    """fwd+bwd of flash_attention[_lse] -> (fn, arg shapes, custom calls)."""
    shapes = [((batch, HEADS, seq, D_HEAD), BF16)] * 3
    names = []
    if key_bias:
        shapes.append(((batch, seq), F32))
        names.append("key_bias")
    if bias is not None:
        shapes.append((bias, F32))
        names.append("bias")
    if dropout:
        shapes.append(((), jnp.int32))
        names.append("dropout_seed")

    def loss(q, k, v, *rest):
        kw = dict(zip(names, rest), causal=causal, dropout_rate=dropout,
                  interpret=False)
        if lse:
            out, stat = fa.flash_attention_lse(q, k, v, **kw)
            return out.astype(F32).sum() + stat.sum()
        return fa.flash_attention(q, k, v, **kw).astype(F32).sum()

    # forward, dq and dkv kernels
    return jax.grad(loss, argnums=(0, 1, 2)), shapes, 3


def _decode(seq, dtype):
    def fn(q, k, v, kb):
        return fa.flash_decode_attention(q, k, v, key_bias=kb,
                                         interpret=False)

    cache = ((SLOTS, HEADS, seq, D_HEAD), dtype)
    return fn, [((SLOTS, HEADS, 1, D_HEAD), dtype), cache, cache,
                ((SLOTS, seq), F32)], 1


def _paged(max_blocks, dtype, slots=SLOTS):
    def fn(q, kp, vp, tables, kb, lengths):
        return fa.flash_decode_paged_attention(q, kp, vp, tables,
                                               key_bias=kb, lengths=lengths,
                                               interpret=False)

    pool = ((slots * max_blocks + 1, HEADS, BLOCK, D_HEAD), dtype)
    return fn, [((slots, HEADS, 1, D_HEAD), dtype), pool, pool,
                ((slots, max_blocks), jnp.int32),
                ((slots, max_blocks * BLOCK), F32),
                ((slots,), jnp.int32)], 1


CASES = {
    "flash_causal_b8_s1024": lambda: _train(8, 1024, True),
    "flash_causal_b4_s4096": lambda: _train(4, 4096, True),
    "flash_bert_b24_s384_keybias": lambda: _train(24, 384, False,
                                                  key_bias=True),
    "flash_general_bias_per_head": lambda: _train(
        4, 512, False, bias=(1, HEADS, 512, 512)),
    "flash_general_bias_ss": lambda: _train(4, 512, True, bias=(512, 512)),
    "flash_dropout_b8_s1024": lambda: _train(8, 1024, True, dropout=0.1),
    "flash_lse_b8_s1024": lambda: _train(8, 1024, True, lse=True),
    # the engines' KV pools are float32 today (models/gpt.py); bf16 is the
    # dtype queue 1 moves them to
    "decode_s1024_f32": lambda: _decode(1024, F32),
    "decode_s4096_f32": lambda: _decode(4096, F32),
    "decode_s1024_bf16": lambda: _decode(1024, BF16),
    "decode_s4096_bf16": lambda: _decode(4096, BF16),
    "paged_64blocks_f32": lambda: _paged(64, F32),
    "paged_64blocks_bf16": lambda: _paged(64, BF16),
    "paged_256blocks_f32": lambda: _paged(256, F32),
    "paged_256blocks_bf16": lambda: _paged(256, BF16),
    # the serve cell's own geometry: 64 slots of max_len 1024
    "paged_64blocks_64slots_f32": lambda: _paged(64, F32, slots=64),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    fn, shapes, n_kernels = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the kernel is in the program: it did not give way to the reference
    assert compiled.as_text().count("tpu_custom_call") >= n_kernels
