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
import numpy as np
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


def _paged(max_blocks, dtype, slots=SLOTS):
    def fn(q, kp, vp, tables, kb, lengths):
        return fa.flash_decode_paged_attention(q, kp, vp, tables,
                                               key_bias=kb, lengths=lengths,
                                               interpret=False)

    # a token's keys are one row of the pool: 12 heads x 64 = 768 lanes
    pool = ((slots * max_blocks + 1, BLOCK, HEADS * D_HEAD), dtype)
    return fn, [((slots, HEADS, 1, D_HEAD), dtype), pool, pool,
                ((slots, max_blocks), jnp.int32),
                ((slots, max_blocks * BLOCK), F32),
                ((slots,), jnp.int32)], 1


def _latent(block, dtype, slots=64, max_len=4352, heads=32, row=640,
            latent=512):
    """The latent (MLA) T = 1 kernel at the widths of the cell
    ``kanana2-serve-chat4k``: 32 heads over a 640-lane pool row."""
    def fn(q, pool, tables, lengths):
        return fa.mla_decode_paged_attention(
            q, pool, tables, lengths, latent, 192 ** -0.5, interpret=False)

    max_blocks = max_len // block
    return fn, [((slots, heads, row), dtype),
                ((slots * max_blocks + 1, block, row), dtype),
                ((slots, max_blocks), jnp.int32),
                ((slots,), jnp.int32)], 1


def _grouped(block=128, slots=64, max_len=5632, heads=64, kv_heads=8, d=128):
    """The paged T = 1 kernel on grouped heads at the widths of the cell
    ``solar2-serve-reason4k``: 64 query heads on 8 key heads of 128, a
    1024-lane bfloat16 pool row."""
    def fn(q, kp, vp, tables, lengths):
        return fa.flash_decode_paged_attention(
            q, kp, vp, tables, lengths=lengths, interpret=False)

    max_blocks = max_len // block
    pool = ((slots * max_blocks + 1, block, kv_heads * d), BF16)
    return fn, [((slots, heads, 1, d), BF16), pool, pool,
                ((slots, max_blocks), jnp.int32),
                ((slots,), jnp.int32)], 1


def _kda_decode(slots=64, heads=64, d=128):
    """The delta-rule T = 1 kernel at the same cell's widths: 64 heads
    of a 128 x 128 float32 state a slot, rewritten in place."""
    from paddle_tpu.kernels import kda

    def fn(state, rows, q, k, v, a, b):
        return kda.kda_decode(state, rows, q, k, v, a, b, interpret=False)

    vec = ((slots, heads, d), F32)
    return fn, [((slots + 1, heads, d, d), F32), ((slots,), jnp.int32),
                vec, vec, vec, vec, ((slots, heads), F32)], 1


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
    "paged_64blocks_f32": lambda: _paged(64, F32),
    "paged_64blocks_bf16": lambda: _paged(64, BF16),
    "paged_256blocks_f32": lambda: _paged(256, F32),
    "paged_256blocks_bf16": lambda: _paged(256, BF16),
    # the serve cell's own geometry: 64 slots of max_len 1024
    "paged_64blocks_64slots_f32": lambda: _paged(64, F32, slots=64),
    # the latent pool follows the model's dtype (bf16); 128 rows a block
    # is one operand a program, 16 rows eight
    "latent_block128_bf16": lambda: _latent(128, BF16),
    "latent_block128_f32": lambda: _latent(128, F32),
    "latent_block16_bf16": lambda: _latent(16, BF16),
    # the cell ``longcat-serve-reason4k``: 64 heads (the query block is
    # [64, 640]) over 64 slots of 5632 positions
    "latent_64heads_block128_bf16": lambda: _latent(
        128, BF16, max_len=5632, heads=64),
    "grouped_64q_8kv_block128_bf16": _grouped,
    "kda_decode_64slots_64heads": _kda_decode,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(chip, case):
    fn, shapes, n_kernels = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the kernel is in the program: it did not give way to the reference
    assert compiled.as_text().count("tpu_custom_call") >= n_kernels
    if case == "flash_causal_b8_s1024":
        _three_named_flash_kernels(compiled.as_text())


def _three_named_flash_kernels(text):
    """The causal sweep's predicates stay inside the kernels: the train
    step still holds three custom calls, under the names the benchmark's
    ``flash_names.event_pattern`` finds them by on the device trace."""
    import re

    from benchmark.kernels import flash_names

    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        pattern = re.compile(flash_names.event_pattern(kernel))
        assert sum(bool(pattern.search(c)) for c in calls) == 1, kernel


def _compile_paged_program(chip, monkeypatch, config_file, cfg_of, which,
                           feed_shapes):
    """One paged program of a serve cell (its configuration file under
    ``benchmark/configs``: slots, max_len and block of its ``serve``),
    built by the model's module (``which(cfg, blocks, block, max_blocks,
    slots)`` -> main, feeds, fetch names) and lowered as the executor
    lowers it, for the described v5e. -> (cfg, blocks, block, compiled)."""
    import json
    import os

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import executor
    from paddle_tpu.fluid.ops import registry

    monkeypatch.setattr(registry, "lowering_backend", lambda: "tpu")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs", config_file)
    with open(path) as f:
        config = json.load(f)
    cfg = cfg_of(config)
    serve = config["serve"]
    slots, block = serve["slots"], serve["block_size"]
    max_blocks = serve["max_len"] // block
    blocks = slots * max_blocks + 1
    with fluid.unique_name.guard():
        main, feeds, fetches = which(cfg, blocks, block, max_blocks, slots)
    compiled = executor._CompiledBlock(
        main, 0, feeds, fetches, fluid.CPUPlace())
    (plan,) = [p for kind, _seg, p in compiled._plans if kind == "xla"]
    declared = main.global_block()

    def dtype_of(name):
        dtype = np.dtype(fluid.core.dtype_to_np(
            declared._find_var_recursive(name).dtype))
        # the executor feeds int64 as int32 (x64 is off)
        return jnp.int32 if dtype == np.int64 else dtype

    def state(name):
        var = declared._find_var_recursive(name)
        return jax.ShapeDtypeStruct(
            tuple(int(d) for d in var.shape), dtype_of(name), sharding=chip)

    shapes = feed_shapes(slots, max_blocks, block)
    args = ([jax.ShapeDtypeStruct(shapes[n], dtype_of(n), sharding=chip)
             for n in plan["feeds"]],
            [state(n) for n in plan["mutable"]],
            [state(n) for n in plan["sharded_const"]],
            {n: state(n) for n in plan["const"]}, None)
    built = jax.jit(plan["raw_fn"], donate_argnums=(1,)).lower(
        *args).compile()
    return cfg, blocks, block, built


def _picked_step(module):
    """``which`` for a family's T = 1 step as the session runs it: the
    module's program with the session's tail (``decode.step_tail``),
    fetching the picked ids and the program's stats."""
    from paddle_tpu.serving import decode

    def step(cfg, blocks, block, max_blocks, slots):
        main, startup, feeds, logits = module.build_paged_step(
            cfg, slots, blocks, block, max_blocks)
        pick, _kept = decode.step_tail(main, startup, logits, 1)
        return main, feeds, [pick] + list(getattr(main, "_step_stats", ()))

    return step


def _pick_checks(built, text, vocab, slots=64):
    """The step hands out [slots] integer ids beside its [slots, vocab]
    float32 logits, and the pick costs no copy of the logits: they leave
    the head's fusion for HBM once (the parent's fusion wrote them there
    itself; with a reader on the chip the compiler may keep them in fast
    memory and send them out with one ``copy-start``)."""
    import re

    outs = [(tuple(o.shape), np.dtype(o.dtype))
            for o in jax.tree_util.tree_leaves(built.out_info)]
    assert outs.count(((slots,), np.dtype("int32"))) == 1
    assert outs.count(((slots, vocab), np.dtype("float32"))) == 1
    logits = r"f32\[%d,%d\]" % (slots, vocab)
    assert not re.findall(r"%%copy[.\d]* = %s" % logits, text)
    assert len(re.findall(r"%%copy-start[.\d]* = \(%s" % logits, text)) <= 1


def _compile_latent_program(chip, monkeypatch, which, feed_shapes):
    """A program of the cell ``kanana2-serve-chat4k`` (64 slots of 4352
    positions, blocks of 128), built by ``models/deepseek.py``."""
    from paddle_tpu.models import deepseek

    return _compile_paged_program(
        chip, monkeypatch, "kanana-2-30b-a3b.json",
        lambda config: deepseek.DeepseekConfig.from_config(
            config, dtype="bfloat16"),
        which, feed_shapes)


def _latent_pool_checks(cfg, blocks, block, built):
    """The program fits the chip, updates the pools in place and copies
    no pool whole into another layout (PR 26 found 24 such copies, ~34 ms
    a step, under the GPT pools' 64-lane rows). -> its text."""
    import re

    memory = built.memory_analysis()
    pool_bytes = cfg.num_hidden_layers * blocks * block * cfg.latent_row * 2
    assert memory.alias_size_in_bytes >= pool_bytes      # updated in place
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.5e9)
    text = built.as_text()
    pool = r"bf16\[%d,1,%d,%d\]" % (blocks, block, cfg.latent_row)
    assert not re.findall(r"%%copy[.\d]* = %s" % pool, text)
    return text


def test_latent_step_program_takes_the_pool_as_it_lies(chip, monkeypatch):
    """The whole T = 1 step: the latent kernel and the three grouped
    products a layer are in it."""
    import re

    from paddle_tpu.models import deepseek

    cfg, blocks, block, built = _compile_latent_program(
        chip, monkeypatch, _picked_step(deepseek),
        lambda slots, max_blocks, block: {
            "step_ids": (slots, 1, 1), "step_pos": (slots, 1, 1),
            "tables": (slots, max_blocks)})
    text = _latent_pool_checks(cfg, blocks, block, built)
    _pick_checks(built, text, cfg.vocab_size)
    layers = cfg.num_hidden_layers
    assert len(re.findall(r"%mla_decode_paged[.\d]* = ", text)) == layers
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 3 * (
        layers - cfg.first_k_dense_replace)


def test_latent_window_program_loops_over_the_keys_it_can_see(
        chip, monkeypatch):
    """The largest prefill window (2048 tokens): one loop over blocks of
    queries and one over chunks of keys a layer, no branch by the fed
    position, and no [heads, 2048, 4352] float32 scores among its
    temporaries (1.1 GB a layer)."""
    import re

    from paddle_tpu.models import deepseek

    t = 2048

    def window(cfg, blocks, block, max_blocks, slots):
        main, _s, feeds, logits = deepseek.build_paged_window(
            cfg, blocks, block, max_blocks, t)
        return main, feeds, [logits.name]

    cfg, blocks, block, built = _compile_latent_program(
        chip, monkeypatch, window, lambda slots, max_blocks, block: {
            "ids": (1, t, 1), "pos_ids": (1, t, 1), "table": (1, max_blocks),
            "window_pos": (1, 1), "last_onehot": (1, t, 1)})
    text = _latent_pool_checks(cfg, blocks, block, built)
    assert len(re.findall(r" while\(", text)) == 2 * cfg.num_hidden_layers
    assert not re.findall(r" conditional\(", text)
    assert built.memory_analysis().temp_size_in_bytes < 0.5e9


def _compile_gpt_program(chip, monkeypatch, which, feed_shapes):
    """A program of the cell ``gpt2s-serve-chat`` (64 slots of 1024
    positions, blocks of 16: 4097 blocks a pool), built by
    ``models/gpt.py``."""
    from paddle_tpu.models import gpt

    return _compile_paged_program(
        chip, monkeypatch, "gpt2-small.json",
        lambda config: gpt.GPTConfig(
            vocab_size=config["vocab_size"], hidden_size=config["n_embd"],
            num_layers=config["n_layer"], num_heads=config["n_head"],
            intermediate_size=config["n_inner"],
            max_position_embeddings=config["n_positions"],
            hidden_dropout=0.0, attention_dropout=0.0,
            use_flash_attention=config["use_flash_attention"]),
        which, feed_shapes)


def _gpt_pool_checks(cfg, blocks, block, built):
    """No GPT pool is copied whole into another layout (with the heads a
    dim of their own, 64 lanes a row, the step held 72 such copies and a
    window 48: 1.77 GB of temporaries and nearly all of the program's
    time); the 24 pools are updated in place. -> the program's text."""
    import re

    from paddle_tpu.models import gpt

    shape = gpt.paged_pool_shape(cfg, blocks, block)
    assert shape == [blocks, 1, block, cfg.hidden_size]
    memory = built.memory_analysis()
    pool_bytes = 2 * cfg.num_layers * int(np.prod(shape)) * 4
    assert pool_bytes > 4.8e9                              # the cell's pools
    assert memory.alias_size_in_bytes >= pool_bytes        # updated in place
    assert memory.temp_size_in_bytes < 0.3e9
    text = built.as_text()
    rows = r"f32\[%d,(1,)?%d,%d\]" % (blocks, block, cfg.hidden_size)
    assert not re.findall(r"%%copy[.\d]* = %s" % rows, text)
    return text


def test_gpt_step_program_takes_the_pool_as_it_lies(chip, monkeypatch):
    """The whole T = 1 step of ``gpt2s-serve-chat``: one paged kernel a
    layer, and nothing re-tiles a pool on its way in or out."""
    import re

    from paddle_tpu.models import gpt

    cfg, blocks, block, built = _compile_gpt_program(
        chip, monkeypatch, _picked_step(gpt),
        lambda slots, max_blocks, block: {
            "step_ids": (slots, 1, 1), "step_pos": (slots, 1, 1),
            "tables": (slots, max_blocks),
            "step_bias": (slots, 1, max_blocks * block)})
    text = _gpt_pool_checks(cfg, blocks, block, built)
    _pick_checks(built, text, cfg.vocab_size)
    assert len(re.findall(r"%flash_decode_paged[.\d]* = ", text)) \
        == cfg.num_layers


GPT_BUCKETS = [64, 128, 256, 512]


@pytest.mark.parametrize("bucket", GPT_BUCKETS)
def test_gpt_window_program_takes_the_pool_as_it_lies(chip, monkeypatch,
                                                      bucket):
    """Every prefill bucket of the cell's ``serve``: the window scatters
    into the pools and gathers its row back with no pool copied whole."""
    import json
    import os

    from paddle_tpu.models import gpt

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "gpt2-small.json")) as f:
        assert json.load(f)["serve"]["prefill_buckets"] == GPT_BUCKETS

    def window(cfg, blocks, block, max_blocks, slots):
        main, _s, feeds, logits = gpt.build_paged_window(
            cfg, blocks, block, max_blocks, bucket)
        return main, feeds, [logits.name]

    cfg, blocks, block, built = _compile_gpt_program(
        chip, monkeypatch, window, lambda slots, max_blocks, block: {
            "ids": (1, bucket, 1), "pos_ids": (1, bucket, 1),
            "table": (1, max_blocks), "window_pos": (1, 1),
            "resume_bias": (1, bucket, max_blocks * block),
            "last_onehot": (1, bucket, 1)})
    _gpt_pool_checks(cfg, blocks, block, built)


def _compile_hybrid_program(chip, monkeypatch, which, feed_shapes):
    """A program of the cell ``solar2-serve-reason4k`` (64 slots of 5632
    positions, blocks of 128; 2 softmax + 6 delta-rule layers, 20 of 320
    experts held), built by ``models/solar_open2.py``."""
    from paddle_tpu.models import solar_open2

    return _compile_paged_program(
        chip, monkeypatch, "solar-open2-250b.json",
        lambda config: solar_open2.SolarOpen2Config.from_config(
            config, dtype="bfloat16"),
        which, feed_shapes)


def _hybrid_cache_checks(cfg, blocks, block, built, slots=64):
    """The program fits the chip beside nothing else, updates pools AND
    states in place, and copies neither whole. -> its text."""
    import re

    from paddle_tpu.models import cache_kinds, solar_open2

    kinds = solar_open2.cache_kinds(cfg)
    held = (cache_kinds.bytes_per_token(kinds) * blocks * block
            + cache_kinds.state_bytes_per_slot(kinds) * (slots + 1))
    memory = built.memory_analysis()
    assert held > 4.5e9                                  # the cell's caches
    assert memory.alias_size_in_bytes >= held            # updated in place
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 15.5e9)
    text = built.as_text()
    pool = r"bf16\[%d,1,%d,1024\]" % (blocks, block)
    state = r"f32\[%d,64,128,128\]" % (slots + 1)
    assert not re.findall(r"%%copy[.\d]* = (%s|%s)" % (pool, state), text)
    return text


def test_hybrid_step_program_steps_states_and_pools_in_place(
        chip, monkeypatch):
    """The whole T = 1 step: one grouped paged kernel a softmax layer, one
    delta-rule kernel a delta-rule layer, three grouped products a layer
    over the 20 experts held."""
    import re

    from paddle_tpu.models import solar_open2

    cfg, blocks, block, built = _compile_hybrid_program(
        chip, monkeypatch, _picked_step(solar_open2),
        lambda slots, max_blocks, block: {
            "step_ids": (slots, 1, 1), "step_pos": (slots, 1, 1),
            "tables": (slots, max_blocks), "state_rows": (slots, 1)})
    text = _hybrid_cache_checks(cfg, blocks, block, built)
    _pick_checks(built, text, cfg.vocab_size)
    assert len(re.findall(r"%flash_decode_paged_gqa[.\d]* = ", text)) == 2
    assert len(re.findall(r"%kda_decode[.\d]* = ", text)) == 6
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 3 * 8
    assert built.memory_analysis().temp_size_in_bytes < 0.5e9


def test_hybrid_window_program_scans_chunks_and_loops_over_keys(
        chip, monkeypatch):
    """The largest prefill window (2048 tokens) from a fed state row."""
    from paddle_tpu.models import solar_open2

    t = 2048

    def window(cfg, blocks, block, max_blocks, slots):
        main, _s, feeds, logits = solar_open2.build_paged_window(
            cfg, blocks, block, max_blocks, t, slots=slots)
        return main, feeds, [logits.name]

    cfg, blocks, block, built = _compile_hybrid_program(
        chip, monkeypatch, window, lambda slots, max_blocks, block: {
            "ids": (1, t, 1), "pos_ids": (1, t, 1), "table": (1, max_blocks),
            "window_pos": (1, 1), "last_onehot": (1, t, 1),
            "state_row": (1, 1), "window_len": (1, 1)})
    _hybrid_cache_checks(cfg, blocks, block, built)
    assert built.memory_analysis().temp_size_in_bytes < 2.5e9


# -- the cell ``longcat-serve-reason4k`` (models/longcat_flash.py) ------------

def _compile_shortcut_program(chip, monkeypatch, which, feed_shapes):
    """A program of the cell ``longcat-serve-reason4k`` (64 slots of 5632
    positions, blocks of 128, eight latent pools), built by
    ``models/longcat_flash.py``."""
    from paddle_tpu.models import longcat_flash

    return _compile_paged_program(
        chip, monkeypatch, "longcat-flash-omni.json",
        lambda config: longcat_flash.LongcatFlashConfig.from_config(
            config, dtype="bfloat16"),
        which, feed_shapes)


def _shortcut_pool_checks(cfg, blocks, block, built):
    """Eight pools updated in place, none copied whole, and weights,
    pools and temporaries together inside the chip's 16.9 GB.
    -> (text, memory)."""
    import re

    memory = built.memory_analysis()
    pool_bytes = 2 * cfg.num_layers * blocks * block * cfg.latent_row * 2
    assert memory.alias_size_in_bytes >= pool_bytes
    assert (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            < 16.5e9)
    text = built.as_text()
    pool = r"bf16\[%d,1,%d,%d\]" % (blocks, block, cfg.latent_row)
    assert not re.findall(r"%%copy[.\d]* = %s" % pool, text)
    return text, memory


@pytest.mark.slow  # ~40 s beside 14 GB of described arguments: it tips tier-1's timing probes; fast equivalents: the case latent_64heads_block128_bf16 above (the kernel at these heads) + tests/test_longcat_flash.py (the step at toy widths). Run before a chip call: -k shortcut
def test_shortcut_step_program_runs_the_latent_kernel_twice_a_layer(
        chip, monkeypatch):
    """The whole T = 1 step at the published widths: two latent kernel
    calls and three grouped products a double layer, the router 768
    wide, the pools taken as they lie."""
    import re

    from paddle_tpu.models import longcat_flash

    cfg, blocks, block, built = _compile_shortcut_program(
        chip, monkeypatch, _picked_step(longcat_flash),
        lambda slots, max_blocks, block: {
            "step_ids": (slots, 1, 1), "step_pos": (slots, 1, 1),
            "tables": (slots, max_blocks)})
    text, memory = _shortcut_pool_checks(cfg, blocks, block, built)
    _pick_checks(built, text, cfg.vocab_size)
    layers = cfg.num_layers
    assert len(re.findall(r"%mla_decode_paged[.\d]* = ", text)) == 2 * layers
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 3 * layers
    assert memory.temp_size_in_bytes < 0.5e9


@pytest.mark.slow  # ~40 s, as the step above; run before a chip call: -k shortcut
def test_shortcut_window_program_fits_beside_weights_and_pools(
        chip, monkeypatch):
    """The largest prefill window (``serve.prefill_chunk`` tokens): its
    expert branch sorts 12 assignments a token of 6144-wide rows; the
    program's arguments (10.35 GB of weights, 3.7 GB of pools) and its
    temporaries fit the chip."""
    import json
    import os

    from paddle_tpu.models import longcat_flash

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "longcat-flash-omni.json")
    with open(path) as f:
        t = json.load(f)["serve"]["prefill_chunk"]

    def window(cfg, blocks, block, max_blocks, slots):
        main, _s, feeds, logits = longcat_flash.build_paged_window(
            cfg, blocks, block, max_blocks, t)
        return main, feeds, [logits.name]

    cfg, blocks, block, built = _compile_shortcut_program(
        chip, monkeypatch, window, lambda slots, max_blocks, block: {
            "ids": (1, t, 1), "pos_ids": (1, t, 1), "table": (1, max_blocks),
            "window_pos": (1, 1), "last_onehot": (1, t, 1)})
    _text, memory = _shortcut_pool_checks(cfg, blocks, block, built)
    print("window", t, memory)
