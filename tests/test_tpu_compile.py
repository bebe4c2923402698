"""Kernels of the serving path compiled for a TPU v5e that is described, not
attached: what Mosaic and XLA refuse at the cells' real widths costs no chip
time here. Nothing runs, so these say nothing about results or speed.

The topology is described inside a fixture (never at import: every xdist
worker imports every test file, and only one process may hold libtpu).
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


@pytest.fixture(scope="module")
def four_chips():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


@pytest.fixture
def tpu_backend(monkeypatch):
    """The backend here is the CPU; the dispatch rule's other inputs are the
    call's own."""
    from paddle_tpu.nn.functional import paged_attention as fpa
    monkeypatch.setattr(fpa.jax, "default_backend", lambda: "tpu")


def _decode_step(q, kc, vc, tables, lens, nk, nv):
    out, kc, vc = F.block_multihead_attention(
        paddle.Tensor(q), paddle.Tensor(kc), paddle.Tensor(vc),
        paddle.Tensor(tables), paddle.Tensor(lens),
        new_k=paddle.Tensor(nk), new_v=paddle.Tensor(nv))
    return out._data, kc._data, vc._data


# (lanes, H, KVH, blocks, table width): the serving configurations; the
# last one's block table, 64 x 1152 int32, is 288 KiB of scalar prefetch
@pytest.mark.parametrize("geometry", [
    pytest.param((32, 32, 8, 4096, 128), id="mistral-7b"),
    pytest.param((64, 32, 2, 16384, 256), id="nemotron-3-super"),
    pytest.param((64, 64, 8, 49152, 1152), id="k-exaone"),
    # 30 heads a side in pages of 32 (two of zeros): a group of one
    pytest.param((128, 32, 32, 10240, 256), id="olmo-hybrid")])
def test_paged_decode_step_compiles_with_the_kernel_and_no_pool_copy(
        one_chip, tpu_backend, geometry):
    """The decode step of ``block_multihead_attention`` at a cell's shapes:
    the scatter of the new token, then ``paged_decode_attn`` reading the
    donated pools as they lie (the reshape to page rows is a bitcast)."""
    B, H, KVH, nb, mb = geometry
    D, bs, dt = 128, 16, jnp.bfloat16

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(_decode_step, donate_argnums=(1, 2)).lower(
        s((B, 1, H, D), dt), s((nb, bs, KVH, D), dt), s((nb, bs, KVH, D), dt),
        s((B, mb), jnp.int32), s((B,), jnp.int32),
        s((B, 1, KVH, D), dt), s((B, 1, KVH, D), dt)).compile().as_text()
    entry = text[text.index("ENTRY"):]
    assert entry.count('custom_call_target="tpu_custom_call"') == 1
    assert "%paged_decode_attn" in entry
    # what makes or moves an array of the pools' size: the two parameters,
    # the two in-place scatters, the two bitcasts to page rows; no copy
    made = re.findall(rf"= bf16\[{nb},\S+ ([\w-]+)\(", entry)
    assert sorted(made) == ["bitcast", "bitcast", "fusion", "fusion",
                            "parameter", "parameter"], made


def test_latent_decode_step_compiles_with_the_kernel_and_no_pool_copy(
        one_chip, tpu_backend):
    """The decode step of ``latent_paged_attention`` at the Kanana cell's
    shapes (64 lanes, 32 query heads over rows of 640 lanes, values their
    first 512, 49 152 pages of 16, a block table of 64 x 2048 int32 = 512
    KiB of scalar prefetch): the scatter of the new rows, then
    ``paged_decode_attn`` reading the ONE donated pool as it lies."""
    B, H, D, dv, nb, bs, mb, dt = 64, 32, 640, 512, 49152, 16, 2048, \
        jnp.bfloat16

    def step(q, pool, tables, lens, new):
        out, pool = F.latent_paged_attention(
            paddle.Tensor(q), paddle.Tensor(pool), paddle.Tensor(tables),
            paddle.Tensor(lens), paddle.Tensor(new), dv, 192 ** -0.5)
        return out._data, pool._data

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(step, donate_argnums=(1,)).lower(
        s((B, 1, H, D), dt), s((nb, bs, D), dt), s((B, mb), jnp.int32),
        s((B,), jnp.int32), s((B, 1, D), dt)).compile().as_text()
    entry = text[text.index("ENTRY"):]
    assert entry.count('custom_call_target="tpu_custom_call"') == 1
    assert "%paged_decode_attn" in entry
    # what makes or moves an array of the pool's size: the parameter and
    # the in-place scatter (the pool is page rows already); no copy
    made = re.findall(rf"= bf16\[{nb},\S+ ([\w-]+)\(", entry)
    assert sorted(made) == ["fusion", "parameter"], made


def test_thirty_kv_heads_would_copy_the_pool_and_are_refused(one_chip):
    """Why ``supports()`` wants K/V heads that fill whole sublane tiles: a
    pool of 30 bfloat16 heads is laid out in 32, so reading it as page rows
    is no bitcast and the compiler copies it, 1.26 GB a pool a step at the
    Olmo-Hybrid cell's size (a 512-block pool here)."""
    from paddle_tpu.ops.pallas import paged_attention as pk
    nb, bs, D, dt = 512, 16, 128, jnp.bfloat16

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    assert not pk.supports((128, 1, 30, D), dt, (nb, bs, 30, D), dt)
    temp = {}
    for heads in (30, 32):
        compiled = jax.jit(pk.paged_decode_attention).lower(
            s((128, 1, heads, D), dt), s((nb, bs, heads, D), dt),
            s((nb, bs, heads, D), dt), s((128, 256), jnp.int32),
            s((128,), jnp.int32)).compile()
        temp[heads] = compiled.memory_analysis().temp_size_in_bytes
    pool = nb * bs * 30 * D * 2
    assert temp[30] >= 2 * pool and temp[32] < pool // 4, temp


def test_delta_rule_decode_step_compiles_in_place(one_chip):
    """``delta_rule_step`` at the Olmo-Hybrid cell's sizes (128 lanes, 30
    heads of 96 x 192 packed two to a row): one Mosaic kernel, the donated
    283 MB state aliased to the new one, and no buffer of the state's size
    beside it."""
    from paddle_tpu.ops.pallas import delta_rule as dk
    B, H, d_k, d_v, p = 128, 30, 96, 192, 2
    f32 = jnp.float32

    def s(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = (B, H // p, d_k, p * d_v)
    assert dk.supports(state, d_k, p)
    compiled = jax.jit(
        lambda q, k, v, a, b, st, fr, idl: dk.delta_rule_step(
            q, k, v, a, b, st, fr, idl, p), donate_argnums=(5,)).lower(
        s((B, H, d_k)), s((B, H, d_k)), s((B, H, d_v)), s((B, H)),
        s((B, H)), s(state), s((B,), bool), s((B,), bool)).compile()
    entry = compiled.as_text()
    entry = entry[entry.index("ENTRY"):]
    assert entry.count('custom_call_target="tpu_custom_call"') == 1
    assert "%delta_rule_step" in entry
    mem = compiled.memory_analysis()
    nbytes = B * H * d_k * d_v * 4
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < nbytes // 8


def test_olmo_hybrid_programs_compile_with_both_kernels(one_chip,
                                                        tpu_backend,
                                                        monkeypatch):
    """The decode step, the mixed step and the prefill chunk of an
    Olmo-Hybrid decoder at the published widths (one linear and one full
    layer, 128 lanes, vocabulary cut to keep the test's arrays small):
    ``delta_rule_step`` once a linear layer and ``paged_decode_attn`` once a
    full layer in the programs that hold a decode step, none in the chunk,
    states and pools donated and no copy of them, no temporary of the size
    the chunk's convolution once took (1.5 GB:
    ``nn.functional.delta_rule.conv_arrays`` has why), and no
    ``triangular_solve`` custom call in a program that runs the chunked rule
    (``unit_lower_inverse`` is products and sweeps; the call was 3.6 of a
    chunk's 13.8 ms)."""
    from paddle_tpu.inference import PagedEngine
    from paddle_tpu.models import OlmoHybridConfig, OlmoHybridForCausalLM
    from paddle_tpu.nn.functional import delta_rule as fdr
    from paddle_tpu.nn.functional.paged_attention import log_paths
    from paddle_tpu.nn.lazy_init import LazyGuard, materialize_layer
    from paddle_tpu.serving import SchedulerConfig

    monkeypatch.setattr(fdr.jax, "default_backend", lambda: "tpu")
    layers, B, nb, context = 2, 128, 320, 4096
    with LazyGuard():
        model = OlmoHybridForCausalLM(OlmoHybridConfig(
            num_hidden_layers=layers, vocab_size=1024, max_seq_len=context,
            layer_types=("linear_attention", "full_attention")))
    for p in model.parameters():
        shape = tuple(p.shape)
        dt = jnp.float32 if len(shape) == 1 else jnp.bfloat16
        p._lazy_init = (lambda _s, _d, shape=shape, dt=dt: jnp.zeros(
            shape, dt), shape, dt)
    materialize_layer(model)
    model.eval()
    eng = PagedEngine(model, max_batch=B, block_size=16, num_blocks=nb,
                      max_blocks_per_seq=context // 16,
                      scheduler=SchedulerConfig(prefill_token_budget=256))
    W = eng.prefill_width

    def rows(r, w):
        return (np.zeros((r, w), np.int32), np.ones((r,), np.int32),
                np.zeros((r, context // 16), np.int32),
                np.zeros((r,), np.float32), np.ones((r,), np.float32),
                np.zeros((r,), np.int32), np.zeros((r,), np.int32))

    def shapes(args):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), args)

    decode = eng._chunk_args(*rows(B, 1))
    mixed = decode + (jnp.zeros((1,), jnp.int32),
                      eng._row_args(*rows(1, W)))
    chunk = eng._chunk_args(*rows(1, W)) + (jnp.zeros((1,), jnp.int32),)
    state = B * 30 * 96 * 192 * 4
    for name, args, paths, kernels in (
            ("decode", decode, ["kernel"], layers),
            ("mixed", mixed, ["composite", "kernel"], layers),
            ("prefill", chunk, ["composite"], 0)):
        with log_paths() as lowered:
            compiled = eng._fns[name].lower(
                *shapes(args), sampling=False).compile()
        assert (W, sorted(set(lowered))) == (256, paths)
        text = compiled.as_text()
        assert not re.search("triangular_solve|TriangularSolve|"
                             "InvertDiagBlocks", text), name
        entry = text[text.index("ENTRY"):]
        assert entry.count('custom_call_target="tpu_custom_call"') == kernels
        assert ("%delta_rule_step" in entry) == bool(kernels)
        assert ("%paged_decode_attn" in entry) == bool(kernels)
        assert not re.findall(
            rf"= (?:bf16\[{nb},16|f32\[{B},15),\S+ copy\(", entry)
        assert compiled.memory_analysis().temp_size_in_bytes < state, name


def test_kimi_delta_step_compiles_in_place(one_chip):
    """``delta_rule_step`` with the decay as a column, at the Solar-Open2
    cell's sizes (256 lanes, 64 heads of 128 x 128, no packing): one Mosaic
    kernel, 16 heads a grid step (three columns a head in one 128-lane
    tile), the donated 1.07 GB state aliased to the new one, and no buffer
    of the state's size beside it."""
    from paddle_tpu.ops.pallas import delta_rule as dk
    B, H, d = 256, 64, 128
    f32 = jnp.float32

    def s(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = (B, H, d, d)
    assert dk.supports(state, d, 1, channel=True)
    assert not dk.supports(state, d, 2, channel=True)
    assert dk.rows_per_block(H, d, d, 1, columns=3) == 16
    compiled = jax.jit(
        lambda q, k, v, a, b, st, fr, idl: dk.delta_rule_step(
            q, k, v, a, b, st, fr, idl, 1), donate_argnums=(5,)).lower(
        s((B, H, d)), s((B, H, d)), s((B, H, d)), s((B, H, d)),
        s((B, H)), s(state), s((B,), bool), s((B,), bool)).compile()
    entry = compiled.as_text()
    entry = entry[entry.index("ENTRY"):]
    assert entry.count('custom_call_target="tpu_custom_call"') == 1
    assert "%delta_rule_step" in entry
    mem = compiled.memory_analysis()
    nbytes = B * H * d * d * 4
    assert mem.alias_size_in_bytes >= nbytes
    assert mem.temp_size_in_bytes < nbytes // 8


def test_solar_open2_programs_compile_with_both_kernels(one_chip,
                                                        tpu_backend,
                                                        monkeypatch):
    """The decode step, the mixed step and the prefill chunk of a Solar Open
    2 decoder at the published widths and the cell's engine sizes (256
    lanes, a 256 x 512 block table of scalar prefetch, context 8192; one GQA
    and one KDA layer, 8 held experts of the router's 320 and a vocabulary
    cut to keep the test's arrays small): ``delta_rule_step`` once a KDA
    layer and ``paged_decode_attn`` once a GQA layer in the programs that
    hold a decode step, none in the chunk, states and pools donated and no
    copy of them, no temporary of the size of the state, and no
    ``triangular_solve`` custom call in a program that runs the chunked
    rule."""
    from paddle_tpu.inference import PagedEngine
    from paddle_tpu.models import SolarOpen2Config, SolarOpen2ForCausalLM
    from paddle_tpu.nn.functional import delta_rule as fdr
    from paddle_tpu.nn.functional.paged_attention import log_paths
    from paddle_tpu.nn.lazy_init import LazyGuard, materialize_layer
    from paddle_tpu.serving import SchedulerConfig

    monkeypatch.setattr(fdr.jax, "default_backend", lambda: "tpu")
    layers, B, nb, context = 2, 256, 640, 8192
    with LazyGuard():
        model = SolarOpen2ForCausalLM(SolarOpen2Config(
            num_hidden_layers=layers, gqa_layers=(0,), vocab_size=1024,
            experts_held=(0, 8), max_seq_len=context))
    for p in model.parameters():
        shape = tuple(p.shape)
        dt = jnp.float32 if len(shape) == 1 else jnp.bfloat16
        p._lazy_init = (lambda _s, _d, shape=shape, dt=dt: jnp.zeros(
            shape, dt), shape, dt)
    materialize_layer(model)
    model.eval()
    eng = PagedEngine(model, max_batch=B, block_size=16, num_blocks=nb,
                      max_blocks_per_seq=context // 16,
                      scheduler=SchedulerConfig(prefill_token_budget=512))
    W = eng.prefill_width

    def rows(r, w):
        return (np.zeros((r, w), np.int32), np.ones((r,), np.int32),
                np.zeros((r, context // 16), np.int32),
                np.zeros((r,), np.float32), np.ones((r,), np.float32),
                np.zeros((r,), np.int32), np.zeros((r,), np.int32))

    def shapes(args):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), args)

    decode = eng._chunk_args(*rows(B, 1))
    mixed = decode + (jnp.zeros((1,), jnp.int32),
                      eng._row_args(*rows(1, W)))
    chunk = eng._chunk_args(*rows(1, W)) + (jnp.zeros((1,), jnp.int32),)
    state = B * 64 * 128 * 128 * 4
    for name, args, paths, kernels in (
            ("decode", decode, ["kernel"], layers),
            ("mixed", mixed, ["composite", "kernel"], layers),
            ("prefill", chunk, ["composite"], 0)):
        with log_paths() as lowered:
            compiled = eng._fns[name].lower(
                *shapes(args), sampling=False).compile()
        assert (W, sorted(set(lowered))) == (256, paths)
        text = compiled.as_text()
        assert not re.search("triangular_solve|TriangularSolve|"
                             "InvertDiagBlocks", text), name
        entry = text[text.index("ENTRY"):]
        assert entry.count('custom_call_target="tpu_custom_call"') == kernels
        assert ("%delta_rule_step" in entry) == bool(kernels)
        assert ("%paged_decode_attn" in entry) == bool(kernels)
        assert not re.findall(
            rf"= (?:bf16\[{nb},16|f32\[{B},64),\S+ copy\(", entry)
        assert compiled.memory_analysis().temp_size_in_bytes < state, name


@pytest.mark.parametrize("partitioned_by", ["the-compiler", "shard-map"])
def test_paged_decode_step_compiles_over_a_mesh(four_chips, tpu_backend,
                                                partitioned_by):
    """Mistral's decode step over dp 2 x mp 2, heads over ``mp`` (tensor
    parallel serving; the ``dp`` pairs hold copies). Nothing but the
    arguments' shardings says so (no global mesh, no context): the caller's
    jit leaves the partitioning to the compiler, which cannot partition a
    Mosaic kernel, so the composite is lowered, as before the kernel was
    there. Under a ``shard_map`` that is manual over both axes each device
    runs the kernel on the heads it holds."""
    from paddle_tpu.distributed.shard_map_compat import shard_map
    from paddle_tpu.nn.functional.paged_attention import log_paths

    mesh = Mesh(np.asarray(four_chips).reshape(2, 2), ("dp", "mp"))
    B, H, KVH, nb, mb, D, bs, dt = 32, 32, 8, 4096, 128, 128, 16, jnp.bfloat16
    heads = P(None, None, "mp", None)
    specs = (heads, heads, heads, P(), P(), heads, heads)
    shapes = ((B, 1, H, D), (nb, bs, KVH, D), (nb, bs, KVH, D), (B, mb),
              (B,), (B, 1, KVH, D), (B, 1, KVH, D))
    dtypes = (dt, dt, dt, jnp.int32, jnp.int32, dt, dt)
    step = _decode_step
    if partitioned_by == "shard-map":
        step = shard_map(_decode_step, mesh, specs, (heads, heads, heads))
    with log_paths() as lowered:
        text = jax.jit(step, donate_argnums=(1, 2)).lower(*(
            jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(mesh, spec))
            for shape, dtype, spec in zip(shapes, dtypes, specs))
        ).compile().as_text()
    kernels = text.count('custom_call_target="tpu_custom_call"')
    if partitioned_by == "shard-map":
        assert (lowered, kernels) == (["kernel"], 1)
    else:
        assert (lowered, kernels) == (["composite"], 0)


def test_mixed_step_compiles_with_the_kernel_for_the_lanes(one_chip,
                                                           tpu_backend):
    """``paged_mixed_step`` at Mistral's widths (two layers of the sixteen):
    one program whose chunk rows attend through the composite and whose 32
    decode rows through ``paged_decode_attn``, once a layer, the pools
    donated and no copy of them made."""
    from paddle_tpu.inference import PagedEngine
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.nn.functional.paged_attention import log_paths
    from paddle_tpu.nn.lazy_init import LazyGuard, materialize_layer

    layers, B, nb, context = 2, 32, 512, 2048
    with LazyGuard():
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=32768, hidden_size=4096, intermediate_size=14336,
            num_layers=layers, num_heads=32, num_kv_heads=8,
            max_seq_len=context, rope_theta=1e6, use_flash_attention=False))
    for p in model.parameters():
        shape = tuple(p.shape)
        p._lazy_init = (lambda _s, _d, shape=shape: jnp.zeros(
            shape, jnp.bfloat16), shape, jnp.bfloat16)
    materialize_layer(model)
    model.eval()
    eng = PagedEngine(model, max_batch=B, block_size=16, num_blocks=nb,
                      max_blocks_per_seq=context // 16)
    W = eng.prefill_width

    def rows(r, w):
        return (np.zeros((r, w), np.int32), np.ones((r,), np.int32),
                np.zeros((r, context // 16), np.int32),
                np.zeros((r,), np.float32), np.ones((r,), np.float32),
                np.zeros((r,), np.int32), np.zeros((r,), np.int32))

    args = eng._chunk_args(*rows(B, 1)) + (
        jnp.zeros((1,), jnp.int32), eng._row_args(*rows(1, W)))
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    with log_paths() as lowered:
        text = eng._fns["mixed"].lower(
            *shapes, sampling=False).compile().as_text()
    assert (W, sorted(set(lowered))) == (256, ["composite", "kernel"])
    entry = text[text.index("ENTRY"):]
    assert entry.count('custom_call_target="tpu_custom_call"') == layers
    assert not re.findall(rf"= bf16\[{nb},\S+ copy\(", entry)


# (causal, S, d, heads): a tile that runs whole has to fit VMEM beside its
# operands (a non-causal tile; Mosaic refuses 2048 x 2048, 16 MB of f32
# scores), and a causal tile of any length runs in bands
@pytest.mark.parametrize("call", [
    pytest.param((True, 1024, 64, 128), id="causal-the-fit-cells-shape"),
    pytest.param((True, 1100, 64, 4), id="causal-ragged-1100"),
    pytest.param((True, 2000, 64, 4), id="causal-ragged-2000"),
    # several 2048-wide causal tiles keep running state beside the band's
    # scores: 19.2 MB of scoped VMEM at d 64, over the default 16 MB limit
    # (every causal call longer than one tile was refused before PR 39)
    pytest.param((True, 4096, 64, 4), id="causal-4096-two-tiles"),
    pytest.param((True, 8192, 64, 8), id="causal-8192-the-lfm2-cells-shape"),
    pytest.param((False, 2048, 64, 4), id="full-2048"),
    pytest.param((False, 4096, 128, 4), id="full-4096"),
    pytest.param((False, 1536, 128, 4), id="full-ragged-1536")])
def test_flash_forward_and_backward_compile_at_their_own_tiles(
        one_chip, monkeypatch, call):
    """``flash_attention_fwd`` with no tile pinned, forward and backward,
    as a model's step calls it: the tiles are the module's (no probe runs
    without a chip) and Mosaic takes all three kernels."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "INTERPRET", False)
    causal, S, d, heads = call
    x = jax.ShapeDtypeStruct((1, S, heads, d), jnp.bfloat16,
                             sharding=one_chip)
    text = jax.jit(jax.value_and_grad(
        lambda q, k, v: jnp.sum(fa.flash_attention_fwd(
            q, k, v, causal=causal).astype(jnp.float32)),
        argnums=(0, 1, 2))).lower(x, x, x).compile().as_text()
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert f"%{kernel}" in text


def _flash_calls(entry):
    return {kernel: len(re.findall(rf"%{kernel}(?:\.\d+)? = ", entry))
            for kernel in ("flash_fwd", "flash_dq", "flash_dkv")}


def _compiled_train_step(lm, batch, seq, one_chip, monkeypatch, whole=False):
    """The ENTRY computation of ``Engine``'s train step over ``lm`` under
    bf16 O1 autocast with AdamW, compiled for the described chip (``whole``:
    every computation of the module, the loops' bodies among them)."""
    from paddle_tpu import amp, nn
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "INTERPRET", False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    # the program's mesh is one device, as in the cells (the suite's is the
    # CPU's eight, over which the flash call would be shard_mapped)
    monkeypatch.setattr(mesh_mod, "_global_mesh", mesh_mod.build_mesh(
        devices=jax.devices()[:1]))

    class Loss(nn.Layer):
        def __init__(self, lm):
            super().__init__()
            self.lm = lm

        def forward(self, ids):
            with amp.auto_cast(level="O1", dtype="bfloat16"):
                return self.lm(ids, labels=ids)[1]

    net = Loss(lm)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=net.parameters())
    eng = Engine(net, loss=lambda loss, _labels: loss, optimizer=opt)
    eng.prepare()

    def s(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = [s(p._data) for p in eng._params]
    state = jax.tree_util.tree_map(s, eng._init_opt_state(None))
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=one_chip)
    text = eng._train_step.lower(
        params, state, jax.ShapeDtypeStruct((), jnp.float32,
                                            sharding=one_chip),
        ids, ids).compile().as_text()
    return text if whole else text[text.index("ENTRY"):]


_GROUPED_KERNEL = r"%ragged-dot-(?!metadata)[\w-]+(?:\.\d+)? = "


def _expert_loop_bodies(entry):
    """Names of the body computations of the ``while``s that ENTRY runs for
    the expert layers (``nn/functional/experts.py`` ``_walk``)."""
    return [m.group(1) for m in re.finditer(
        r"[^\n]* while\([^\n]*body=%([\w.-]+)[^\n]*", entry)
        if "moe.experts/while" in m.group(0)]


def _computations(text):
    """``{name: text}`` of a compiled module's computations."""
    found = re.split(r"\n(?=(?:ENTRY )?%[\w.-]+ \([^\n]*\) -> [^\n]* \{\n)",
                     text)
    return {re.match(r"(?:ENTRY )?%([\w.-]+) ", c).group(1): c
            for c in found if re.match(r"(?:ENTRY )?%[\w.-]+ \(", c)}


def test_lfm2_train_step_compiles_with_the_grouped_matmuls(one_chip,
                                                           monkeypatch):
    """``Engine``'s train step over a small LFM2-MoE (every kind of layer,
    blocks rematerialised, bf16 O1 autocast) at 2 x 1024 tokens, the rows
    from which the expert product takes its grouped form: XLA lowers each
    ``ragged_dot`` and each of its transposes to a ``ragged-dot`` Mosaic
    kernel inside the layer's two loops, no branch over sizes comes from
    the layer, and the attention layer runs the three flash kernels, the
    forward one once (the block keeps the kernel's two residuals,
    ``models/_remat.py``)."""
    from paddle_tpu.models import Lfm2MoeForCausalLM, lfm2_moe_tiny
    from paddle_tpu.nn.functional import experts

    batch, seq = 2, 1024
    assert experts.takes_grouped_form(batch * seq)
    cfg = lfm2_moe_tiny(hidden_size=128, num_attention_heads=2,
                        num_key_value_heads=1, intermediate_size=256,
                        moe_intermediate_size=128, num_experts=8,
                        experts_held=(0, 4), vocab_size=512, recompute=True)
    text = _compiled_train_step(Lfm2MoeForCausalLM(cfg), batch, seq,
                                one_chip, monkeypatch, whole=True)
    comps = _computations(text)
    entry = text[text.index("ENTRY"):]
    # the expert layer walks its sorted pairs in ONE loop a direction
    # (``nn/functional/experts.py`` ``_walk``): every grouped matmul is in
    # the body of a ``while`` traced under ``moe.experts``, none in ENTRY
    loops = _expert_loop_bodies(entry)
    # four routed layers, a loop forward and a loop backward each (the
    # rematerialised forward's results are read by nothing, its loop is
    # dropped) or a third where the compiler keeps that copy
    assert 4 * 2 <= len(loops) <= 4 * 3
    assert not re.findall(_GROUPED_KERNEL, entry)
    grouped = sum(len(re.findall(_GROUPED_KERNEL, comps[b])) for b in loops)
    # three products forward; backward two again and six transposes, less
    # what the compiler shares between them
    assert 4 * 9 <= grouped <= 4 * 12
    assert not [line for line in text.splitlines()
                if " conditional(" in line and "moe" in line]
    # the bodies' own operations carry the layer's scope, forward and
    # backward; the kernels are ``ragged-dot-*`` whatever they were traced
    # under (``moe.grouped_matmul`` is read on the lowered call,
    # ``tests/test_lfm2_moe.py``)
    for body in loops:
        assert "moe.experts/while/body/moe.group/" in comps[body], body
    assert _flash_calls(entry) == {"flash_fwd": 1, "flash_dq": 1,
                                   "flash_dkv": 1}


GPT2_STEP = dict(layers=2, heads=16, batch=8, seq=1024)
#: the logsumexp of a layer's call there: (B, H / 2, 2, S)
_LSE = "8,8,2,1024"


@pytest.fixture(scope="module")
def gpt2_step(four_chips):
    """Two GPT-2 medium blocks at the fit cell's shape (8 x 1024 tokens, 16
    heads of 64, blocks rematerialised), the train step's ENTRY: compiled
    once for the tests that read it."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    g = GPT2_STEP
    with pytest.MonkeyPatch.context() as patch:
        return _compiled_train_step(GPTForCausalLM(GPTConfig(
            vocab_size=512, hidden_size=1024, num_layers=g["layers"],
            num_heads=g["heads"], max_seq_len=g["seq"], recompute=True)),
            g["batch"], g["seq"], SingleDeviceSharding(four_chips[0]), patch)


def test_gpt2_train_step_runs_each_forward_kernel_once(gpt2_step):
    """The policy's names reach through the jitted kernel wrapper in the
    TPU's lowering, so the step holds one forward kernel a layer, and the
    logsumexp crosses to the backward as (B, H / 2, 2, S) rows, the two
    heads of a lane block together, tiled as they are (``T(2,128)``: 4 bytes
    a value, which is what ``flash_plan`` says)."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    g = GPT2_STEP
    assert _flash_calls(gpt2_step) == dict.fromkeys(
        ("flash_fwd", "flash_dq", "flash_dkv"), g["layers"])
    rows = re.findall(
        rf"= f32\[{_LSE}\]{{3,2,1,0:T\(2,128\)}} ", gpt2_step)
    assert len(rows) >= g["layers"], "the logsumexp is not kept as rows"
    assert fa.flash_plan(g["seq"], g["seq"], True, 2048, 2048, g["heads"],
                         64) == {
        "tiles": [1, 1], "sub_block": 256, "executed_share": 0.625,
        "heads_per_block": 2, "io_bytes": g["seq"] * 64 * 2,
        "stats_bytes": g["seq"] * 4}


def test_gpt2_train_step_lays_nothing_out_around_the_flash_calls(gpt2_step):
    """The kernels take their blocks from the (B, S, H*d) array the
    projections make and take: the compiled step holds no ``pad``, no 4-D
    (B, H, S, d) or (B, S, H, d) array and no transpose of one, and no
    reduction for delta (the backward kernels' own). What is left of XLA's
    layout work on a layer's attention arrays is its own choice for the kept
    ``out``: written S-minor for the out-projection's products, copied back
    for the two backward kernels (two copies a layer where there were 21,
    and 7 pads)."""
    g = GPT2_STEP
    b, s, h = g["batch"], g["seq"], g["heads"]
    found = _instructions(gpt2_step)
    assert not [n for n, (shape, op, _o) in found.items() if op == "pad"
                and "bf16" in shape]
    four_d = rf"bf16\[{b},(?:{s},{h}|{h},{s}),64\]"
    assert not re.findall(four_d, gpt2_step)
    big = rf"(?:bf16|f32)\[{b},{s},{h * 64}\]"
    copies = [n for n, (shape, op, _o) in found.items()
              if op == "copy" and re.search(big, shape)]
    assert len(copies) <= 2 * g["layers"], copies


def _instructions(entry):
    """``{name: (result shape, opcode, [operand names])}`` of a
    computation's text."""
    found = {}
    for line in entry.splitlines():
        named = re.match(r"\s*(?:ROOT )?%([\w.-]+) =( .*)", line)
        opcode = named and re.search(r" ([a-z][\w-]*)\(", named.group(2))
        if opcode:
            rest = named.group(2)
            found[named.group(1)] = (
                rest[:opcode.start()], opcode.group(1),
                re.findall(r"%([\w.-]+)", rest[opcode.end():]))
    return found


def test_gpt2_train_step_holds_the_row_statistics_as_rows(gpt2_step):
    """No float32 column (.., S, 1) exists anywhere in the step (a tile of
    8 x 128 to every 8 values: 64 MB a layer where the values are 512 KB),
    and the kept logsumexp goes from ``flash_fwd`` to ``flash_dq`` and
    ``flash_dkv`` as it is: nothing computes on it on the way (the parent's
    ``reduce`` to the row and ``copy`` back into the column, 5.4 ms of a
    GPT-2 medium step). The compiler's own prefetch of the row into the
    other memory space (``copy-start`` / ``copy-done``, asynchronous, the
    same shape and tiling) is not a layout copy and may stand."""
    g = GPT2_STEP
    assert not re.findall(rf"f32\[(?:\d+,)*{g['seq']},1\]", gpt2_step)
    found = _instructions(gpt2_step)
    users = {}
    for name, (_shape, _opcode, operands) in found.items():
        for operand in operands:
            users.setdefault(operand, []).append(name)
    passes_on = ("get-tuple-element", "bitcast", "copy-start", "copy-done")
    for fwd in (n for n in found if re.fullmatch(r"flash_fwd(\.\d+)?", n)):
        reached, todo = set(), [
            u for u in users[fwd] if found[u][1] == "get-tuple-element"
            and f"f32[{_LSE}]" in found[u][0]]
        assert len(todo) == 1, (fwd, todo)
        while todo:
            for user in users.get(todo.pop(), []):
                if found[user][1] in passes_on:
                    todo.append(user)
                else:
                    reached.add(user)
        assert {re.sub(r"\.\d+$", "", r) for r in reached} == {
            "flash_dq", "flash_dkv"}, (fwd, reached)


@pytest.mark.parametrize("chips", [1, 4], ids=["one-chip", "dp4"])
def test_head_and_loss_compile_chunk_by_chunk_at_the_fit_cells_shape(
        four_chips, monkeypatch, chips):
    """GPT-2 medium's head + loss, forward and backward, at 8 x 1024 rows a
    chip x 1024 x 50 257 under bf16 O1 autocast (``models/_head.py``): a
    scan of two chunks whose temporaries are a chunk's bf16 logits and the
    float32 table gradient, where the (8192, 50 257) float32 logits alone
    are 1.65 GB; over ``dp`` 4 a chip chunks its own rows, nothing is
    gathered, and the loss, the count and the table's gradient are each
    reduced once, outside the loop."""
    from paddle_tpu import amp
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.models._head import next_token_loss

    mesh = Mesh(np.asarray(four_chips[:chips]), ("dp",))
    monkeypatch.setattr(mesh_mod, "_global_mesh", mesh)
    rows, whole = NamedSharding(mesh, P("dp")), NamedSharding(mesh, P())

    def loss(h, table, ids):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return next_token_loss(Tensor(h), Tensor(table), Tensor(ids),
                                   transpose_y=True)._data

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        jax.ShapeDtypeStruct((8 * chips, 1024, 1024), jnp.float32,
                             sharding=rows),
        jax.ShapeDtypeStruct((50257, 1024), jnp.float32, sharding=whole),
        jax.ShapeDtypeStruct((8 * chips, 1024), jnp.int32,
                             sharding=rows)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 0.8e9
    text = compiled.as_text()
    assert "bf16[2,4096,1024]" in text      # the scan's rows: two chunks
    assert not re.search(r" all-(gather|to-all)(-start)?\(", text)
    reduces = re.findall(r" all-reduce(?:-start)?\(", text)
    assert len(reduces) == (3 if chips > 1 else 0)
    body = re.search(r" while\(.*?body=%([\w.-]+)", text).group(1)
    assert "all-reduce" not in re.search(
        rf"(?ms)^%{re.escape(body)} \(.*?^}}", text).group(0)


SMALLTHINKER_STEP = dict(seq=16384, window=4096, heads=2)


def test_smallthinker_16k_train_step_compiles_with_the_windowed_kernels(
        one_chip, monkeypatch):
    """``Engine``'s train step over a narrow SmallThinker (one full + NoPE
    layer, one 4096-window rotary layer, heads of 128, blocks
    rematerialised, bf16 O1 autocast) at the cell's 1 x 16 384 tokens,
    compiled for the described v5e: the full layer and the window layer
    each run the three flash kernels at the module's own 2048 tiles (what
    Mosaic refuses of a windowed tile it refuses here), the forward one once
    a layer (the block keeps the kernel's two residuals), the expert product
    takes its grouped form, and no array as large as S x S exists in the
    whole program."""
    from paddle_tpu.core import residuals
    from paddle_tpu.models import SmallThinkerForCausalLM, smallthinker_tiny
    from paddle_tpu.ops.pallas import flash_attention as fa

    g = SMALLTHINKER_STEP
    cfg = smallthinker_tiny(
        hidden_size=256, num_hidden_layers=2, num_attention_heads=g["heads"],
        num_key_value_heads=1, head_dim=128, sliding_window_size=g["window"],
        max_position_embeddings=g["seq"], moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, moe_ffn_hidden_size=128,
        experts_held=(0, 4), vocab_size=512, recompute=True)
    assert [blk.self_attn.window for blk in SmallThinkerForCausalLM(
        cfg).model.layers] == [None, g["window"]]
    with residuals.kept_residuals():    # a fresh log: none is open here
        whole = _compiled_train_step(SmallThinkerForCausalLM(cfg), 1,
                                     g["seq"], one_chip, monkeypatch,
                                     whole=True)
    text = whole[whole.index("ENTRY"):]
    assert _flash_calls(text) == dict.fromkeys(
        ("flash_fwd", "flash_dq", "flash_dkv"), 2)
    # the grouped matmuls run in the expert layers' loop bodies: two layers,
    # a loop forward and one backward each, none of them a branch
    comps = _computations(whole)
    bodies = _expert_loop_bodies(text)
    assert 2 * 2 <= len(bodies) <= 2 * 3
    assert all(re.findall(_GROUPED_KERNEL, comps[b]) for b in bodies)
    assert not re.findall(_GROUPED_KERNEL, text)
    assert not [line for line in whole.splitlines()
                if " conditional(" in line and "moe" in line]
    square = rf"\[(?:\d+,)*{g['seq']},{g['seq']}(?:,\d+)*\]"
    assert not re.findall(square, whole)
    # what the two calls were built with: the same 8 x 8 tiles of 2048, the
    # window's 21 of the 36 that the causal call runs
    full = fa.flash_plan(g["seq"], g["seq"], True, 2048, 2048, g["heads"],
                         128)
    band = fa.flash_plan(g["seq"], g["seq"], True, 2048, 2048, g["heads"],
                         128, window=g["window"])
    assert full["tiles"] == band["tiles"] == [8, 8]
    assert band["tiles_run"] == 21
    # the band's pairs are 0.4375 of the triangle's; in bands of 256 rows
    # and lanes of 128 keys the kernels run 0.458 of what the causal ones do
    assert band["executed_share"] / full["executed_share"] == pytest.approx(
        0.458, abs=0.002)
