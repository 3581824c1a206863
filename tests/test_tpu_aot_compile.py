"""Compile every Pallas entry point for the chip, without a chip.

``jax.experimental.topologies`` gives compile-only ``TPU v5 lite``
devices from libtpu; lowering a jitted function against them runs the
real Pallas->Mosaic and XLA:TPU compilers. That is the check the Pallas
interpreter cannot make (it accepted int64 index-map literals, 1-D
blocks XLA tiles differently, and un-partitioned kernels in sharded
programs — all of which the chip refuses). It says nothing about
numerics or speed: ``chip_smoke.py`` runs the same shapes on the chip
against the jnp references.

Shapes are the ones the 645M train step, the BERT step and the serving
engine use. Standalone (tier-1 may stop by timeout before this file):

    JAX_PLATFORMS=cpu python -m pytest tests/test_tpu_aot_compile.py -q
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

import paddle_tpu  # noqa: F401 — turns on jax_enable_x64, as every user does
from paddle_tpu.core.flags import pallas_mode_override

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        t = topologies.get_topology_desc(topology_name="v5e:2x2",
                                         platform="tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu on this host
        pytest.skip(f"no compile-only TPU topology: {type(e).__name__}: {e}")
    assert t.devices[0].device_kind == "TPU v5 lite"
    return t


@pytest.fixture(autouse=True)
def _compiled_mode():
    # the default backend here is the CPU: pin the kernels to Mosaic
    with pallas_mode_override("compiled"):
        yield


def _compile(fn, *specs):
    """Lower + compile ``fn`` for the specs' devices; returns the
    StableHLO text and the compiled executable."""
    lowered = jax.jit(fn).lower(*specs)
    return lowered.as_text(), lowered.compile()


def _on(topo, shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(
        shape, dtype,
        sharding=sharding or SingleDeviceSharding(topo.devices[0]))


def _flash_fwd_bwd(causal, rate=0.0, bias=False, partition=None):
    from paddle_tpu.ops.pallas.flash_attention import (_flash_bwd_bhsd,
                                                       _flash_fwd_bhsd)

    def fn(q, k, v, do, *extras):
        extras = list(extras)
        key_bias = extras.pop(0) if bias else None
        seed = extras.pop(0) if rate else None
        kw = dict(causal=causal, scale=q.shape[-1] ** -0.5,
                  dropout_rate=rate, partition=partition)
        out, lse = _flash_fwd_bhsd(q, k, v, seed, key_bias, **kw)
        return out, _flash_bwd_bhsd(q, k, v, out, lse, do, seed, key_bias,
                                    **kw)

    return fn


@pytest.mark.parametrize("shape,kv_heads,causal,rate", [
    ((4, 16, 2048, 128), 16, True, 0.0),     # 645M train step
    ((36, 12, 512, 64), 12, False, 0.0),     # BERT-base
    ((36, 12, 512, 64), 12, False, 0.1),     # ... with in-kernel dropout
    ((8, 16, 128, 128), 16, True, 0.0),      # short prefill
    ((4, 16, 2048, 128), 4, True, 0.0),      # GQA 16/4
])
def test_flash_fwd_bwd_compiles(topo, shape, kv_heads, causal, rate):
    q = _on(topo, shape, BF16)
    kv = _on(topo, (shape[0], kv_heads) + shape[2:], BF16)
    extras = [_on(topo, (1,), jnp.int32)] if rate else []
    text, _ = _compile(_flash_fwd_bwd(causal, rate), q, kv, kv, q, *extras)
    assert text.count("tpu_custom_call") == 2   # fwd, the one backward pass


def _train_attention(q, k, v, do):
    """The train cell's attention as a layer runs it: [B, S, H, D] in,
    (out, dq, dk, dv) out, through the primitive's forward and vjp."""
    from paddle_tpu.ops.pallas.flash_attention import (_flash_vjp,
                                                       flash_attention_bshd)

    scale = q.shape[-1] ** -0.5
    out, lse = flash_attention_bshd(q, k, v, causal=True, scale=scale)
    return out, _flash_vjp((do,), (q, k, v, out, lse), causal=True,
                           scale=scale)[:3]


def test_train_attention_is_one_backward_pass_in_the_models_layout(topo):
    """cgpt590m-train-2k's shape: the backward is the one kernel named
    ``flash_bwd_dkv`` (the name ``benchmark/metrics/flash_roofline.json``
    reads), no lane-padded [B, H, S, 128] float32 statistic is made, and
    with heads of 128 nothing q-shaped is transposed between the
    projections."""
    x = _on(topo, (4, 2048, 12, 128), BF16)
    text, compiled = _compile(_train_attention, x, x, x, x)
    assert text.count("tpu_custom_call") == 2
    hlo = compiled.as_text()
    assert "flash_fwd" in hlo and "flash_bwd_dkv" in hlo
    assert "flash_bwd_dq" not in hlo
    assert "4x12x2048x128xf32" not in text
    moved = [l for l in text.splitlines()
             if "stablehlo.transpose" in l and "x128xbf16" in l]
    assert not moved, moved


@pytest.mark.parametrize("seq,form", [(8192, "single"), (16384, "split")])
def test_backward_form_follows_the_dq_accumulators_size(topo, seq, form):
    """The longest sequence the single pass takes at heads of 128 (a
    4 MiB accumulator) compiles; twice that keeps the two kernels."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    assert fa._single_pass_fits(seq, 128) == (form == "single")
    q = _on(topo, (1, 2, seq, 128), BF16)
    _, compiled = _compile(_flash_fwd_bwd(True), q, q, q, q)
    assert ("flash_bwd_dq" in compiled.as_text()) == (form == "split")


def test_flash_key_bias_compiles(topo):
    # the padding-mask route, taken at Sk >= _MASK_FLASH_MIN_SK
    q = _on(topo, (8, 12, 1024, 64), BF16)
    for b in (8, 1):
        bias = _on(topo, (b, 1024), jnp.float32)
        _compile(_flash_fwd_bwd(False, bias=True), q, q, q, q, bias)


def test_flash_varlen_compiles(topo):
    from paddle_tpu.ops.pallas.flash_attention_varlen import (_vflash_bwd,
                                                              _vflash_fwd)

    def fn(q, k, v, cu, do):
        kw = dict(causal=True, scale=128 ** -0.5, n_seqs=5, interpret=False)
        out, lse = _vflash_fwd(q, k, v, cu, cu, **kw)
        return out, _vflash_bwd(q, k, v, cu, cu, out, lse, do, **kw)

    q = _on(topo, (16, 8192, 128), BF16)
    cu = _on(topo, (6,), jnp.int32)
    text, _ = _compile(fn, q, q, q, cu, q)
    assert text.count("tpu_custom_call") == 3


@pytest.mark.parametrize("shape", [(4, 2048, 2048), (8, 2048)])
def test_rms_norm_fwd_bwd_compiles(topo, shape):
    from paddle_tpu.ops.pallas.rms_norm import _rms_bwd, _rms_fwd

    def fn(x, w, g):
        return _rms_fwd(x, w, eps=1e-6), _rms_bwd(x, w, g, eps=1e-6)

    x = _on(topo, shape, BF16)
    text, _ = _compile(fn, x, _on(topo, shape[-1:], BF16), x)
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("slots,heads,kv_heads,blocks,width", [
    (8, 16, 16, 96, 8),          # a small engine, and with GQA 16/4
    (8, 16, 4, 96, 8),
    (128, 12, 12, 736, 16),      # the Cerebras-GPT cells' decode step
    (128, 64, 8, 4096, 64),      # K-EXAONE's full layer, max_seq_len 8,192
])
def test_paged_decode_compiles(topo, slots, heads, kv_heads, blocks, width):
    """The decode kernel over pages of 128, up to the benchmark cells'
    pools and tables: the pools stay in HBM (``pl.ANY``), so the compiled
    call holds no copy of one, and the kernel keeps the name its
    roofline's readers look for."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel)

    pool = _on(topo, (kv_heads, blocks, 128, 128), BF16)
    text, compiled = _compile(
        paged_attention_decode_kernel, _on(topo, (slots, heads, 128), BF16),
        pool, pool, _on(topo, (slots,), jnp.int32),
        _on(topo, (slots, width), jnp.int32))
    assert "tpu_custom_call" in text and "paged_decode" in text
    assert not _pool_sized_copies(compiled.as_text(), pool.shape)


def _pool_sized_copies(hlo, pool_shape):
    """``copy`` instructions of a compiled text whose result holds as many
    elements as the pool, whatever view of it they copy."""
    size = int(np.prod(pool_shape))
    found = []
    for line in hlo.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line)
        if m and np.prod([int(d) for d in m.group(1).split(",")]) >= size:
            found.append(line.strip()[:160])
    return found


def _aliased_params(hlo):
    """Parameter numbers the compiled module aliases to a result."""
    m = re.search(r"input_output_alias=\{(.*?)\}, entry_computation_layout",
                  hlo)
    return ({int(p) for p in re.findall(r"\}: \((\d+),", m.group(1))}
            if m else set())


@pytest.mark.parametrize("kv_heads", [12, 8])
def test_kv_write_compiles(topo, kv_heads):
    """The in-place K/V write at the benchmark cell's pool, rows (the
    decode step's 128, a suffix prefill's 2048) and blocks: Mosaic takes
    the kernel, no path copies the pool and each returns its argument's
    buffer."""
    from paddle_tpu.ops.pallas.kv_write import (kv_write_blocks,
                                                kv_write_kernel)

    pool = _on(topo, (kv_heads, 736, 128, 128), BF16)
    for fn, n_rows in ((kv_write_kernel, 128), (kv_write_kernel, 2048),
                       (kv_write_blocks, 2048)):
        lowered = jax.jit(fn, donate_argnums=(0,)).lower(
            pool, _on(topo, (n_rows, kv_heads, 128), BF16),
            _on(topo, (n_rows,), jnp.int32))
        assert ("tpu_custom_call" in lowered.as_text()) == (
            fn is kv_write_kernel)
        hlo = lowered.compile().as_text()
        assert not _pool_sized_copies(hlo, pool.shape)
        assert _aliased_params(hlo) == {0}


def test_engine_programs_copy_no_pool(topo):
    """Two layers at the benchmark cell's serving geometry (12 kv heads of
    128, 736 blocks of 128, 128 slots, 2048 positions): no compiled step
    of ``ServeEngine`` holds a ``copy`` the size of a K or V pool, and
    every donated pool is aliased to its result. With the scatter on the
    pool's flat view (the ``reference`` write) XLA:TPU re-laid each pool
    out and back in every program: 76% of both serving cells' device
    time (PERF.md, PR 26). This keeps the copies from coming back."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serve import ServeEngine

    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=50257, hidden_size=1536, num_hidden_layers=2,
        num_attention_heads=12, intermediate_size=6144,
        max_position_embeddings=2048, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0))
    model.eval()
    model.to(dtype="bfloat16")
    eng = ServeEngine(model, max_slots=128, block_size=128, num_blocks=736,
                      max_seq_len=2048, prefix_cache=True, name="aot-pool",
                      trace=False, slo=False)
    assert eng.attention_backend == "kernel"
    pool = (12, 736, 128, 128)
    assert eng._caches[0][0].shape == pool
    lowered = eng.lowered(prompt_lens=(64, 2048), suffix_lens=(64,),
                          bursts=(2,), device=topo.devices[0])
    assert sorted(lowered) == ["burst.2", "decode", "prefill.2048",
                               "prefill.64", "suffix_prefill.64"]
    # the caches are the flat arguments after the weights' leaves
    n_arrays = len(jax.tree.leaves(eng._arrays))
    caches = set(range(n_arrays, n_arrays + 4))
    for name, low in lowered.items():
        hlo = low.compile().as_text()
        assert not _pool_sized_copies(hlo, pool), name
        assert caches <= _aliased_params(hlo), name
        assert "kv_write" in hlo, name


def _dispatch_ops(hlo, kind):
    """The instructions of ``kind`` (``sort``, ``gather``, ``scatter``)
    that the compiled text puts down to a ``moe/dispatch`` scope."""
    return [line.strip()[:160] for line in hlo.splitlines()
            if "moe/dispatch" in line and re.search(rf" {kind}\(", line)]


@pytest.mark.parametrize("tokens", [32, 128, 2048])
def test_moe_experts_compiles(topo, tokens):
    """The held experts' products at the published widths (16 experts of
    [6144, 4096] gate-up and [2048, 6144] down): a decode step's 32 x 8
    (GLM-5) and 128 x 8 (K-EXAONE) rows on the direct path, whose
    dispatch sorts and gathers nothing, and a prefill piece's 2,048 x 8
    through the grouped buffer: Mosaic takes both kernels, the group
    sizes being data, two calls a layer either way."""
    from paddle_tpu.ops.pallas.moe_experts import (buffer_rows, experts_ffn,
                                                   takes_direct)

    def fn(x, w, experts, gate_up, down):
        return experts_ffn(x, w, experts, gate_up, down, first=0,
                           scope="moe")

    text, compiled = _compile(
        fn, _on(topo, (tokens, 6144), BF16),
        _on(topo, (tokens, 8), jnp.float32), _on(topo, (tokens, 8), jnp.int32),
        _on(topo, (16, 6144, 4096), BF16), _on(topo, (16, 2048, 6144), BF16))
    assert text.count("tpu_custom_call") == 2 and "moe_experts" in text
    hlo = compiled.as_text()
    direct = takes_direct(tokens, "kernel")
    assert direct == (tokens <= 128)
    for kind in ("sort", "gather"):
        assert bool(_dispatch_ops(hlo, kind)) != direct, kind
    assert buffer_rows(tokens, 8, 16, 128) == tokens * 8 + 16 * 128
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_paged_decode_window_ring_compiles(topo):
    """The decode kernel with a lower bound over a ring of two pages, at
    64 query heads over 8 key-value heads."""
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel)

    def fn(q, k, v, lengths, rings, starts):
        return paged_attention_decode_kernel(q, k, v, lengths, rings,
                                             starts=starts, ring=True)

    text, _ = _compile(
        fn, _on(topo, (128, 64, 128), BF16),
        _on(topo, (8, 256, 128, 128), BF16),
        _on(topo, (8, 256, 128, 128), BF16), _on(topo, (128,), jnp.int32),
        _on(topo, (128, 2), jnp.int32), _on(topo, (128,), jnp.int32))
    assert "tpu_custom_call" in text and "paged_decode" in text


def test_flash_forward_band_compiles(topo):
    """The banded causal forward of a window layer's prefill at the
    largest bucket: [1, 64, 8192, 128] over 8 key-value heads, window 128."""
    from paddle_tpu.ops.pallas.flash_attention import _flash_fwd_bhsd

    def fn(q, k, v):
        return _flash_fwd_bhsd(q, k, v, causal=True, scale=128 ** -0.5,
                               window=128)

    text, _ = _compile(fn, _on(topo, (1, 64, 8192, 128), BF16),
                       _on(topo, (1, 8, 8192, 128), BF16),
                       _on(topo, (1, 8, 8192, 128), BF16))
    assert "tpu_custom_call" in text and "flash_fwd" in text


def _copies_shaped_like(hlo, pool_shape):
    """``copy`` instructions whose result has the pool's dimensions, in
    whatever order (a prompt's activations are larger than a ring pool,
    so size alone does not tell them apart)."""
    want = sorted(pool_shape)
    found = []
    for line in hlo.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line)
        if m and sorted(int(d) for d in m.group(1).split(",")) == want:
            found.append(line.strip()[:160])
    return found


def test_exaone_engine_programs_copy_no_pool(topo):
    """Two layers of EXAONE-MoE at the published widths and the cell's
    geometry (a dense sliding layer over a ring pool, a sparse full
    layer over the block table): the decode step and the 64 and 2,048
    prefill buckets hold no ``copy`` shaped like either pool, alias all
    four donated pools, and run ``kv_write``, ``paged_decode``,
    ``moe_experts`` and (prefill) ``flash_fwd``; the experts of the decode
    step and the 64 bucket take the direct path, the 2,048 bucket's the
    grouped buffer."""
    from paddle_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                              ExaoneMoeForCausalLM)
    from paddle_tpu.serve import ServeEngine

    model = ExaoneMoeForCausalLM(ExaoneMoeConfig(
        vocab_size=19200, num_hidden_layers=2,
        layer_types=("sliding_attention", "full_attention"),
        experts_held=(0, 16), dtype="bfloat16", deferred_init=True))
    model.eval()
    eng = ServeEngine(model, max_slots=128, block_size=128, num_blocks=1024,
                      max_seq_len=8192, name="aot-exaone", trace=False,
                      slo=False)
    assert eng.attention_backend == "kernel" and eng.ring_blocks == 2
    ring, table = (8, 256, 128, 128), (8, 1024, 128, 128)
    assert [c[0].shape for c in eng._caches] == [ring, table]
    lowered = eng.lowered(prompt_lens=(64, 2048), device=topo.devices[0])
    n_arrays = len(jax.tree.leaves(eng._arrays))
    caches = set(range(n_arrays, n_arrays + 4))
    for name, low in lowered.items():
        hlo = low.compile().as_text()
        for pool in (ring, table):
            assert not _copies_shaped_like(hlo, pool), name
        assert not _pool_sized_copies(hlo, table), name
        assert caches <= _aliased_params(hlo), name
        for kernel in ("kv_write", "moe_experts") + (
                ("paged_decode",) if name == "decode" else ("flash_fwd",)):
            assert kernel in hlo, (name, kernel)
        # 128 decode rows and a 64 bucket fit one tile: the direct path,
        # whose dispatch neither sorts nor gathers; 2,048 rows do not
        grouped = name == "prefill.2048"
        for kind in ("sort", "gather"):
            assert bool(_dispatch_ops(hlo, kind)) == grouped, (name, kind)


def test_ssm_decode_compiles_in_place(topo):
    """The recurrent step at the Granite cell's state, 96 slots of 64
    heads of [64, 128] in bfloat16 (laid out two heads a lane row,
    ``[96, 32, 128, 128]``): Mosaic takes the kernel (the row ids in SMEM
    driving the block index maps), the donated state is aliased to the
    result and no ``copy`` of its size is in the compiled text."""
    from paddle_tpu.ops.pallas.ssm_decode import (ssm_decode_kernel,
                                                  state_shape)

    assert state_shape(96, 64, 64, 128) == (96, 32, 128, 128)
    state = _on(topo, (96, 32, 128, 128), BF16)
    lowered = jax.jit(ssm_decode_kernel, donate_argnums=(0,)).lower(
        state, _on(topo, (96, 64, 64), BF16),
        _on(topo, (96, 64), jnp.float32), _on(topo, (64,), jnp.float32),
        _on(topo, (96, 128), BF16), _on(topo, (96, 128), BF16),
        _on(topo, (96,), jnp.bool_))
    text = lowered.as_text()
    assert "tpu_custom_call" in text and "ssm_decode" in text
    hlo = lowered.compile().as_text()
    assert not _pool_sized_copies(hlo, state.shape)
    assert 0 in _aliased_params(hlo)


def test_flash_forward_heads_of_64_compiles(topo):
    """The causal forward of a prompt's attention at head size 64, 32
    query heads over 8 key-value heads (half a lane tile a head), at the
    largest bucket of the Granite cell."""
    from paddle_tpu.ops.pallas.flash_attention import _flash_fwd_bhsd

    def fn(q, k, v):
        return _flash_fwd_bhsd(q, k, v, causal=True, scale=1 / 64)

    text, _ = _compile(fn, _on(topo, (1, 32, 4096, 64), BF16),
                       _on(topo, (1, 8, 4096, 64), BF16),
                       _on(topo, (1, 8, 4096, 64), BF16))
    assert "tpu_custom_call" in text and "flash_fwd" in text


def test_heads_of_64_do_not_slice_a_lane_tile(topo):
    """Why the engine packs two heads of 64 into a pool row: written as
    they come, ``[8, blocks, 128, 64]``, the pool is padded to 128 lanes
    on the device and Mosaic refuses ``kv_write``'s slice of 64 of them."""
    from paddle_tpu.ops.pallas.kv_write import kv_write_kernel

    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(kv_write_kernel, _on(topo, (8, 64, 128, 64), BF16),
                 _on(topo, (96, 8, 64), BF16), _on(topo, (96,), jnp.int32))


def test_granite_engine_programs_keep_state_and_pools_in_place(topo):
    """Three layers of Granite 4.0-H at the published widths and the
    cell's geometry (a Mamba-2 layer, an attention layer with heads of 64,
    a Mamba-2 layer): the attention layer's pools hold two heads a row
    (``[4, 1536, 128, 128]``: 1.6 GB over the model's four such layers
    where ``[8, 1536, 128, 64]`` would be padded to 3.2), the decode step
    and the 64 and 1,024 prefill buckets hold no ``copy`` the size of a
    state or shaped like a pool, every donated cache is aliased to its
    result, and the kernels are there by name."""
    from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                  GraniteHybridForCausalLM)
    from paddle_tpu.serve import ServeEngine

    model = GraniteHybridForCausalLM(GraniteHybridConfig(
        num_hidden_layers=3, layer_types=("mamba", "attention", "mamba"),
        dtype="bfloat16", deferred_init=True))
    model.eval()
    eng = ServeEngine(model, max_slots=96, block_size=128, num_blocks=1536,
                      max_seq_len=4096, name="aot-granite", trace=False,
                      slo=False)
    assert eng.attention_backend == "kernel" and eng._pack == 2
    state, tail, pool = (96, 32, 128, 128), (3, 96, 4352), (4, 1536, 128, 128)
    assert [tuple(a.shape for a in c) for c in eng._caches] == [
        (tail, state), (pool, pool), (tail, state)]
    lowered = eng.lowered(prompt_lens=(64, 1024), device=topo.devices[0])
    n_arrays = len(jax.tree.leaves(eng._arrays))
    caches = set(range(n_arrays, n_arrays + 6))
    for name, low in lowered.items():
        hlo = low.compile().as_text()
        assert not _pool_sized_copies(hlo, state), name
        assert not _copies_shaped_like(hlo, pool), name
        assert caches <= _aliased_params(hlo), name
        for kernel in ("kv_write",) + (
                ("ssm_decode", "paged_decode") if name == "decode"
                else ("flash_fwd",)):
            assert kernel in hlo, (name, kernel)


def test_ssm_decode_compiles_at_128_heads(topo):
    """The recurrent step at Granite 4.0-H Small's state, 128 slots of 128
    heads of [64, 128] in bfloat16 (``[128, 64, 128, 128]``: a row's block
    is 2 MiB, in and out double-buffered about 8 MiB of VMEM, its body 64
    groups unrolled): Mosaic takes it with the one row a grid step, in
    place, with no copy of the state."""
    from paddle_tpu.ops.pallas.ssm_decode import (ssm_decode_kernel,
                                                  state_shape)

    assert state_shape(128, 128, 64, 128) == (128, 64, 128, 128)
    state = _on(topo, (128, 64, 128, 128), BF16)
    lowered = jax.jit(ssm_decode_kernel, donate_argnums=(0,)).lower(
        state, _on(topo, (128, 128, 64), BF16),
        _on(topo, (128, 128), jnp.float32), _on(topo, (128,), jnp.float32),
        _on(topo, (128, 128), BF16), _on(topo, (128, 128), BF16),
        _on(topo, (128,), jnp.bool_))
    assert "ssm_decode" in lowered.as_text()
    hlo = lowered.compile().as_text()
    assert not _pool_sized_copies(hlo, state.shape)
    assert 0 in _aliased_params(hlo)


def test_moe_experts_compiles_at_expert_width_768(topo):
    """Granite 4.0-H Small's held experts: 36 of [4096, 1536] gate-up and
    [768, 4096] down at a decode step's 128 x 10 rows (the down product's
    contraction, 768, one k tile), on the direct path: no sort and no
    gather in the dispatch."""
    from paddle_tpu.ops.pallas.moe_experts import experts_ffn

    tokens = 128

    def fn(x, w, experts, gate_up, down):
        return experts_ffn(x, w, experts, gate_up, down, first=0,
                           scope="moe")

    text, compiled = _compile(
        fn, _on(topo, (tokens, 4096), BF16),
        _on(topo, (tokens, 10), jnp.float32),
        _on(topo, (tokens, 10), jnp.int32),
        _on(topo, (36, 4096, 1536), BF16), _on(topo, (36, 768, 4096), BF16))
    assert text.count("tpu_custom_call") == 2 and "moe_experts" in text
    hlo = compiled.as_text()
    assert not _dispatch_ops(hlo, "sort") and not _dispatch_ops(hlo, "gather")
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_granite_moe_engine_programs_keep_state_and_pools_in_place(topo):
    """Two layers of Granite 4.0-H Small at the published widths (a Mamba-2
    layer of 128 heads, the attention layer with heads of 128, each with
    its expert layer: 36 held of 72, top 10, and the shared expert) at the
    cell's slots: heads of 128 take the unpacked pool (``[8, blocks, 128,
    128]``), the decode step runs ``ssm_decode``, ``paged_decode`` and
    ``moe_experts`` and the 64 bucket ``flash_fwd`` and ``moe_experts``
    (both on the direct path: no sort and no gather in the dispatch), no
    program copies a state or a pool, and every donated cache is aliased
    to its result."""
    from paddle_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                  GraniteHybridForCausalLM)
    from paddle_tpu.serve import ServeEngine

    model = GraniteHybridForCausalLM(GraniteHybridConfig(
        vocab_size=50176, hidden_size=4096, intermediate_size=768,
        shared_intermediate_size=1536, num_hidden_layers=2,
        layer_types=("mamba", "attention"), mamba_n_heads=128,
        attention_multiplier=1 / 128, logits_scaling=16.0,
        num_local_experts=72, num_experts_per_tok=10, experts_held=(0, 36),
        dtype="bfloat16", deferred_init=True))
    model.eval()
    eng = ServeEngine(model, max_slots=128, block_size=128, num_blocks=256,
                      max_seq_len=4096, name="aot-granite-moe", trace=False,
                      slo=False)
    assert eng.attention_backend == "kernel" and eng._pack == 1
    state, tail, pool = (128, 64, 128, 128), (3, 128, 8448), (8, 256, 128,
                                                                128)
    assert [tuple(a.shape for a in c) for c in eng._caches] == [
        (tail, state), (pool, pool)]
    lowered = eng.lowered(prompt_lens=(64,), device=topo.devices[0])
    n_arrays = len(jax.tree.leaves(eng._arrays))
    caches = set(range(n_arrays, n_arrays + 4))
    for name, low in lowered.items():
        hlo = low.compile().as_text()
        assert not _pool_sized_copies(hlo, state), name
        assert not _copies_shaped_like(hlo, pool), name
        assert caches <= _aliased_params(hlo), name
        for kernel in ("kv_write", "moe_experts") + (
                ("ssm_decode", "paged_decode") if name == "decode"
                else ("flash_fwd",)):
            assert kernel in hlo, (name, kernel)
        assert not _dispatch_ops(hlo, "sort"), name        # the direct path
        assert not _dispatch_ops(hlo, "gather"), name


def test_mla_decode_compiles(topo):
    """The latent decode kernel at the DeepSeek-V2 cell's shapes: 192
    rows of 128 absorbed queries over a pool of 6,656 pages of 128 rows
    in 640 lanes (a 576-wide row in five lane tiles), the value the first
    512 lanes, eight pages a turn: the unmasked turn in two blocks of
    four pages and a last turn of each size from one page to eight."""
    from paddle_tpu.ops.pallas.mla_decode import mla_decode_kernel

    def fn(q, pool, lengths, tables):
        return mla_decode_kernel(q, pool, lengths, tables, dv=512,
                                 sm_scale=0.1147)

    text, compiled = _compile(
        fn, _on(topo, (192, 128, 640), BF16),
        _on(topo, (1, 6656, 128, 640), BF16), _on(topo, (192,), jnp.int32),
        _on(topo, (192, 64), jnp.int32))
    assert "tpu_custom_call" in text and "mla_decode" in text
    assert not _pool_sized_copies(compiled.as_text(), (1, 6656, 128, 640))


@pytest.mark.parametrize("tables", [16, 5, 1])
def test_mla_decode_compiles_where_a_turn_is_narrower(topo, tables):
    """The same kernel over tables of two turns, of five pages (a turn is
    the table: a block of four pages and one of one) and of one page: the
    forms of turn a short ``max_seq_len`` makes."""
    from paddle_tpu.ops.pallas.mla_decode import mla_decode_kernel

    text, _ = _compile(
        lambda q, pool, lengths, t: mla_decode_kernel(
            q, pool, lengths, t, dv=512, sm_scale=0.1147),
        _on(topo, (192, 128, 640), BF16),
        _on(topo, (1, 6656, 128, 640), BF16), _on(topo, (192,), jnp.int32),
        _on(topo, (192, tables), jnp.int32))
    assert "tpu_custom_call" in text and "mla_decode" in text


@pytest.mark.parametrize("rows, fresh", [(192, False), (2048, True)])
def test_latent_row_write_compiles_in_place(topo, rows, fresh):
    """A decode step's 192 latent rows (the ``rows`` kernel) and a
    prompt's 2,048 (whole blocks) into the 640-lane pool: aliased, no
    pool-sized copy."""
    from paddle_tpu.ops.pallas.kv_write import kv_write

    pool = _on(topo, (1, 6656, 128, 640), BF16)
    lowered = jax.jit(
        lambda pool, new, slots: kv_write(pool, new, slots,
                                          rows_start_blocks=fresh),
        donate_argnums=(0,)).lower(
            pool, _on(topo, (rows, 1, 640), BF16),
            _on(topo, (rows,), jnp.int32))
    hlo = lowered.compile().as_text()
    assert not _pool_sized_copies(hlo, pool.shape)
    assert 0 in _aliased_params(hlo)
    assert ("tpu_custom_call" in hlo) == (not fresh)   # rows | blocks


def test_a_latent_row_of_576_lanes_is_refused(topo):
    """Why the engine pads a latent row to 640 lanes: a pool written as
    the row comes, ``[1, blocks, 128, 576]``, lies in 640 lanes on the
    device all the same (4.5 tiles round up), and Mosaic refuses both
    kernels' slices of 576 of them."""
    from paddle_tpu.ops.pallas.kv_write import kv_write_kernel
    from paddle_tpu.ops.pallas.mla_decode import mla_decode_kernel

    pool = _on(topo, (1, 6656, 128, 576), BF16)
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(kv_write_kernel, pool, _on(topo, (192, 1, 576), BF16),
                 _on(topo, (192,), jnp.int32))
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(lambda q, pool, l, t: mla_decode_kernel(
            q, pool, l, t, dv=512, sm_scale=0.1),
            _on(topo, (192, 128, 576), BF16), pool,
            _on(topo, (192,), jnp.int32), _on(topo, (192, 64), jnp.int32))


@pytest.mark.parametrize("seq", [512, 8192])
def test_flash_forward_two_head_sizes_compiles(topo, seq):
    """The causal forward of a latent-attention prefill: 128 heads, a
    query-key head of 192 and a value head of 128, at a short bucket and
    the cell's largest."""
    from paddle_tpu.ops.pallas.flash_attention import _flash_fwd_bhsd

    def fn(q, k, v):
        return _flash_fwd_bhsd(q, k, v, causal=True, scale=0.1147)[0]

    text, compiled = _compile(fn, _on(topo, (1, 128, seq, 192), BF16),
                              _on(topo, (1, 128, seq, 192), BF16),
                              _on(topo, (1, 128, seq, 128), BF16))
    assert "tpu_custom_call" in text and "flash_fwd" in text
    assert jax.eval_shape(fn, *(jax.ShapeDtypeStruct(s, BF16) for s in (
        (1, 128, seq, 192), (1, 128, seq, 192), (1, 128, seq, 128)))
        ).shape == (1, 128, seq, 128)


def test_deepseek_engine_programs_keep_the_latent_pools_in_place(topo):
    """Two layers of DeepSeek-V2 at the published widths and the cell's
    geometry (the dense layer and a sparse one holding routing group 0):
    one pool a layer of 640-lane rows, the decode step and the 64 and
    2,048 prefill buckets hold no ``copy`` shaped like a pool, alias both
    donated pools, and run ``kv_write``, ``moe_experts`` and
    ``mla_decode`` (decode) or ``flash_fwd`` (prefill); no program keeps
    a per-head key or value of the pool's 6,656 blocks."""
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)
    from paddle_tpu.serve import ServeEngine

    model = DeepseekV2ForCausalLM(DeepseekV2Config(
        vocab_size=12800, num_hidden_layers=2, experts_held=(0, 20),
        dtype="bfloat16", deferred_init=True))
    model.eval()
    eng = ServeEngine(model, max_slots=192, block_size=128, num_blocks=6656,
                      max_seq_len=8192, name="aot-deepseek", trace=False,
                      slo=False)
    assert eng.attention_backend == "kernel"
    pool = (1, 6656, 128, 640)
    assert [tuple(a.shape for a in c) for c in eng._caches] == [(pool,)] * 2
    lowered = eng.lowered(prompt_lens=(64, 2048), device=topo.devices[0])
    n_arrays = len(jax.tree.leaves(eng._arrays))
    caches = set(range(n_arrays, n_arrays + 2))
    for name, low in lowered.items():
        hlo = low.compile().as_text()
        assert not _copies_shaped_like(hlo, pool), name
        assert caches <= _aliased_params(hlo), name
        for kernel in ("moe_experts",) + (
                ("kv_write", "mla_decode") if name == "decode"
                else ("flash_fwd",)):
            assert kernel in hlo, (name, kernel)
        assert not re.search(r"\[(?:\d+,)*6656,(?:\d+,)*128,(?:128|192|256)\]",
                             hlo.replace("[1,6656,128,640]", "")), name


def test_dsa_index_compiles(topo):
    """The lightning indexer's decode kernel at the GLM-5 cell's shapes: 32
    rows of 32 index queries of 128 over a pool of 4,096 pages of 128
    index keys, tables of 160 pages, sixteen pages a turn."""
    from paddle_tpu.ops.pallas.dsa_index import dsa_index_kernel

    text, compiled = _compile(
        lambda q, w, pool, lengths, tables: dsa_index_kernel(
            q, w, pool, lengths, tables, scale=128 ** -0.5),
        _on(topo, (32, 32, 128), BF16), _on(topo, (32, 32), jnp.float32),
        _on(topo, (1, 4096, 128, 128), BF16), _on(topo, (32,), jnp.int32),
        _on(topo, (32, 160), jnp.int32))
    assert "tpu_custom_call" in text and "dsa_index" in text
    assert not _pool_sized_copies(compiled.as_text(), (1, 4096, 128, 128))


def test_mla_sparse_decode_compiles(topo):
    """Absorbed attention over the selected rows at the cell's shapes: 32
    rows of 64 queries, ``keep`` rows over 160 pages a stream, a pool
    of 4,096 pages of 640-lane rows walked in place and never copied
    whole."""
    from paddle_tpu.ops.pallas.mla_sparse_decode import (
        mla_sparse_decode_kernel)

    text, compiled = _compile(
        lambda q, pool, keep, lengths, tables: mla_sparse_decode_kernel(
            q, pool, keep, lengths, tables, dv=512, sm_scale=256 ** -0.5),
        _on(topo, (32, 64, 640), BF16), _on(topo, (1, 4096, 128, 640), BF16),
        _on(topo, (32, 1, 20480), jnp.float32), _on(topo, (32,), jnp.int32),
        _on(topo, (32, 160), jnp.int32))
    assert "tpu_custom_call" in text and "mla_sparse_decode" in text
    assert not _pool_sized_copies(compiled.as_text(), (1, 4096, 128, 640))


def test_dsa_select_compiles(topo):
    """A decode step's selection in VMEM at the GLM-5 cell's shape: 32
    streams' scores over tables of 160 pages of 128, written as the
    ``keep`` rows ``mla_sparse_decode`` reads, with no copy between."""
    from paddle_tpu.ops.pallas.dsa_select import dsa_select_kernel
    from paddle_tpu.ops.pallas.mla_sparse_decode import (
        mla_sparse_decode_kernel)

    def fn(scores, lengths, q, pool, tables):
        keep = dsa_select_kernel(scores, lengths, topk=2048)
        return mla_sparse_decode_kernel(q, pool, keep, lengths, tables,
                                        dv=512, sm_scale=256 ** -0.5)

    text, compiled = _compile(
        fn, _on(topo, (32, 20480), jnp.float32), _on(topo, (32,), jnp.int32),
        _on(topo, (32, 64, 640), BF16), _on(topo, (1, 4096, 128, 640), BF16),
        _on(topo, (32, 160), jnp.int32))
    assert "dsa_select" in text and "mla_sparse_decode" in text
    hlo = compiled.as_text()
    assert not re.search(r"= f32\[32,1,20480\]\S* (copy|fusion)\(", hlo)


@pytest.mark.parametrize("seq", [8192, 16384, 20480])
def test_dsa_prefill_select_compiles(topo, seq):
    """A prompt's selection scored and selected in VMEM at the GLM-5
    cell's buckets past the 2,048 selected (32 index heads of 128,
    bfloat16): blocks of 256 queries while a block's float32 keys fit 16
    MiB, of 128 at 20,480, each under the VMEM limit it asks for."""
    from paddle_tpu.ops.pallas.dsa_select import (dsa_prefill_select_kernel,
                                                  prefill_block,
                                                  prefill_vmem_bytes)

    block = prefill_block(seq)
    assert block == (128 if seq > 16384 else 256)
    # Mosaic refuses a kernel whose buffers pass the limit it is given
    assert prefill_vmem_bytes(seq, 32, 128, BF16, block) < 64 << 20
    text, compiled = _compile(
        lambda q, w, k: dsa_prefill_select_kernel(q, w, k, 2048,
                                                  128 ** -0.5),
        _on(topo, (seq, 32, 128), BF16), _on(topo, (seq, 32), jnp.float32),
        _on(topo, (seq, 128), BF16))
    assert "tpu_custom_call" in text and "dsa_prefill_select" in text


@pytest.mark.parametrize("seq", [4096, 20480])
def test_flash_forward_under_a_selection_compiles(topo, seq):
    """The causal forward of a DSA prefill: 64 heads of 256 and 256 under
    an int8 ``[S, S]`` selection mask, at a bucket past the 2,048 selected
    and at the cell's largest."""
    from paddle_tpu.ops.pallas.flash_attention import _flash_fwd_bhsd

    def fn(q, k, v, sel):
        return _flash_fwd_bhsd(q, k, v, causal=True, scale=0.0625,
                               select=sel)[0]

    text, _ = _compile(fn, *(_on(topo, (1, 64, seq, 256), BF16)
                             for _ in range(3)),
                       _on(topo, (seq, seq), jnp.int8))
    assert "tpu_custom_call" in text and "flash_fwd" in text


def test_glm_engine_programs_keep_both_pools_in_place(topo):
    """Two layers of GLM-5 at the published widths and the cell's engine
    geometry (the dense layer and a sparse one holding experts [0, 16)):
    two pools a layer (640-lane latent rows and 128-wide index keys), the
    decode step and the 4,096 and 20,480 prefill buckets (past the 2,048
    selected, so their selection runs) hold no ``copy`` shaped like a pool
    and alias every donated pool, the largest in under 4.5 GB of
    temporaries; the decode step runs ``dsa_index``, ``dsa_select`` and
    ``mla_sparse_decode`` and no ``mla_decode``, the prefill
    ``dsa_prefill_select`` and the flash forward."""
    from paddle_tpu.models.glm_moe_dsa import (GlmMoeDsaConfig,
                                               GlmMoeDsaForCausalLM)
    from paddle_tpu.serve import ServeEngine

    model = GlmMoeDsaForCausalLM(GlmMoeDsaConfig(
        vocab_size=19360, num_hidden_layers=2, first_k_dense_replace=1,
        experts_held=(0, 16), dtype="bfloat16", deferred_init=True))
    model.eval()
    eng = ServeEngine(model, max_slots=32, block_size=128, num_blocks=1024,
                      max_seq_len=20480, name="aot-glm", trace=False,
                      slo=False)
    assert eng.attention_backend == "kernel"
    pools = ((1, 1024, 128, 640), (1, 1024, 128, 128))
    assert [tuple(a.shape for a in c) for c in eng._caches] == [pools] * 2
    lowered = eng.lowered(prompt_lens=(4096, 20479), device=topo.devices[0])
    assert set(lowered) == {"decode", "prefill.4096", "prefill.20480"}
    n_arrays = len(jax.tree.leaves(eng._arrays))
    caches = set(range(n_arrays, n_arrays + 4))
    for name, low in lowered.items():
        compiled = low.compile()
        hlo = compiled.as_text()
        if name == "prefill.20480":
            # what the cell's largest bucket holds beside the weights and
            # the pool (4.24 GB at five layers): it has to fit in 16
            assert compiled.memory_analysis().temp_size_in_bytes < 4.5e9
        for pool in pools:
            assert not _copies_shaped_like(hlo, pool), (name, pool)
        assert caches <= _aliased_params(hlo), name
        for kernel in ("moe_experts", "kv_write") + (
                ("dsa_index", "dsa_select", "mla_sparse_decode")
                if name == "decode" else ("dsa_prefill_select", "flash_fwd")):
            assert kernel in hlo, (name, kernel)
        assert "mla_decode\"" not in hlo, name


class _TopoMesh:
    """The slice of ``ProcessMesh`` a KernelPartition needs, over
    compile-only devices (a ProcessMesh indexes ``jax.devices()``)."""

    def __init__(self, devices, shape, names):
        self.jax_mesh = jax.sharding.Mesh(
            np.array(devices).reshape(shape), names)

    def get_dim_size(self, name):
        return self.jax_mesh.shape[name]


def test_sharded_flash_and_rms_norm_compile_per_shard(topo):
    """q/k/v sharded P("dp", "mp") on v5e:2x2: the Mosaic calls must be
    on per-shard shapes, with no gather in front of them. Without the
    partition the lowering raises "Mosaic kernels cannot be
    automatically partitioned"."""
    from paddle_tpu.ops.kernel_partition import KernelPartition
    from paddle_tpu.ops.pallas.rms_norm import _rms_bwd, _rms_fwd

    mesh = _TopoMesh(topo.devices, (2, 2), ("dp", "mp"))
    part = KernelPartition(mesh, batch="dp", heads="mp")
    qs = NamedSharding(mesh.jax_mesh, P("dp", "mp"))
    q = _on(topo, (4, 16, 2048, 128), BF16, qs)
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(_flash_fwd_bwd(True), q, q, q, q)
    _, compiled = _compile(_flash_fwd_bwd(True, partition=part), q, q, q, q)
    hlo = compiled.as_text()
    calls = [l for l in hlo.splitlines() if "tpu_custom_call" in l]
    assert len(calls) == 2
    assert all("bf16[2,8,2048,128]" in l for l in calls), calls
    assert "all-gather" not in hlo and "all-to-all" not in hlo

    def rms(x, w, g):
        return (_rms_fwd(x, w, eps=1e-6, partition=part),
                _rms_bwd(x, w, g, eps=1e-6, partition=part))

    x = _on(topo, (4, 2048, 2048), BF16,
            NamedSharding(mesh.jax_mesh, P("dp")))
    w = _on(topo, (2048,), BF16, NamedSharding(mesh.jax_mesh, P()))
    _, compiled = _compile(rms, x, w, x)
    hlo = compiled.as_text()
    calls = [l for l in hlo.splitlines() if "tpu_custom_call" in l]
    assert len(calls) == 2
    assert all("bf16[4096,2048]" in l for l in calls), calls
    assert "all-gather" not in hlo
