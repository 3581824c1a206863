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
    assert text.count("tpu_custom_call") == 3   # fwd, bwd dq, bwd dkv


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


@pytest.mark.parametrize("kv_heads", [16, 4])
def test_paged_decode_compiles(topo, kv_heads):
    # the serving shape: 8 slots, 96 pages of 128, 8 pages per sequence
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_kernel)

    pages = _on(topo, (kv_heads, 96, 128, 128), BF16)
    text, _ = _compile(
        paged_attention_decode_kernel, _on(topo, (8, 16, 128), BF16),
        pages, pages, _on(topo, (8,), jnp.int32),
        _on(topo, (8, 8), jnp.int32))
    assert "tpu_custom_call" in text


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
    assert len(calls) == 3
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
