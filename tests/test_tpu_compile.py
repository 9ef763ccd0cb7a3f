"""Compile every Pallas kernel for a described TPU v5e, without the chip.

On TPU ``backend="auto"`` resolves to these kernels, so one kernel that
Mosaic refuses breaks every program of its class there — and interpret mode
(what the rest of the suite runs) cannot see a refusal: unaligned or
strided sublane loads, block shapes off the (8, 128) tiling, scoped-VMEM
overruns.  Each case lowers the kernel at the shapes the CNN serving path
uses (ResNet-50 at 224x224, batch 8) or the LM classes use, compiles it for
a ``v5e:2x2`` topology description, and checks the executable holds the
kernel as a ``tpu_custom_call``.

The topology is described inside a module fixture (only one process may
load libtpu at a time, and it keeps it until it exits), never at import.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import common
from repro.kernels import depthwise_conv as dw
from repro.kernels import flash_attention as fa
from repro.kernels import fused_conv as fc
from repro.kernels import mac_matmul as mm
from repro.kernels import matmul_epilogue as me
from repro.kernels import pooling as pk
from repro.kernels import residual_rmsnorm as rr
from repro.kernels import wkv_chunk as wk

I8, F32, BF16 = jnp.int8, jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def tpu_spec(topo):
    """ShapeDtypeStruct factory on one described chip, with the kernels
    lowering through Mosaic (not interpret mode) while this module runs."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    cache_was_on = jax.config.jax_enable_compilation_cache
    # a described-chip executable written to the persistent cache cannot be
    # read back here; keep it out of the cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()  # no interpret-mode trace may be reused
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(common, "interpret_mode", lambda: False)
        yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                        sharding=one_chip)
    jax.clear_caches()  # nor may a Mosaic trace reach a later CPU test
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _conv(stride, act="relu", residual=False):
    if residual:
        return lambda x, w, s, b, r: fc.fused_conv_int8(
            x, w, s, b, r, stride=stride, act=act)
    return lambda x, w, s, b: fc.fused_conv_int8(x, w, s, b, stride=stride,
                                                 act=act)


def _conv_args(x, w):
    cout = w[-1]
    return [(x, I8), (w, I8), ((cout,), F32), ((cout,), F32)]


# name -> (kernel name in the executable, fn, [(shape, dtype), ...])
CASES = {
    "fused_conv-stem224": ("fused_conv", _conv(2),
                           _conv_args((8, 224, 224, 3), (7, 7, 3, 64))),
    "fused_conv-stem64": ("fused_conv", _conv(2),
                          _conv_args((8, 64, 64, 3), (7, 7, 3, 64))),
    "fused_conv-3x3s2": ("fused_conv", _conv(2),
                         _conv_args((8, 56, 56, 128), (3, 3, 128, 128))),
    "fused_conv-3x3s1-7x7": ("fused_conv", _conv(1),
                             _conv_args((8, 7, 7, 512), (3, 3, 512, 512))),
    "fused_conv-residual-c3": (
        "fused_conv", _conv(1, residual=True),
        _conv_args((8, 56, 56, 64), (1, 1, 64, 256))
        + [((8, 56, 56, 256), F32)]),
    "depthwise_conv-3x3s1": (
        "depthwise_conv",
        lambda x, w, s, b: dw.depthwise_conv_int8(x, w, s, b, stride=1,
                                                  act="relu6"),
        [((8, 112, 112, 32), I8), ((3, 3, 32), I8), ((32,), F32),
         ((32,), F32)]),
    "depthwise_conv-3x3s2": (
        "depthwise_conv",
        lambda x, w, s, b: dw.depthwise_conv_int8(x, w, s, b, stride=2,
                                                  act="relu6"),
        [((8, 56, 56, 144), I8), ((3, 3, 144), I8), ((144,), F32),
         ((144,), F32)]),
    "sep_block": (
        "sep_block",
        lambda x, wd, ds, db, wp, ps, pb: dw.sep_block_int8(
            x, wd, ds, db, wp, ps, pb, stride=1),
        [((8, 56, 56, 64), I8), ((3, 3, 64), I8), ((64,), F32),
         ((64,), F32), ((64, 128), I8), ((128,), F32), ((128,), F32)]),
    "maxpool-3x3s2": ("maxpool", lambda x: pk.maxpool2d(x, k=3, stride=2),
                      [((8, 112, 112, 64), F32)]),
    "avgpool-2x2s2": ("avgpool", lambda x: pk.avgpool2d(x, k=2, stride=2),
                      [((8, 56, 56, 128), F32)]),
    "global_avgpool": ("global_avgpool", pk.global_avgpool,
                       [((8, 7, 7, 2048), F32)]),
    "matmul_epilogue-residual": (
        "matmul_epilogue",
        lambda x, w, b, r: me.matmul_epilogue(x, w, b, act="relu",
                                              residual=r),
        [((8, 56, 56, 64), F32), ((64, 256), F32), ((256,), F32),
         ((8, 56, 56, 256), F32)]),
    "mac_matmul_int8": ("mac_matmul_int8", mm.mac_matmul_int8,
                        [((256, 2048), I8), ((2048, 2048), I8),
                         ((2048,), F32)]),
    "residual_rmsnorm": ("residual_rmsnorm", rr.residual_rmsnorm,
                         [((4, 128, 2048), F32), ((4, 128, 2048), F32),
                          ((2048,), F32)]),
    "flash_attention": ("flash_attention", fa.flash_attention,
                        [((8, 512, 128), BF16)] * 3),
    "wkv_chunk": ("wkv_chunk",
                  lambda r, k, v, lw, u, s0: wk.wkv_chunk(r, k, v, lw, u, s0,
                                                          chunk=64),
                  [((1, 128, 4, 64), F32)] * 4
                  + [((4, 64), F32), ((1, 4, 64, 64), F32)]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_compiles_for_v5e(case, tpu_spec):
    kernel, fn, args = CASES[case]
    specs = [tpu_spec(shape, dtype) for shape, dtype in args]
    hlo = jax.jit(fn).lower(*specs).compile().as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls, f"{case}: no tpu_custom_call in the executable"
    assert any(f"/{kernel}/pallas_call" in line for line in calls), (
        case, [line[:160] for line in calls])
