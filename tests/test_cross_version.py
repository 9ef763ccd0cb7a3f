"""Cross-version equivalence: the extension ladder changes cost, never
semantics.

Two acceptance properties for the CNN class:
1. logits at every extension level v0..v4 (pallas backend, interpret mode on
   CPU) agree with the v0 baseline within accumulated int8-quant tolerance —
   for all six CNNs (heavyweights ride the slow lane);
2. at v4 the dispatch for lenet5 / vgg16 / resnet50 has ZERO baseline conv,
   GEMM, or pool sites — every site reaches its Pallas kernel (extending PR
   4's mobile-only coverage check to the plain + residual CNN classes), and
   ResNet50's 16 bottleneck skip-adds are all fused into conv/GEMM epilogues
   (zero standalone skip-add HBM round-trips in the profiler report).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch, profiler
from repro.core.extensions import resolve_table
from repro.kernels import fused_conv as fc
from repro.kernels import matmul_epilogue as me
from repro.kernels import pooling as pk
from repro.kernels import ref
from repro.models import cnn

LEVELS = ("v0", "v1", "v2", "v3", "v4")

# int8-quant tolerance on the relative L2 error of the logits, scaled up
# for the deep stacks (quantization error accumulates per layer)
_EQUIV_CASES = [
    pytest.param("lenet5", None, 0.05, id="lenet5"),
    pytest.param("mobilenetv1", (32, 32, 3), 0.2, id="mobilenetv1"),
    pytest.param("resnet50", (32, 32, 3), 0.25, id="resnet50-small"),
    pytest.param("vgg16", None, 0.25, marks=pytest.mark.slow, id="vgg16"),
    pytest.param("resnet50", None, 0.25, marks=pytest.mark.slow,
                 id="resnet50"),
    pytest.param("mobilenetv2", None, 0.25, marks=pytest.mark.slow,
                 id="mobilenetv2"),
    pytest.param("densenet121", None, 0.25, marks=pytest.mark.slow,
                 id="densenet121"),
    pytest.param("mobilenetv1", None, 0.25, marks=pytest.mark.slow,
                 id="mobilenetv1-full"),
]


@pytest.mark.parametrize("name,in_shape,tol", _EQUIV_CASES)
def test_logits_agree_across_all_versions(name, in_shape, tol):
    init, apply, native_shape = cnn.get_cnn(name)
    in_shape = in_shape or native_shape
    p = init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, *in_shape))
    base = apply(p, x)  # v0: pure baseline
    assert np.isfinite(np.asarray(base)).all()
    for lvl in LEVELS[1:]:
        table = resolve_table(lvl, "pallas", model_class="cnn")
        with dispatch.use_table(table):
            out = apply(p, x)
        rel = float(jnp.linalg.norm(out - base) / jnp.linalg.norm(base))
        assert np.isfinite(np.asarray(out)).all(), lvl
        assert rel < tol, (name, lvl, rel)


@pytest.mark.parametrize("name,batch_shape", [
    pytest.param("lenet5", None, id="lenet5"),
    pytest.param("vgg16", None, id="vgg16"),
    pytest.param("resnet50", None, id="resnet50"),
    # DenseNet at its benchmark size: odd grids (55, 27, 13, 6) and
    # concatenated maps of every width 64 + 32i (shape-only trace)
    pytest.param("densenet121", (32, 224, 224, 3), id="densenet121-224"),
])
def test_v4_dispatch_zero_baseline_conv_and_pool_sites(name, batch_shape,
                                                        monkeypatch):
    """Acceptance: at v4/pallas every conv, GEMM, and pool site in the
    plain + residual + densely connected CNNs reaches its kernel — the jnp
    fallbacks inside the wrappers are never taken — and DenseNet's 61
    pre-activated 1x1 GEMMs take the kernel's prologue variant."""
    init, apply, in_shape = cnn.get_cnn(name)
    p = jax.eval_shape(init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct(batch_shape or (1, *in_shape), jnp.float32)
    sites = profiler.profile_fn(apply, p, x).site_counts

    kernel_calls = {"conv": [], "gemm": [], "pool": [], "preact": []}
    fallbacks = []

    def counting(bucket, real):
        def wrapped(*a, **k):
            kernel_calls[bucket].append(1)
            if k.get("pre_scale") is not None:
                kernel_calls["preact"].append(1)
            return real(*a, **k)
        return wrapped

    def falling(real, label):
        def wrapped(*a, **k):
            fallbacks.append(label)
            return real(*a, **k)
        return wrapped

    monkeypatch.setattr(fc, "fused_conv_int8",
                        counting("conv", fc.fused_conv_int8))
    monkeypatch.setattr(me, "matmul_epilogue",
                        counting("gemm", me.matmul_epilogue))
    for kname in ("maxpool2d", "avgpool2d", "global_avgpool"):
        monkeypatch.setattr(pk, kname, counting("pool", getattr(pk, kname)))
    for rname in ("fused_conv_ref", "pool_ref", "matmul_epilogue_ref",
                  "depthwise_conv_ref", "sep_block_ref"):
        monkeypatch.setattr(ref, rname, falling(getattr(ref, rname), rname))

    with dispatch.use_table(resolve_table("v4", "pallas", model_class="cnn")):
        jax.eval_shape(apply, p, x)

    assert not fallbacks, fallbacks  # the acceptance criterion
    absorbed = sites["sep_block"]  # none in these three models
    assert len(kernel_calls["conv"]) == sites["fused_conv"] - absorbed
    assert len(kernel_calls["gemm"]) == sites["matmul_epilogue"]
    assert len(kernel_calls["pool"]) == sites["pool"]
    if name != "lenet5":  # lenet5's stride-2 convs subsume pooling
        assert sites["pool"] > 0
    # 58 bottlenecks and 3 transitions in DenseNet-121, none elsewhere
    assert len(kernel_calls["preact"]) == (61 if name == "densenet121" else 0)


@pytest.mark.parametrize("name", ["mobilenetv1", "mobilenetv2",
                                  "densenet121"])
def test_v2_pooling_dispatches_through_pool_kernels(name, monkeypatch):
    """All pooling CNNs run their pool sites on the Pallas kernels from v2
    (the pool extension's activation level) — including DenseNet's avgpool2
    transition pools."""
    init, apply, in_shape = cnn.get_cnn(name)
    p = init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, *in_shape))
    sites = profiler.profile_fn(lambda x: apply(p, x), x).site_counts
    assert sites["pool"] > 0
    calls, ref_calls = [], []
    for kname in ("maxpool2d", "avgpool2d", "global_avgpool"):
        real = getattr(pk, kname)
        monkeypatch.setattr(
            pk, kname,
            lambda *a, _r=real, **k: calls.append(1) or _r(*a, **k),
        )
    real_ref = ref.pool_ref
    monkeypatch.setattr(
        ref, "pool_ref",
        lambda *a, **k: ref_calls.append(1) or real_ref(*a, **k),
    )
    with dispatch.use_table(resolve_table("v2", "pallas", model_class="cnn")):
        jax.eval_shape(lambda x: apply(p, x), x)
    assert len(calls) == sites["pool"]
    assert not ref_calls


def test_resnet50_residual_adds_all_fused_into_epilogues():
    """ResNet50's profiler report shows every bottleneck skip-add riding a
    conv/GEMM epilogue (acc_mac pseudo-sites) — and no standalone
    full-tensor skip-add survives anywhere in the traced graph."""
    init, apply, in_shape = cnn.get_cnn("resnet50")
    p = init(jax.random.PRNGKey(0))
    prof = profiler.profile_fn(lambda x: apply(p, x),
                               jnp.zeros((1, *in_shape)))
    n_blocks = sum(n for n, _, _ in cnn._R50_STAGES)
    assert prof.site_counts["acc_mac"] == n_blocks == 16
    ins = prof.as_costmodel_inputs()
    assert ins["acc_bytes_saved"] > 0
    # the acc_mac credit actually moves both ladders at v3+
    from repro.core import costmodel

    v2 = costmodel.apply_level(ins, "v2")
    v3 = costmodel.apply_level(ins, "v3")
    no_acc = dict(ins, acc_bytes_saved=0.0, acc_flops=0.0)
    assert v3["hbm_bytes"] < v2["hbm_bytes"]
    assert (costmodel.apply_level(no_acc, "v3")["hbm_bytes"]
            > v3["hbm_bytes"])
    assert (costmodel.rv32_cycles(ins, "v3")
            < costmodel.rv32_cycles(no_acc, "v3"))
    # v2 (acc_mac not yet active) is unchanged by zeroing the acc inputs
    assert costmodel.rv32_cycles(ins, "v2") == costmodel.rv32_cycles(
        no_acc, "v2")


def test_guarded_residual_sites_claim_no_acc_savings():
    """A residual site the kernels would decline (grouped conv, exotic act,
    broadcast-shaped residual) must record NO acc_mac pseudo-site — same
    guard-mirroring contract as conv_epilogue/dw_mac/pool."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(k1, (1, 8, 8, 4))
    w = jax.random.normal(k2, (3, 3, 2, 4)) / 4.0  # groups=2 weight shape
    res = jnp.zeros((1, 8, 8, 4))
    prof = profiler.profile_fn(
        lambda x: cnn.conv2d(x, w, groups=2, act="relu", residual=res), x
    )
    assert prof.site_counts["acc_mac"] == 0
    # broadcastable-but-not-exact residual on a GEMM site: also no credit
    w2 = jax.random.normal(k2, (4, 6)) * 0.1
    prof = profiler.profile_fn(
        lambda x: cnn.dense(x.reshape(1, -1)[:, :4], w2,
                            residual=jnp.zeros((1, 6))[:1]), x
    )
    assert prof.site_counts["acc_mac"] == 1  # exact shape: credited
    prof = profiler.profile_fn(
        lambda x: cnn.dense(jnp.zeros((3, 4)), w2,
                            residual=jnp.zeros((1, 6))), x
    )
    assert prof.site_counts["acc_mac"] == 0  # broadcast shape: no credit
    # the eligible ResNet50 sites still get their 16 credits
    # (covered by test_resnet50_residual_adds_all_fused_into_epilogues)


def test_pool_baseline_accepts_int8_inputs():
    """v0/v1 run the pool *baseline* — it must take the same int8 inputs
    the v2+ kernels serve, with the oracle's dtype rules."""
    from repro.kernels import ref

    x = jax.random.randint(jax.random.PRNGKey(0), (1, 9, 9, 4), -127, 128,
                           jnp.int8)
    # no active table: dispatch runs the cnn.py baseline
    got = cnn.maxpool(x, 3, 2)
    assert got.dtype == jnp.int8
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.pool_ref(x, op="max", k=3, stride=2))
    )
    got = cnn.avgpool2(x)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref.pool_ref(x, op="avg", k=2, stride=2)),
        rtol=1e-6,
    )
    assert cnn.avgpool_global(x).dtype == jnp.float32


def test_pool_extension_moves_the_ladder_at_v2():
    """DenseNet121 (five pool sites incl. the avgpool2 transitions): the
    pool credit lands at v2 on both ladders and nowhere earlier."""
    from repro.core import costmodel

    init, apply, in_shape = cnn.get_cnn("densenet121")
    p = init(jax.random.PRNGKey(0))
    prof = profiler.profile_fn(lambda x: apply(p, x),
                               jnp.zeros((1, *in_shape)))
    assert prof.site_counts["pool"] == 5  # stem max + 3 avg2 + global
    ins = prof.as_costmodel_inputs()
    assert ins["pool_flops"] > 0 and ins["pool_saved_bytes"] > 0
    no_pool = dict(ins, pool_flops=0.0, pool_saved_bytes=0.0)
    assert (costmodel.apply_level(ins, "v2")["hbm_bytes"]
            < costmodel.apply_level(no_pool, "v2")["hbm_bytes"])
    assert (costmodel.apply_level(ins, "v1")["hbm_bytes"]
            == costmodel.apply_level(no_pool, "v1")["hbm_bytes"])
    # rv32: pool ops cost full slots at v1, half at v2+
    v1_delta = (costmodel.rv32_cycles(ins, "v1")
                - costmodel.rv32_cycles(no_pool, "v1"))
    v2_delta = (costmodel.rv32_cycles(ins, "v2")
                - costmodel.rv32_cycles(no_pool, "v2"))
    assert v1_delta == pytest.approx(ins["pool_flops"])
    assert v2_delta == pytest.approx(0.5 * ins["pool_flops"])
