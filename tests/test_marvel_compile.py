"""marvel.compile front door: the deployable MarvelProgram artifact.

Covers the acceptance contract: all six paper CNNs compile to programs whose
__call__ matches the v0 baseline (int8-tolerance when quantized), the AOT
executable is reused across same-shape calls (hit/miss counters), buckets
split by shape, extension resolution is baked at trace time, unknown
backends raise, rewrite failures warn, and the CNN batch-inference path
serves real requests off the artifact.
"""
import asyncio
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import marvel
from repro.core import dispatch
from repro.core.extensions import resolve_table
from repro.core.pipeline import MarvelReport, run_marvel_flow
from repro.models.cnn import CNN_MODELS, get_cnn


def _setup(name):
    init, apply, in_shape = get_cnn(name)
    params = init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, *in_shape))
    return params, apply, x


# ---------------------------------------------------------------------------
# the acceptance sweep: all six paper CNNs
# ---------------------------------------------------------------------------


@pytest.mark.slow  # the six-CNN sweep is the fast lane's long pole
@pytest.mark.parametrize("name", list(CNN_MODELS))
def test_compile_matches_baseline_all_six(name):
    params, apply, x = _setup(name)
    prog = marvel.compile(lambda a: apply(params, a), x, level="v4")
    assert isinstance(prog, marvel.MarvelProgram)
    assert prog.model_class == "cnn"
    y0 = apply(params, x)
    y = prog(x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(y0), rtol=1e-4, atol=1e-4
    )
    # deploy precompile was the only miss; the call above hit its bucket
    assert prog.cache_misses == 1
    assert prog.cache_hits == 1


@pytest.mark.parametrize("name", ["lenet5", "mobilenetv2"])
def test_compile_quantized_int8_tolerance(name):
    params, apply, x = _setup(name)
    prog = marvel.compile(apply, x, params=params, level="v4", quantize=True)
    assert prog.quantized and prog.quant_stats["quantized"] > 0
    y0 = np.asarray(apply(params, x))
    yq = np.asarray(prog(x))
    scale = np.abs(y0).max() + 1e-6
    assert np.abs(yq - y0).max() <= 0.25 * scale, (
        f"int8 PTQ error too large: {np.abs(yq - y0).max()} vs scale {scale}"
    )


def test_rewrite_is_baked_into_the_artifact():
    """The deployed program, not just the report, carries the chess_rewrite
    fusions — per shape bucket."""
    from repro.core.rewrite import count_custom_instructions

    params, apply, x = _setup("lenet5")
    prog = marvel.compile(lambda a: apply(params, a), x)
    assert prog.rewrite_baked
    counts = count_custom_instructions(prog.baked_jaxpr(x))
    assert sum(counts.values()) >= 3  # 2 convs + fc fuse on lenet5
    # a different batch bucket re-rewrites at its own shapes
    xb = jnp.concatenate([x] * 2)
    counts_b = count_custom_instructions(prog.baked_jaxpr(xb))
    assert counts_b == counts
    np.testing.assert_allclose(
        np.asarray(prog(xb)), np.asarray(apply(params, xb)),
        rtol=1e-4, atol=1e-4,
    )
    # do_rewrite=False deploys the unrewritten program
    prog0 = marvel.compile(lambda a: apply(params, a), x, do_rewrite=False,
                           precompile=False)
    assert not prog0.rewrite_baked
    assert sum(count_custom_instructions(prog0.baked_jaxpr(x)).values()) == 0


def test_quantize_requires_params():
    params, apply, x = _setup("lenet5")
    with pytest.raises(ValueError, match="params"):
        marvel.compile(lambda a: apply(params, a), x, quantize=True)


# ---------------------------------------------------------------------------
# AOT cache: compile-once-call-many, shape/dtype bucketing
# ---------------------------------------------------------------------------


def test_aot_cache_hit_and_bucket_counters():
    params, apply, x = _setup("lenet5")
    prog = marvel.compile(lambda a: apply(params, a), x)
    assert (prog.cache_misses, prog.cache_hits) == (1, 0)  # deploy compile
    prog(x)
    prog(x)
    assert (prog.cache_misses, prog.cache_hits) == (1, 2)
    xb = jnp.concatenate([x] * 4)  # new shape -> new bucket, one miss
    prog(xb)
    prog(xb)
    assert (prog.cache_misses, prog.cache_hits) == (2, 3)
    assert prog.cache_size == 2


def test_compile_from_shape_structs_then_call_hits():
    init, apply, in_shape = get_cnn("lenet5")
    params = init(jax.random.PRNGKey(0))
    spec = jax.ShapeDtypeStruct((2, *in_shape), jnp.float32)
    prog = marvel.compile(lambda a: apply(params, a), spec)
    assert prog.cache_misses == 1  # lowered from the spec alone
    x = jnp.ones((2, *in_shape))
    y = prog(x)
    assert prog.cache_hits == 1 and y.shape == (2, 10)


def test_cost_and_resolved_extensions_accessors():
    params, apply, x = _setup("lenet5")
    prog = marvel.compile(lambda a: apply(params, a), x, precompile=False)
    for lvl in ("v0", "v2", "v4"):
        c = prog.cost(lvl)
        assert set(c) == {"rv32_cycles", "rv32_energy_j", "tpu_cycles",
                          "tpu_energy_j", "hbm_bytes"}
    assert prog.cost()["rv32_cycles"] == prog.cost("v4")["rv32_cycles"]
    assert prog.cost("v0")["rv32_cycles"] > prog.cost("v4")["rv32_cycles"]
    with pytest.raises(ValueError, match="v9"):
        prog.cost("v9")
    assert isinstance(prog.resolved_extensions, dict)
    assert "MarvelProgram" in prog.summary()


# ---------------------------------------------------------------------------
# backend resolution
# ---------------------------------------------------------------------------


def test_unknown_backend_raises_listing_backends():
    params, apply, x = _setup("lenet5")
    with pytest.raises(ValueError) as ei:
        marvel.compile(lambda a: apply(params, a), x, backend="pallsa")
    assert "pallas" in str(ei.value) and "ref" in str(ei.value)
    with pytest.raises(ValueError, match="unknown processor version"):
        marvel.compile(lambda a: apply(params, a), x, level="v7")


def test_auto_backend_resolution_per_platform():
    import repro.kernels.ops  # noqa: F401  (registers pallas)

    cpu = resolve_table("v4", "auto", platform="cpu")
    assert dict(cpu) == {}  # pallas kernels are tpu-production only
    tpu = resolve_table("v4", "auto", platform="tpu")
    assert tpu.impl_for("fused_conv") == "pallas"
    assert tpu.impl_for("matmul_epilogue") == "pallas"
    # class-aware restriction drops patterns of unselected extensions
    restricted = resolve_table("v4", "auto", extensions=["conv_mac"],
                               platform="tpu")
    assert dict(restricted) == {"fused_conv": "pallas"}


def test_forced_pallas_backend_bakes_table():
    params, apply, x = _setup("lenet5")
    prog = marvel.compile(lambda a: apply(params, a), x, backend="pallas",
                          precompile=False)
    # lenet5's class-aware selection includes conv_mac + fusedmac patterns
    assert prog.resolved_extensions.get("fused_conv") == "pallas"
    assert prog.resolved_extensions.get("matmul_epilogue") == "pallas"
    # interpret-mode kernels still match the baseline numerically
    y0 = np.asarray(apply(params, x))
    y = np.asarray(prog(x))
    np.testing.assert_allclose(y, y0, rtol=5e-2, atol=5e-2)


def test_baked_program_ignores_ambient_context():
    """The artifact's impls are fixed at compile; surrounding contexts and
    other threads cannot change what the binary computes."""
    params, apply, x = _setup("lenet5")
    prog = marvel.compile(lambda a: apply(params, a), x, backend="ref")
    y0 = np.asarray(prog(x))
    with dispatch.use_table(resolve_table("v4", "pallas", model_class="cnn")):
        y1 = np.asarray(prog(x))
    np.testing.assert_array_equal(y0, y1)
    assert prog.cache_misses == 1  # no retrace, no recompile


# ---------------------------------------------------------------------------
# rewrite failure surfacing
# ---------------------------------------------------------------------------


def test_rewrite_failure_warns_and_sets_flag(monkeypatch):
    from repro.core import rewrite as rewrite_mod

    def boom(fn, *a):
        raise RuntimeError("synthetic rewrite failure")

    monkeypatch.setattr(rewrite_mod, "rewrite", boom)
    params, apply, x = _setup("lenet5")
    with pytest.warns(RuntimeWarning, match="chess_rewrite failed"):
        prog = marvel.compile(lambda a: apply(params, a), x,
                              precompile=False)
    assert prog.report.rewrite_ok is False
    assert "error" in prog.report.rewrite_stats
    assert "FAILED" in prog.report.summary()


def test_run_marvel_flow_delegates_and_stays_quiet():
    params, apply, x = _setup("lenet5")
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no spurious warnings on success
        rep = run_marvel_flow(lambda a: apply(params, a), x)
    assert isinstance(rep, MarvelReport)
    assert rep.rewrite_ok is True
    assert rep.model_class == "cnn"
    assert 1.7 <= rep.rv32_speedup_v4 <= 2.4


def test_run_marvel_flow_accepts_shape_structs():
    init, apply, in_shape = get_cnn("lenet5")
    params = init(jax.random.PRNGKey(0))
    spec = jax.ShapeDtypeStruct((1, *in_shape), jnp.float32)
    rep = run_marvel_flow(lambda a: apply(params, a), spec)
    assert rep.model_class == "cnn"


# ---------------------------------------------------------------------------
# the CNN batch-inference path (the artifact is servable)
# ---------------------------------------------------------------------------


def _serve_wave(engine, images, first_uid=0):
    """Submit ``images`` with consecutive uids before the batcher runs, so
    they coalesce into full batches, and await every result."""
    async def main():
        async with engine:
            return await asyncio.gather(*[
                engine.submit_nowait(im, uid=first_uid + i)
                for i, im in enumerate(images)])

    return asyncio.run(main())


def test_cnn_engine_serves_off_the_artifact():
    init, apply, in_shape = get_cnn("lenet5")
    params = init(jax.random.PRNGKey(0))
    x = jnp.zeros((1, *in_shape))
    prog = marvel.compile(apply, x, params=params, precompile=False)
    engine = prog.serve(max_batch=4)
    rng = np.random.default_rng(0)
    imgs = [rng.standard_normal(in_shape).astype(np.float32)
            for _ in range(6)]
    results = _serve_wave(engine, imgs)
    assert len(results) == 6 and engine.batches_run == 2
    ref = np.asarray(apply(params, jnp.stack(imgs)))
    want = np.argmax(ref, axis=-1)
    for i in range(6):
        assert results[i].done and results[i].label == int(want[i])
        assert results[i].probs.shape == (ref.shape[-1],)
    # 6 requests -> one bucket-4 batch + one bucket-2 batch, two compiles
    assert prog.cache_size == 2
    # a second wave of the same sizes recompiles nothing
    misses = prog.cache_misses
    _serve_wave(engine, imgs, first_uid=100)
    assert prog.cache_misses == misses


def test_cnn_engine_warmup_precompiles_buckets():
    init, apply, in_shape = get_cnn("lenet5")
    params = init(jax.random.PRNGKey(0))
    prog = marvel.compile(apply, jnp.zeros((1, *in_shape)), params=params,
                          precompile=False)
    engine = prog.serve(max_batch=4)  # buckets 1, 2, 4
    engine.warmup(in_shape)
    assert prog.cache_size == 3 and prog.cache_misses == 3
    _serve_wave(engine, [np.zeros(in_shape, np.float32)])
    assert prog.cache_misses == 3 and prog.cache_hits == 1


def test_serve_requires_cnn_class():
    w = jnp.ones((8, 8))
    prog = marvel.compile(lambda a: a @ w, jnp.ones((4, 8)),
                          precompile=False)
    assert prog.model_class != "cnn"
    with pytest.raises(NotImplementedError, match="cnn"):
        prog.serve()
