"""DenseNet-121 in the program against the benchmark's plain reference
(``chipbench/refs/densenet121.py``) on seeded random weights, the
pre-activation prologue of the 1x1 GEMM kernel (``preact_matmul``), and the
program's ``ref_fallbacks`` counter.

The model runs at 40x40, batch 2, with a 10-way head: the smallest square
input whose every grid stays non-empty through the stem, the max pool and
the three transitions (40 -> 20 -> 9 -> 4 -> 2 -> 1; at 32x32 the last
block's grid is empty and the global pool averages nothing).
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.spec import HERE, load_module
from repro import marvel
from repro.core import dispatch
from repro.core.extensions import resolve_table
from repro.kernels import matmul_epilogue as me
from repro.kernels import ref
from repro.models import cnn

SIZE, BATCH, CLASSES = 40, 2, 10


def _draw(path, leaf, rng):
    """One parameter as the reference's ``init`` draws it (He-scaled
    normal weights, batchnorm scales in [0.8, 1.2) and shifts N(0, 0.1)),
    from numpy: compiling the reference's 242 seeded draws takes half a
    minute on the CPU."""
    name = path[-1].key
    if name == "s":
        a = rng.uniform(0.8, 1.2, leaf.shape)
    elif name == "b":
        a = 0.1 * rng.standard_normal(leaf.shape)
    else:
        a = rng.standard_normal(leaf.shape) * np.sqrt(
            2.0 / np.prod(leaf.shape[:-1]))
    return jnp.asarray(a, jnp.float32)


@pytest.fixture(scope="module")
def model():
    cfg = json.loads((HERE / "configs" / "densenet121-224.json").read_text())
    cfg.update(in_shape=[SIZE, SIZE, 3], num_classes=CLASSES)
    reference = load_module(HERE, "refs", cfg["reference"])
    shapes = jax.eval_shape(functools.partial(reference.init, cfg=cfg),
                            jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    params = jax.tree.map_with_path(lambda p, a: _draw(p, a, rng), shapes)
    x = jax.random.normal(jax.random.PRNGKey(4), (BATCH, SIZE, SIZE, 3))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(reference.forward, cfg=cfg))(params,
                                                                      x)
    return params, x, np.asarray(want)


def _rel(got, want):
    return float(np.linalg.norm(np.asarray(got) - want)
                 / np.linalg.norm(want))


def test_the_reference_has_the_programs_layout(model):
    params, _, _ = model
    program = jax.eval_shape(cnn.densenet121_init, jax.random.PRNGKey(0))
    assert jax.tree.structure(program) == jax.tree.structure(params)
    # every shape but the head's classes is the program's own
    for (path, a), b in zip(jax.tree.leaves_with_path(program),
                            jax.tree.leaves(params)):
        if path[0].key != "head":
            assert a.shape == b.shape, path


def test_v0_agrees_with_the_reference(model):
    """v0 runs the dispatch baselines: the same float32 mathematics as the
    reference, so only the order of the sums differs."""
    params, x, want = model
    got = jax.jit(cnn.densenet121_apply)(params, x)
    assert _rel(got, want) < 1e-5


def test_v4_agrees_with_the_reference_and_no_site_falls_back(model):
    """v4 through ``marvel.compile`` with the Pallas kernels (interpret
    mode here).  Band 0.05: the stem and the 58 3x3 convs run int8 with
    activations scaled per tensor and weights per output channel, a
    rounding step of about 0.4% of each tensor's largest value at each of
    59 sites, compounded through the depth; the 61 pre-activated 1x1
    GEMMs and the head stay float32 (read here: about 0.01)."""
    params, x, want = model
    prog = marvel.compile(cnn.densenet121_apply, np.zeros((1, SIZE, SIZE, 3),
                                                          np.float32),
                          params=params, level="v4", backend="pallas",
                          precompile=False)
    got = prog(x)
    assert np.isfinite(np.asarray(got)).all()
    assert _rel(got, want) < 0.05
    assert prog.ref_fallbacks == 0
    assert prog.metrics()["ref_fallbacks"] == 0


def test_ref_fallbacks_counts_a_site_that_leaves_its_kernel():
    """A conv whose act the kernel epilogue lacks falls back to its jnp
    oracle while the bucket is traced: counted once per bucket built, not
    on a cache hit."""
    w = jax.random.normal(jax.random.PRNGKey(0), (3, 3, 4, 8))

    def fn(x):
        return cnn.conv2d(x, w, act="silu")

    x = np.ones((2, 8, 8, 4), np.float32)
    prog = marvel.compile(fn, x, level="v4", backend="pallas",
                          precompile=False)
    assert prog.ref_fallbacks == 0
    prog(x)
    assert prog.ref_fallbacks == 1
    prog(x)  # cache hit: nothing traced
    assert prog.metrics()["ref_fallbacks"] == 1
    prog(np.ones((4, 8, 8, 4), np.float32))  # a second bucket
    assert prog.ref_fallbacks == 2


def test_the_c1_epilogue_fold_equals_the_unfused_bn_relu():
    """A bottleneck's BN2-ReLU rides its 1x1 GEMM's epilogue; the prologue
    is its BN1-ReLU: one site equals the three steps done apart."""
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = jax.random.normal(ks[0], (2, 5, 5, 96))
    w = jax.random.normal(ks[1], (1, 1, 96, 128)) / np.sqrt(96)
    s1, b1 = 1 + 0.2 * jax.random.normal(ks[2], (96,)), \
        0.1 * jax.random.normal(ks[3], (96,))
    s2, b2 = 1 + 0.2 * jax.random.normal(ks[4], (128,)), \
        0.1 * jax.random.normal(ks[5], (128,))
    relu = lambda a: jnp.maximum(a, 0.0)
    apart = relu(relu(x * s1 + b1).reshape(-1, 96) @ w[0, 0] * s2 + b2)
    for table in (dispatch.EMPTY_TABLE,
                  resolve_table("v4", "pallas", model_class="cnn")):
        with dispatch.use_table(table):
            fused = cnn.conv2d(x, w, pre_scale=s1, pre_shift=b1, scale=s2,
                               shift=b2, act="relu")
        np.testing.assert_allclose(np.asarray(fused).reshape(-1, 128),
                                   np.asarray(apart), rtol=1e-5, atol=1e-5)


def test_a_prologue_on_a_spatial_conv_is_refused():
    x, w = jnp.ones((1, 6, 6, 4)), jnp.ones((3, 3, 4, 8))
    with pytest.raises(ValueError):
        cnn.conv2d(x, w, pre_scale=jnp.ones((4,)), pre_shift=jnp.zeros((4,)))


# M = 1063 rows: two whole blocks of PRE_BM and a ragged third
@pytest.mark.parametrize("k_in", [64, 96, 160, 1000, 1300])
@pytest.mark.parametrize("residual", [False, True],
                         ids=["plain", "residual"])
def test_preact_matmul_equals_jnp(k_in, residual):
    """The prologue kernel against the jnp oracle.  The shifts centre on
    -0.5, so the relu zeroes about a third of x: a padded or out-of-range
    lane that got relu(shift) instead of 0 would show.  K 1300 is past
    WHOLE_K, so K is split and its padded lanes must give relu(0) = 0."""
    m_rows, n_out = 2 * 23 * 23 + 5, 200 if k_in >= 1000 else 128
    ks = jax.random.split(jax.random.PRNGKey(k_in), 7)
    x = jax.random.normal(ks[0], (m_rows, k_in))
    w = jax.random.normal(ks[1], (k_in, n_out)) / np.sqrt(k_in)
    ps = jax.random.uniform(ks[2], (k_in,), minval=0.5, maxval=1.5)
    pt = -0.5 + 0.1 * jax.random.normal(ks[3], (k_in,))
    s = jax.random.uniform(ks[4], (n_out,), minval=0.8, maxval=1.2)
    t = jax.random.normal(ks[5], (n_out,))
    r = jax.random.normal(ks[6], (m_rows, n_out)) if residual else None
    got = me.matmul_epilogue(x, w, None, act="relu", scale=s, shift=t,
                             residual=r, pre_scale=ps, pre_shift=pt)
    want = ref.matmul_epilogue_ref(x, w, None, act="relu", scale=s, shift=t,
                                   residual=r, pre_scale=ps, pre_shift=pt)
    assert got.shape == (m_rows, n_out)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _pallas_eqn(fn, *args):
    (outer,) = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
                if "jaxpr" in e.params]  # the jitted kernel wrapper
    (call,) = [e for e in outer.params["jaxpr"].jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    return call


def _body(call):
    return [e.primitive.name for e in call.params["jaxpr"].eqns]


# the epilogue-only kernel body: zero the accumulator on the first K step,
# load the x, w and accumulator tiles, contract, accumulate, and on the last
# K step the epilogue
EPILOGUE_ONLY = ["program_id", "eq", "convert_element_type", "cond",
                 "get", "get", "get", "dot_general", "add", "swap",
                 "program_id", "eq", "convert_element_type", "cond"]


def test_without_prologue_operands_the_kernel_is_unchanged():
    """No prologue operands: the epilogue-only kernel, its operands padded
    to the tile as always, and nothing computed on x before the MXU."""
    x, w = jnp.ones((2, 9, 9, 100)), jnp.ones((100, 130))
    b, r = jnp.ones((130,)), jnp.ones((2, 9, 9, 130))
    with_residual = (lambda x, w, b, r: me.matmul_epilogue(
        x, w, b, act="relu", residual=r))
    for fn, args, n_in in ((me.matmul_epilogue, (x, w, b), 4),
                           (with_residual, (x, w, b, r), 5)):
        call = _pallas_eqn(fn, *args)
        assert call.params["name"] == "matmul_epilogue"
        assert len(call.invars) == n_in
        # M 162 -> 256, N 130 -> 256, K 100 -> 128 at the 128 tiles
        assert call.params["grid_mapping"].grid == (2, 2, 1)
        assert _body(call) == EPILOGUE_ONLY
    call = _pallas_eqn(lambda x, w, ps: me.matmul_epilogue(
        x, w, b, pre_scale=ps, pre_shift=-ps), x, w, jnp.ones((100,)))
    assert call.params["name"] == "preact_matmul"
    assert len(call.invars) == 6
    body = _body(call)
    assert "max" in body[:body.index("dot_general")]
    # x read as it lies: its 162 rows one block, K whole
    assert call.params["grid_mapping"].grid == (1, 2, 1)
