"""Pallas flash-attention kernels (fwd + bwd) under interpret mode.

The regular tests exercise the blockwise-XLA fallback (CPU backend); these
run the actual Pallas kernels via ``pl.pallas_call(..., interpret=True)``
so the TPU code path itself is numerically validated on every CI run.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from bigdl_tpu.ops import flash_attention_mod as fa


@pytest.fixture(autouse=True)
def interpret_mode():
    old = fa._INTERPRET
    fa._INTERPRET = True
    yield
    fa._INTERPRET = old


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32) * 0.3)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_forward_matches_reference(causal):
    q, k, v = (_rand((1, 2, 256, 128), i) for i in range(3))
    cfg = fa._Config(causal, 1 / np.sqrt(128), 128, 128, True)
    assert fa._pallas_ok(q, k, cfg)
    out = fa.flash_attention(q, k, v, causal=causal)
    want = fa.attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_matches_reference(causal):
    q, k, v = (_rand((1, 2, 256, 128), 10 + i) for i in range(3))
    cot = _rand((1, 2, 256, 128), 99)

    def f_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=causal) * cot)

    def f_ref(q, k, v):
        return jnp.sum(fa.attention_reference(q, k, v, causal=causal) * cot)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3,
            err_msg=f"d{name} mismatch")


def test_pallas_backward_rectangular_causal():
    """seq_q != seq_k (decode/cross shapes) through the Pallas kernels."""
    q = _rand((1, 1, 128, 128), 1)
    k = _rand((1, 1, 256, 128), 2)
    v = _rand((1, 1, 256, 128), 3)
    cot = _rand((1, 1, 128, 128), 4)

    def f(fn):
        return jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) * cot),
            argnums=(0, 1, 2))(q, k, v)

    for got, want in zip(f(fa.flash_attention), f(fa.attention_reference)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


def test_pallas_bf16_grads_finite():
    q, k, v = (_rand((1, 2, 256, 128), 20 + i).astype(jnp.bfloat16)
               for i in range(3))
    g = jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, causal=True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for t in g:
        assert bool(jnp.all(jnp.isfinite(t.astype(jnp.float32))))


# --------------------------------------------------------------------- #
# PR 29: bf16 operands, fp32 accumulation                               #
# --------------------------------------------------------------------- #
def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


# (causal, seq_q, seq_k, block_q, block_k); None = flash_blocks' own
_BF16_CASES = [
    (False, 512, 512, 128, 128),
    (True, 512, 512, 128, 128),
    (True, 512, 512, 256, 128),    # the diagonal crosses two kv blocks
    (True, 512, 512, 128, 256),    # ... and two q blocks
    (True, 512, 512, None, None),
    (True, 128, 256, 128, 128),    # rectangular causal
]
# Measured in interpret mode on the CPU over these cases (largest error
# over the largest reference entry): forward 1.4e-3 to 3.1e-3, dq 3.2e-3
# to 3.8e-3, dk 2.6e-3 to 5.3e-3, dv 2.7e-3 to 3.6e-3.  The parent's
# kernels, which upcast every operand, read 1.3e-3 to 2.3e-3 and 2.1e-3 to
# 3.3e-3 on the same inputs: the bf16 result's own rounding leads both.
# The limits are twice the worst reading.
_BF16_FWD_TOL = 6e-3
_BF16_BWD_TOL = 1e-2


@pytest.mark.parametrize("causal,sq,sk,bq,bk", _BF16_CASES)
def test_pallas_bf16_matches_reference_on_upcast_inputs(causal, sq, sk,
                                                        bq, bk):
    q = _rand((1, 2, sq, 128), 30).astype(jnp.bfloat16)
    k = _rand((1, 2, sk, 128), 31).astype(jnp.bfloat16)
    v = _rand((1, 2, sk, 128), 32).astype(jnp.bfloat16)
    cot = _rand((1, 2, sq, 128), 33).astype(jnp.bfloat16)
    up = [t.astype(jnp.float32) for t in (q, k, v)]

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=causal, block_q=bq,
                                  block_k=bk)

    def ref(q, k, v):
        return fa.attention_reference(q, k, v, causal=causal)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) * cot.astype(jnp.float32))

    out = flash(q, k, v)
    assert out.dtype == jnp.bfloat16
    assert _rel_err(out, ref(*up)) < _BF16_FWD_TOL
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(*up)
    for g, w, name in zip(got, want, "qkv"):
        assert g.dtype == jnp.bfloat16
        assert _rel_err(g, w) < _BF16_BWD_TOL, f"d{name}"


def _pallas_calls(fn, *args):
    """The pallas_call equations of fn's jaxpr, in order."""
    return [e for e in _walk(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "pallas_call"]


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def _grad_fn(causal):
    return jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal=causal, block_q=128, block_k=128
    ).astype(jnp.float32)), argnums=(0, 1, 2))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kernels_feed_the_mxu_the_inputs_own_dtype(dtype):
    """The nine matmuls (forward 2, dk/dv 4, dq 3) take operands of the
    inputs' dtype and give fp32; nothing is transposed in a loop."""
    q, k, v = (_rand((1, 1, 256, 128), i).astype(dtype) for i in range(3))
    calls = _pallas_calls(_grad_fn(False), q, k, v)
    assert len(calls) == 3
    for call, n_dots in zip(calls, (2, 4, 3)):
        eqns = list(_walk(call.params["jaxpr"]))
        dots = [e for e in eqns if e.primitive.name == "dot_general"]
        assert len(dots) == n_dots
        for e in dots:
            assert [x.aval.dtype for x in e.invars] == [dtype, dtype]
            assert e.outvars[0].aval.dtype == jnp.float32
        assert not [e for e in eqns if e.primitive.name == "transpose"]
        # exp and the carried accumulators stay fp32
        assert all(e.outvars[0].aval.dtype == jnp.float32 for e in eqns
                   if e.primitive.name == "exp")
    # causal: a masked and an unmasked copy of each loop body
    calls = _pallas_calls(_grad_fn(True), q, k, v)
    for call, n_dots in zip(calls, (2, 4, 3)):
        eqns = list(_walk(call.params["jaxpr"]))
        assert len([e for e in eqns
                    if e.primitive.name == "dot_general"]) == 2 * n_dots
        assert len([e for e in eqns
                    if e.primitive.name == "select_n"]) >= 1


def test_kernel_results_are_what_the_benchmark_tells_them_by():
    """The contract with benchmarks/metrics/_flash.py (its docstring):
    three calls; the forward returns (o, fp32 lse column), the dk/dv
    kernel two arrays of the operands' type, the dq kernel one."""
    from benchmarks.metrics import _flash
    b, h, s, d = 2, 2, 256, 128
    q, k, v = (_rand((b, h, s, d), i).astype(jnp.bfloat16)
               for i in range(3))
    calls = _pallas_calls(_grad_fn(True), q, k, v)
    got = [[(o.aval.shape, o.aval.dtype) for o in c.outvars] for c in calls]
    bf16, f32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
    assert got == [[((b * h, s, d), bf16), ((b * h, s, 1), f32)],
                   [((b * h, s, d), bf16)] * 2,
                   [((b * h, s, d), bf16)]]

    def hlo_name(results):
        names = {bf16: "bf16", f32: "f32"}
        arrays = ", ".join(f"{names[t]}[{','.join(map(str, shp))}]{{2,1,0}}"
                           for shp, t in results)
        result = f"({arrays})" if len(results) > 1 else arrays
        return f"%call.1 = {result} custom-call(%a, %b), custom_call_target"

    assert [_flash.classify(hlo_name(r)) for r in got] == \
        ["fwd", "dkv", "dq"]


@pytest.mark.parametrize("seq,dtype", [(2048, jnp.bfloat16),
                                       (15360, jnp.bfloat16),
                                       (7680, jnp.float32),
                                       (256, jnp.float32)])
def test_flash_blocks_tile_the_sequence_and_fit(seq, dtype):
    bq, bk = fa.flash_blocks(seq, seq, 128, dtype)
    assert bq % 128 == 0 and bk % 128 == 0
    assert seq % bq == 0 and seq % bk == 0
    assert fa._vmem_bytes(bq, bk, seq, seq, 128, dtype) \
        <= fa._VMEM_LIMIT_BYTES
    shape = (1, 16, seq, 128)
    path, why = fa.attention_path(shape, shape, dtype, backend="tpu")
    assert path == "pallas"
    assert f"blocks {bq} x {bk}" in why and jnp.dtype(dtype).name in why


def test_flash_blocks_for_the_benchmark_shape_and_odd_shapes():
    # the LM cells' shape: larger than one lane tile, as measured fastest
    bq, bk = fa.flash_blocks(2048, 2048, 128, jnp.bfloat16)
    assert bq * bk > 128 * 128
    # a sequence no block of 128 divides: the one-tile default, and
    # attention_path says why that is the scan
    assert fa.flash_blocks(200, 200, 128, jnp.float32) == (128, 128)
    shape = (1, 2, 200, 128)
    path, why = fa.attention_path(shape, shape, jnp.float32, backend="tpu")
    assert path == "blockwise" and "not multiples" in why
    # an explicit block still wins
    shape = (1, 2, 2048, 128)
    assert "blocks 128 x 256" in fa.attention_path(
        shape, shape, jnp.bfloat16, block_q=128, block_k=256,
        backend="tpu")[1]


def test_default_blocks_reach_the_kernels_and_the_scan_keeps_128():
    q, k, v = (_rand((1, 1, 512, 128), i).astype(jnp.bfloat16)
               for i in range(3))
    fwd, = _pallas_calls(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True), q, k, v)
    bq, bk = fa.flash_blocks(512, 512, 128, jnp.bfloat16)
    assert fwd.params["grid_mapping"].grid == (1, 512 // bq)
    fa._INTERPRET = False       # the CPU's route: the scan, blocks of 128
    jaxpr = jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True))(q, k, v)
    scans = [e for e in _walk(jaxpr.jaxpr) if e.primitive.name == "scan"]
    assert scans and scans[0].params["length"] == 512 // 128
