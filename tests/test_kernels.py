"""Pallas kernels vs pure-jnp oracles (interpret mode): shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize as Q
from repro.kernels import dispatch, ops, ref


F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.mark.parametrize("mode", ["ternary", "binary"])
@pytest.mark.parametrize("mkn", [
    (4, 256, 384, F32), (128, 512, 512, F32), (1, 1024, 256, F32),
    (67, 320, 136, F32), (8, 64, 8, F32), (2, 320, 136, F32),
    (5, 480, 200, F32), (8, 1056, 264, F32),
    # the GEMM at tail and full chunk rows, past one row tile, with ragged
    # N (a partial last N tile) and a rank-128 contraction
    (9, 128, 264, F32), (16, 128, 300, BF16), (64, 256, 264, F32),
    (128, 128, 300, F32), (200, 384, 264, BF16), (256, 128, 300, F32),
    (300, 352, 264, F32), (16, 256, 128, BF16)])
def test_packed_matmul_matches_ref(mode, mkn):
    """Both kernels `ops.packed_matmul` routes to (the GEMV at 1-8 rows, the
    GEMM above) against the plain reference, for float32 and bfloat16
    activations, and float32 to float32 accuracy: with activations spread
    over 1e-3 .. 1e3 it agrees to 1e-5 of its largest output, where the
    same reference fed one bfloat16 pass of the activations does not."""
    M, K, N, dtype = mkn
    kw, kx, ku = jax.random.split(jax.random.PRNGKey(M * K + N), 3)
    w = jax.random.normal(kw, (K, N)) * 0.02
    u = jax.random.uniform(ku, (K, N))
    alpha = 0.05
    wp = ops.quantize_pack(w, u, alpha, mode=mode)
    wp_ref = (ref.quantize_pack_ternary_ref if mode == "ternary"
              else ref.quantize_pack_binary_ref)(w, u, alpha)
    np.testing.assert_array_equal(np.asarray(wp), np.asarray(wp_ref))

    x = jax.random.normal(kx, (M, K), jnp.float32).astype(dtype)
    y = ops.packed_matmul(x, wp, K, alpha, mode=mode)
    matmul_ref = (ref.ternary_matmul_ref if mode == "ternary"
                  else ref.binary_matmul_ref)
    y_ref = matmul_ref(x.astype(jnp.float32), wp, K, alpha)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    if dtype != F32:
        return

    spread = jax.random.uniform(jax.random.fold_in(kx, 1), (M, K),
                                minval=np.log(1e-3), maxval=np.log(1e3))
    xw = x * jnp.exp(spread)
    yw_ref = np.asarray(matmul_ref(xw, wp, K, alpha))
    tol = dict(rtol=1e-5, atol=1e-5 * np.abs(yw_ref).max())
    np.testing.assert_allclose(
        np.asarray(ops.packed_matmul(xw, wp, K, alpha, mode=mode)), yw_ref,
        **tol)
    one_pass = matmul_ref(xw.astype(jnp.bfloat16).astype(jnp.float32), wp, K,
                          alpha)
    assert not np.allclose(np.asarray(one_pass), yw_ref, **tol)


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                yield from _eqns(getattr(v.jaxpr, "jaxpr", v.jaxpr))


@pytest.mark.parametrize("mode", ["ternary", "binary"])
@pytest.mark.parametrize("M", [*range(1, 10), 16, 300])
def test_packed_matmul_kernel_follows_rows(mode, M):
    """`ops.packed_matmul` makes one launch: `<mode>_packed_gemv` at 1-8
    rows, `<mode>_packed_matmul` above, as `dispatch.launch_counts`
    tallies it and the jaxpr names it.  Above 8 rows the codes go in as
    they are: at a ragged N (and, binary, a K of 11 packed rows) the jaxpr
    pads no uint32 array."""
    K, N = 352, 264                  # shapes no other test traces
    x = jnp.ones((M, K), jnp.float32)
    group = Q.TERNARY_GROUP if mode == "ternary" else Q.BINARY_GROUP
    wp = jnp.zeros((K // group, N), jnp.uint32)
    before = dispatch.launch_counts()
    closed = jax.make_jaxpr(lambda x, w: ops.packed_matmul(
        x, w, K, mode=mode, interpret=True))(x, wp)
    after = dispatch.launch_counts()
    traced = {k: n - before.get(k, 0) for k, n in after.items()
              if n != before.get(k, 0)}
    kernel = f"{mode}_packed_gemv" if M <= 8 else f"{mode}_packed_matmul"
    assert traced == {kernel: 1}

    eqns = list(_eqns(closed.jaxpr))
    assert [e.params["name"] for e in eqns
            if e.primitive.name == "pallas_call"] == [kernel]
    if M > 8:
        assert not [e for e in eqns if e.primitive.name == "pad"
                    and e.invars[0].aval.dtype == jnp.uint32]


@pytest.mark.parametrize("mode", ["ternary", "binary"])
@pytest.mark.parametrize("M", [64, 300])
def test_packed_matmul_k_blocks(monkeypatch, mode, M):
    """Where all of K does not fit the GEMM's VMEM budget, K runs in blocks
    into a float32 accumulator, and past one row tile the later tiles reuse
    the first one's decoded codes: the result still matches the reference.
    A budget just below the whole-K plan's forces it at small widths."""
    from repro.kernels import packed_matmul as PK

    K, N = 1024, 264
    group = Q.TERNARY_GROUP if mode == "ternary" else Q.BINARY_GROUP
    mp, bm, _, _ = PK._gemm_plan(M, K, N, 3, group)
    monkeypatch.setattr(PK, "GEMM_VMEM_BYTES", PK._gemm_vmem(
        3, bm, 128, K, K, mp // bm, 1, group) - 1)
    assert PK._gemm_plan(M, K, N, 3, group)[3] < K
    kw, kx, ku = jax.random.split(jax.random.PRNGKey(M + K), 3)
    wp = ops.quantize_pack(jax.random.normal(kw, (K, N)) * 0.02,
                           jax.random.uniform(ku, (K, N)), 0.05, mode=mode)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    y = jax.jit(lambda x, wp: PK.packed_matmul(x, wp, K, mode=mode,
                                               interpret=True))(x, wp)
    matmul_ref = (ref.ternary_matmul_ref if mode == "ternary"
                  else ref.binary_matmul_ref)
    y_ref = np.asarray(matmul_ref(x, wp, K))
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-5,
                               atol=1e-5 * np.abs(y_ref).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_packed_matmul_dtypes(dtype):
    M, K, N = 16, 256, 128
    kw, kx, ku = jax.random.split(jax.random.PRNGKey(0), 3)
    w = jax.random.normal(kw, (K, N)) * 0.02
    u = jax.random.uniform(ku, (K, N))
    wp = ops.quantize_pack(w, u, 0.05, mode="ternary")
    x = jax.random.normal(kx, (M, K)).astype(dtype)
    y = ops.packed_matmul(x, wp, K, 0.05, mode="ternary")
    y_ref = ref.ternary_matmul_ref(x, wp, K, 0.05)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_packed_matmul_batched_input():
    kw, kx, ku = jax.random.split(jax.random.PRNGKey(1), 3)
    w = jax.random.normal(kw, (128, 64)) * 0.02
    u = jax.random.uniform(ku, w.shape)
    wp = ops.quantize_pack(w, u, 0.05, mode="ternary")
    x = jax.random.normal(kx, (2, 3, 128))
    y = ops.packed_matmul(x, wp, 128, 0.05, mode="ternary")
    assert y.shape == (2, 3, 64)
    y2 = ops.packed_matmul(x.reshape(6, 128), wp, 128, 0.05, mode="ternary")
    np.testing.assert_allclose(np.asarray(y).reshape(6, 64), np.asarray(y2),
                               rtol=1e-5)


@pytest.mark.parametrize("mode,group", [("ternary", 16), ("binary", 32)])
def test_qtensor_qmatmul_end_to_end(mode, group):
    """qmatmul(x, QTensor) == deterministic quantization matmul; 16x/32x bytes."""
    from repro.core.qtensor import QTensor

    K, N = 512, 256
    w = jax.random.normal(jax.random.PRNGKey(2), (K, N)) * 0.02
    alpha = Q.glorot_alpha(K, N)
    qt = QTensor.from_master(w, mode, alpha)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, K))
    y = ops.qmatmul(x, qt)
    qfn = Q.ternarize_deterministic if mode == "ternary" else Q.binarize_deterministic
    y_ref = x @ qfn(w, alpha)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    assert qt.nbytes == K * N * 4 // group


def test_quantize_pack_fused_equals_two_step():
    """Fused kernel == (stochastic quantize, then pack) composition."""
    w = jax.random.normal(jax.random.PRNGKey(4), (256, 128)) * 0.03
    u = jax.random.uniform(jax.random.PRNGKey(5), w.shape)
    a = 0.04
    fused = ops.quantize_pack(w, u, a, mode="ternary")
    q = Q.ternarize_stochastic(w, u, a) / a
    np.testing.assert_array_equal(np.asarray(fused),
                                  np.asarray(Q.pack_ternary(q)))
