"""Whole-tick fused decode (DESIGN.md §11): single-launch parity, the
static mul-freeness proof, and the backend-honest dispatch policy.

Covers: fused whole-tick == per-layer unfused serving math to 1e-5 for
LSTM+GRU x {binary, ternary} x B in {1, 4}; live-mask dead-row freeze
(bit-exact); ragged-K padding (H not a multiple of the 128 lane tile or the
pack group); the accumulation-only GEMV jaxpr contains zero
mul/dot_general; one traced decode tick dispatches EXACTLY one Pallas
launch (and the CPU dense fallback dispatches zero); interpret-mode Pallas
== dense CPU fallback == ref path on the same inputs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bnlstm as BL
from repro.core.qtensor import QTensor
from repro.core.quantize import QuantSpec
from repro.kernels import dispatch, ops, ref
from repro.kernels.packed_matmul import accumulate_gemv


def _rnn_cfg(cell, mode="ternary", hidden=40, layers=2, vocab=50):
    return BL.RNNConfig(vocab=vocab, d_hidden=hidden, n_layers=layers,
                        cell=cell, quant=QuantSpec(mode=mode, norm="batch"))


def _packed_vars(cfg, seed=0):
    var = BL.rnn_lm_init(jax.random.PRNGKey(seed), cfg)
    # walk the BN running stats off init so the folded affines are
    # non-trivial (catches scale/shift fold bugs the init stats would hide)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    var["state"] = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape),
        var["state"])
    return {"params": BL.export_packed_rnn(var["params"], cfg),
            "state": var["state"]}


def _walked_state(qvar, cfg, tables, B):
    """A per-slot state a few real steps off zero."""
    st = BL.rnn_state_init(cfg, B, per_slot=True)
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, 3), 0, cfg.vocab)
    for i in range(3):
        _, st = BL.rnn_decode_step(qvar, toks[:, i], cfg, st, tables=tables,
                                   fused=False)
    return st


# --- whole-tick parity -------------------------------------------------------


@pytest.mark.parametrize("B", [1, 4])
@pytest.mark.parametrize("mode", ["ternary", "binary"])
@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_fused_tick_matches_unfused(cell, mode, B):
    cfg = _rnn_cfg(cell, mode)
    qvar = _packed_vars(cfg)
    tables = BL.rnn_decode_tables(qvar, cfg, dense=False)
    st = _walked_state(qvar, cfg, tables, B)
    tok = jax.random.randint(jax.random.PRNGKey(9), (B,), 0, cfg.vocab)
    lg_f, st_f = BL.rnn_decode_step(qvar, tok, cfg, st, tables=tables,
                                    fused=True, interpret=True)
    lg_u, st_u = BL.rnn_decode_step(qvar, tok, cfg, st, tables=tables,
                                    fused=False)
    np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_u), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_f.h), np.asarray(st_u.h),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_f.c), np.asarray(st_u.c),
                               atol=1e-5)


@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_fused_tick_live_mask_freezes_dead_rows(cell):
    cfg = _rnn_cfg(cell)
    qvar = _packed_vars(cfg)
    tables = BL.rnn_decode_tables(qvar, cfg, dense=False)
    st = _walked_state(qvar, cfg, tables, 4)
    tok = jnp.array([3, 7, 1, 9], jnp.int32)
    live = jnp.array([True, False, True, False])
    lg_f, st_f = BL.rnn_decode_step(qvar, tok, cfg, st, tables=tables,
                                    fused=True, interpret=True, live=live)
    lg_u, st_u = BL.rnn_decode_step(qvar, tok, cfg, st, tables=tables,
                                    fused=False, live=live)
    # dead rows: BIT-exact freeze of h, c and pos inside the kernel
    for dead in (1, 3):
        np.testing.assert_array_equal(np.asarray(st_f.h[:, dead]),
                                      np.asarray(st.h[:, dead]))
        np.testing.assert_array_equal(np.asarray(st_f.c[:, dead]),
                                      np.asarray(st.c[:, dead]))
        assert int(st_f.pos[dead]) == int(st.pos[dead])
    # live rows step identically to the unfused masked step
    for alive in (0, 2):
        np.testing.assert_allclose(np.asarray(st_f.h[:, alive]),
                                   np.asarray(st_u.h[:, alive]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(lg_f[alive]),
                                   np.asarray(lg_u[alive]), atol=1e-5)


@pytest.mark.parametrize("hidden", [40, 136])
def test_fused_tick_ragged_k_padding(hidden):
    """H neither a lane-tile (128) nor pack-group multiple: pad codes and
    pad activation lanes must contribute exactly nothing across layers."""
    cfg = _rnn_cfg("lstm", "binary", hidden=hidden)  # binary: pad code = -1
    qvar = _packed_vars(cfg)
    tables = BL.rnn_decode_tables(qvar, cfg, dense=False)
    st = _walked_state(qvar, cfg, tables, 2)
    tok = jnp.array([5, 11], jnp.int32)
    lg_f, st_f = BL.rnn_decode_step(qvar, tok, cfg, st, tables=tables,
                                    fused=True, interpret=True)
    lg_u, st_u = BL.rnn_decode_step(qvar, tok, cfg, st, tables=tables,
                                    fused=False)
    np.testing.assert_allclose(np.asarray(lg_f), np.asarray(lg_u), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_f.h), np.asarray(st_u.h),
                               atol=1e-5)


def test_fused_tick_greedy_argmax_matches_logits():
    cfg = _rnn_cfg("lstm")
    qvar = _packed_vars(cfg)
    tables = BL.rnn_decode_tables(qvar, cfg, dense=False)
    st = _walked_state(qvar, cfg, tables, 4)
    tok = jnp.array([3, 7, 1, 9], jnp.int32)
    logits, _, _, greedy = ops.fused_decode_tick(
        tok, st.h, st.c, tables[0]["tick"], cell="lstm", mode="ternary",
        vocab=cfg.vocab, interpret=True)
    np.testing.assert_array_equal(np.asarray(greedy),
                                  np.asarray(jnp.argmax(logits, axis=-1)))


# --- the static mul-freeness proof (tier-1) ----------------------------------


@pytest.mark.parametrize("mode,group", [("ternary", 16), ("binary", 32)])
def test_gemv_jaxpr_is_accumulation_only(mode, group):
    """The packed GEMV consumes decoded weights with ZERO multiplies: its
    jaxpr (recursively) contains no mul/dot_general — the paper's
    replace-every-MAC-with-an-accumulation claim as a compiler fact."""
    x = jnp.ones((8, 64), jnp.float32)
    codes = jnp.asarray(np.random.default_rng(0).integers(
        0, 2**32, (64 // group, 128), dtype=np.uint32))
    dispatch.assert_accumulation_only(accumulate_gemv, x, codes, mode=mode)


def test_accumulation_assertion_catches_multiplies():
    x = jnp.ones((4, 8))
    with pytest.raises(AssertionError, match="multiply"):
        dispatch.assert_accumulation_only(lambda a: a @ a.T, x)
    with pytest.raises(AssertionError, match="multiply"):
        dispatch.assert_accumulation_only(lambda a: a * 2.0, x)


def test_accumulate_gemv_matches_dense_oracle():
    kx, kw, ku = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (4, 128))
    for mode in ("ternary", "binary"):
        w = jax.random.normal(kw, (128, 256)) * 0.02
        u = jax.random.uniform(ku, w.shape)
        wp = ops.quantize_pack(w, u, 0.05, mode=mode)
        y = accumulate_gemv(x, wp, mode=mode)
        fn = (ref.ternary_matmul_ref if mode == "ternary"
              else ref.binary_matmul_ref)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(fn(x, wp, 128, 1.0)),
                                   rtol=1e-5, atol=1e-5)


# --- launches per tick (counted like tick_traces) ----------------------------


def test_decode_tick_is_one_pallas_launch():
    """Tracing one packed decode tick dispatches EXACTLY one Pallas launch;
    the dense-table tick dispatches ZERO (the CPU serving path never runs
    interpret-mode Pallas)."""
    cfg = _rnn_cfg("lstm")
    qvar = _packed_vars(cfg)
    st = BL.rnn_state_init(cfg, 4, per_slot=True)
    tok = jnp.zeros((4,), jnp.int32)
    live = jnp.ones((4,), bool)

    packed_tb = BL.rnn_decode_tables(qvar, cfg, dense=False)
    n = dispatch.traced_launches(
        lambda t, s: BL.rnn_decode_step(qvar, t, cfg, s, tables=packed_tb,
                                        live=live, interpret=True), tok, st)
    assert n == 1, f"packed decode tick traced {n} launches, want 1"

    dense_tb = BL.rnn_decode_tables(qvar, cfg, dense=True)
    n = dispatch.traced_launches(
        lambda t, s: BL.rnn_decode_step(qvar, t, cfg, s, tables=dense_tb,
                                        live=live), tok, st)
    assert n == 0, f"dense decode tick traced {n} launches, want 0"


def test_cpu_runtime_defaults_to_dense_tables():
    """Backend-honest dispatch: off the TPU a packed runtime serves through
    dense tables (no tick artifact, no Pallas); on it through packed ones."""
    from repro.serve.recurrent import RNNRuntime

    cfg = _rnn_cfg("lstm")
    qvar = _packed_vars(cfg)
    rt = RNNRuntime(cfg, qvar)
    on_tpu = dispatch.backend() == "tpu"
    assert rt._dense_tables == (not on_tpu)
    assert ("tick" in rt.tables[0]) == on_tpu


# --- backend parity guard ----------------------------------------------------


@pytest.mark.parametrize("mode", ["ternary", "binary"])
def test_qmatmul_backend_parity(mode):
    """interpret-mode Pallas == dense fallback == ref oracle on the same
    inputs, so the dispatch policy can never silently diverge per backend.
    On CPU `interpret=None` takes the dense fallback and `interpret=True`
    the emulated kernel; on the TPU both run the compiled kernel."""
    K, N = 256, 128
    w = jax.random.normal(jax.random.PRNGKey(2), (K, N)) * 0.02
    qt = QTensor.from_master(w, mode, 0.05)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, K))

    y_default = ops.qmatmul(x, qt)                    # backend policy path
    y_pallas = ops.qmatmul(x, qt, interpret=dispatch.backend() != "tpu")
    y_dense = jnp.dot(x, qt.dequantize(jnp.float32))  # the CPU fallback math
    fn = (ref.ternary_matmul_ref if mode == "ternary"
          else ref.binary_matmul_ref)
    y_ref = fn(x, qt.codes, K, qt.alpha)

    for name, y in (("default", y_default), ("pallas", y_pallas),
                    ("dense", y_dense)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5, err_msg=name)


def test_tick_backend_parity():
    """The fused tick (interpret Pallas) == dense-tables unfused step ==
    packed-tables unfused step, one triangle per backend."""
    cfg = _rnn_cfg("gru", "ternary")
    qvar = _packed_vars(cfg)
    packed_tb = BL.rnn_decode_tables(qvar, cfg, dense=False)
    dense_tb = BL.rnn_decode_tables(qvar, cfg, dense=True)
    st = _walked_state(qvar, cfg, packed_tb, 2)
    tok = jnp.array([4, 8], jnp.int32)
    lg_k, st_k = BL.rnn_decode_step(qvar, tok, cfg, st, tables=packed_tb,
                                    fused=True, interpret=True)
    lg_p, st_p = BL.rnn_decode_step(qvar, tok, cfg, st, tables=packed_tb,
                                    fused=False)
    lg_d, st_d = BL.rnn_decode_step(qvar, tok, cfg, st, tables=dense_tb,
                                    fused=False)
    np.testing.assert_allclose(np.asarray(lg_k), np.asarray(lg_p), atol=1e-5)
    np.testing.assert_allclose(np.asarray(lg_p), np.asarray(lg_d), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st_k.h), np.asarray(st_d.h),
                               atol=1e-5)
