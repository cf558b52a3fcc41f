"""The packed kernels of the main path compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unsupported casts and gathers, or more VMEM than a kernel
may use.  These tests compile — nothing runs — each kernel of the served
path at its published width for a v5e chip that is described, not attached,
and assert the compiled program holds the Pallas kernel (`tpu_custom_call`).

Shapes: the paper's BN-LSTM (`rnn-paper` = char-PTB H=1000 vocab 50, whose
head fits the fused tick; word-PTB large, 2 x H=1500 vocab 10k, whose head
runs as an XLA dot beside it), the fused tick at the in-kernel head's VMEM
budget, the packed GEMV and MXU GEMM at prefill shapes, and the qwen3-0.6b
projections through `qmatmul` at decode (M <= 8) and prefill (M = 256).

The topology is described inside a fixture, never at import: describing
it loads libtpu, which one process at a time may hold, so only the test
worker that runs this file does it.  The CPU stays the default backend
(tests/conftest.py); the described devices never hold an array.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, rnn_paper
from repro.core.qtensor import QTensor
from repro.kernels import decode_step as DK
from repro.kernels import ops

GROUP = {"ternary": 16, "binary": 32}
MODES = ("ternary", "binary")


def _up(a, m):
    return -(-a // m) * m


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the compiled program"


def _tick_args(dev, cfg, mode, B=8, vocab=None):
    """Abstract operands of `ops.fused_decode_tick` for an RNNConfig."""
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt,
                                                           sharding=dev)
    L, H, g = cfg.n_layers, cfg.d_hidden, cfg.n_gates
    V = vocab or cfg.vocab
    hp = _up(H, DK.BN_TILE)
    R = _up(hp, 8 * GROUP[mode]) // GROUP[mode]
    vp = _up(V, DK.BN_TILE)
    lx = max(L - 1, 1)
    tick = {"rows0": S((V, g * H)),
            "codes_h": S((L, g, R, hp), jnp.uint32),
            "codes_x": S((lx, g, R, hp), jnp.uint32),
            "scale_h": S((L, g, hp)), "shift_h": S((L, g, hp)),
            "scale_x": S((lx, g, hp)), "shift_x": S((lx, g, hp)),
            "scale_c": S((L, 1, hp)), "shift_c": S((L, 1, hp)),
            "ws": S((hp, vp)), "bs": S((1, vp))}
    return (S((B,), jnp.int32), S((L, B, H)), S((L, B, H)),
            S((B,), jnp.bool_), tick), V


def _head_budget_vocab(H, B=8):
    """Largest 128-multiple vocab whose padded head still takes the
    in-kernel branch of `ops.fused_decode_tick`."""
    hp, bp = _up(H, DK.BN_TILE), _up(B, 8)
    return ops.HEAD_VMEM_BYTES // ((hp + 2 * bp) * 4) // 128 * 128


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("config", [
    "char_ptb",                  # H=1000, vocab 50: head in-kernel
    "word_ptb_large",            # 2 x H=1500, vocab 10k: head as XLA dot
    "char_text8_head_budget",    # H=2000, the largest in-kernel head
])
def test_fused_tick_compiles(one_chip, config, mode):
    if config == "char_text8_head_budget":
        cfg, vocab = rnn_paper.char_text8(mode), _head_budget_vocab(2000)
    else:
        cfg, vocab = getattr(rnn_paper, config)(mode=mode), None
    (tok, h, c, live, tick), V = _tick_args(one_chip, cfg, mode, vocab=vocab)
    hp, vp = tick["ws"].shape
    in_kernel = (hp * vp + 2 * 8 * vp) * 4 <= ops.HEAD_VMEM_BYTES
    assert in_kernel == (config != "word_ptb_large")
    fn = lambda tok, h, c, live, tick: ops.fused_decode_tick(
        tok, h, c, tick, cell=cfg.cell, mode=mode, vocab=V, live=live,
        interpret=False)
    _compile(fn, tok, h, c, live, tick)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("M", [1, 8])
def test_packed_gemv_compiles(one_chip, mode, M):
    """The decode GEMV (M <= 8) of `rnn-paper`'s h-side: (H, 4H), with x
    zero-padded to the codes' K coverage as `qmatmul` does."""
    H = rnn_paper.char_ptb().d_hidden
    kg = -(-H // GROUP[mode])
    K = kg * GROUP[mode]
    x = jax.ShapeDtypeStruct((M, K), jnp.float32, sharding=one_chip)
    wp = jax.ShapeDtypeStruct((kg, 4 * H), jnp.uint32, sharding=one_chip)
    _compile(lambda x, wp: ops.packed_matmul(x, wp, K, 0.05, mode=mode,
                                             interpret=False), x, wp)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("mkn", [(256, 1024, 4096), (32, 1008, 4000)])
def test_packed_matmul_compiles(one_chip, mode, mkn):
    """The MXU decode-tile GEMM (M > 8): a 256-token prefill chunk at
    rnn-paper's padded width, and a short ragged one."""
    M, K, N = mkn
    x = jax.ShapeDtypeStruct((M, K), jnp.float32, sharding=one_chip)
    wp = jax.ShapeDtypeStruct((_up(K, GROUP[mode]) // GROUP[mode], N),
                              jnp.uint32, sharding=one_chip)
    _compile(lambda x, wp: ops.packed_matmul(x, wp, K, 0.05, mode=mode,
                                             interpret=False), x, wp)


def _qwen3_projections():
    c = get_config("qwen3-0.6b")
    q, kv = c.n_heads * c.head_dim, c.n_kv * c.head_dim
    return {"q": (c.d_model, q), "kv": (c.d_model, kv), "o": (q, c.d_model),
            "up": (c.d_model, c.d_ff), "down": (c.d_ff, c.d_model)}


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("M", [8, 256])
@pytest.mark.parametrize("proj", ["q", "kv", "o", "up", "down"])
def test_qwen3_projection_compiles(one_chip, proj, M, mode):
    """qwen3-0.6b's packed projections through `qmatmul` (channel-scaled
    QTensors, bf16 activations): the GEMV at decode, the GEMM at prefill."""
    K, N = _qwen3_projections()[proj]
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    qt = QTensor(codes=S((K // GROUP[mode], N), jnp.uint32),
                 scale=S((N,), jnp.bfloat16), k=K, mode=mode, alpha=0.05)
    _compile(lambda x, qt: ops.qmatmul(x, qt, interpret=False),
             S((M, K), jnp.bfloat16), qt)
