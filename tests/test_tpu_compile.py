"""The packed kernels of the main path compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unsupported casts and gathers, or more VMEM than a kernel
may use.  These tests compile — nothing runs — each kernel of the served
path at its published width for a v5e chip that is described, not attached,
and assert the compiled program holds the Pallas kernel (`tpu_custom_call`).

Shapes: the paper's BN-LSTM (`rnn-paper` = char-PTB H=1000 vocab 50, whose
head fits the fused tick; word-PTB large, 2 x H=1500 vocab 10k, whose head
runs as an XLA dot beside it), the fused tick at the in-kernel head's VMEM
budget, the packed GEMV and MXU GEMM at prefill shapes, the 8-row GEMV at
every zamba2-7b width and at the widest contraction of the configurations
(llama-3.2-vision-90b's down projection), in both modes, and the qwen3-0.6b
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
from repro.kernels import packed_matmul as PK

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
    """The GEMV of `rnn-paper`'s h-side, (H, 4H), at one row (prefill) and
    at 8 (decode), with x zero-padded to the codes' K coverage as `qmatmul`
    does."""
    H = rnn_paper.char_ptb().d_hidden
    kg = -(-H // GROUP[mode])
    K = kg * GROUP[mode]
    x = jax.ShapeDtypeStruct((M, K), jnp.float32, sharding=one_chip)
    wp = jax.ShapeDtypeStruct((kg, 4 * H), jnp.uint32, sharding=one_chip)
    _compile(lambda x, wp: ops.packed_matmul(x, wp, K, 0.05, mode=mode,
                                             interpret=False), x, wp)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("mkn", [(256, 1024, 4096), (32, 1008, 4000),
                                 (128, 28672, 8192), (256, 28672, 8192)])
def test_packed_matmul_compiles(one_chip, mode, mkn):
    """The MXU GEMM (M > 8): a 256-token prefill chunk at rnn-paper's
    padded width, a short ragged one, and llama-3.2-vision-90b's down
    projection, the widest contraction of the configurations, at 128 rows
    (all of K in one block) and at 256 (K in blocks: all of K would not fit
    the VMEM budget in float32)."""
    M, K, N = mkn
    bk = PK._gemm_plan(M, _up(K, 8 * GROUP[mode]), N, 3, GROUP[mode])[3]
    assert (bk < K) == (mkn == (256, 28672, 8192))
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


def _zamba2_7b_matrices():
    """(K, N) of every packed matrix of zamba2-7b (models/transformer.py
    hybrid path, models/mamba2.py)."""
    c = get_config("zamba2-7b")
    d, di, ff, r = c.d_model, c.d_inner, c.d_ff, c.adapter_rank
    d_proj = 2 * di + 2 * c.ssm_groups * c.ssm_state + c.ssm_heads
    return {"mamba_in": (d, d_proj), "mamba_out": (di, d),
            "attn_qkv": (2 * d, c.n_heads * c.head_dim),
            "attn_o": (c.n_heads * c.head_dim, d), "gate_up": (d, 2 * ff),
            "down": (ff, d), "adapter_in": (d, r), "adapter_out": (r, 2 * ff),
            "linear": (d, d)}


@pytest.mark.parametrize("M", [8, 128, 16, 64])
@pytest.mark.parametrize("mat", ["mamba_in", "mamba_out", "attn_qkv",
                                 "attn_o", "gate_up", "down", "adapter_in",
                                 "adapter_out", "linear"])
def test_zamba2_7b_matrix_compiles(one_chip, mat, M):
    """zamba2-7b's ternary matrices through `qmatmul` at their published
    widths, float32 activations: the GEMV of a decode tick over 8 slots,
    and the GEMM of a 128-token prefill chunk and of the 16- and 64-row
    buckets a chunk's tail is padded to."""
    K, N = _zamba2_7b_matrices()[mat]
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    qt = QTensor(codes=S((K // 16, N), jnp.uint32), scale=S((N,), jnp.float32),
                 k=K, mode="ternary", alpha=0.02)
    _compile(lambda x, qt: ops.qmatmul(x, qt, interpret=False),
             S((M, K), jnp.float32), qt)


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("kn", [(3584, 14704), (7168, 3584), (7168, 7168),
                                (14336, 3584), (3584, 28672), (128, 28672),
                                (3584, 128)])
def test_zamba2_7b_gemv_compiles(one_chip, kn, mode):
    """The 8-row GEMV of a zamba2-7b decode tick at every packed width of the model, in both modes, float32 activations:
    the rank-128 adapters contract only 8 (ternary) or 4 (binary) packed
    rows, padded to 8."""
    K, N = kn
    kg = _up(K, GROUP[mode]) // GROUP[mode]
    x = jax.ShapeDtypeStruct((8, K), jnp.float32, sharding=one_chip)
    wp = jax.ShapeDtypeStruct((kg, N), jnp.uint32, sharding=one_chip)
    _compile(lambda x, wp: ops.packed_matmul(x, wp, K, 0.05, mode=mode,
                                             interpret=False), x, wp)


@pytest.mark.parametrize("mode", MODES)
def test_widest_gemv_compiles(one_chip, mode):
    """The 8-row GEMV at the widest contraction of the configurations,
    llama-3.2-vision-90b's down projection (d_ff, d_model): the GEMV decodes
    all of K into one VMEM tile, so its VMEM grows with K."""
    cfg = get_config("llama-3.2-vision-90b")
    K, N = cfg.d_ff, cfg.d_model
    x = jax.ShapeDtypeStruct((8, K), jnp.float32, sharding=one_chip)
    wp = jax.ShapeDtypeStruct((K // GROUP[mode], N), jnp.uint32,
                              sharding=one_chip)
    _compile(lambda x, wp: ops.packed_matmul(x, wp, K, 0.05, mode=mode,
                                             interpret=False), x, wp)


def test_zamba2_7b_decode_tick_compiles_in_place(one_chip, monkeypatch):
    """The whole zamba2-7b decode tick at its published widths, 8 slots at
    1,024 tokens of context, packed ternary, as the engine jits it (pool
    donated): every packed matmul is an 8-row GEMV, the tick fits
    a v5e's HBM, and it updates the SSM states and the 13 calls' K/V
    caches in place — no copy of a 3.4 GB cache stack, so
    next to the arguments it needs only small temporaries."""
    import dataclasses
    from repro.core.qtensor import export_packed
    from repro.kernels import dispatch
    from repro.models import transformer as T

    monkeypatch.setattr(dispatch, "use_pallas", lambda interpret=None: True)
    monkeypatch.setattr(dispatch, "resolve_interpret",
                        lambda interpret=None: False)
    cfg = dataclasses.replace(get_config("zamba2-7b"), dtype="float32")
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), t)
    prm = put(jax.eval_shape(lambda k: export_packed(T.model_init(k, cfg),
                                                     cfg.quant),
                             jax.random.PRNGKey(0)))
    pool = put(jax.eval_shape(lambda: T.init_caches(
        cfg, 8, 1024, dtype=jnp.float32, per_slot=True)))
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def tick(prm, pool, pending, live):
        logits, pool = T.decode_step(prm, pending, pool, cfg, live=live)
        return pool, jnp.argmax(logits, -1)

    jax.clear_caches()               # so every packed matmul traces anew
    before = dispatch.launch_counts()
    compiled = jax.jit(tick, donate_argnums=(1,)).lower(
        prm, pool, S((8,), jnp.int32), S((8,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    traced = {k for k, n in dispatch.launch_counts().items()
              if n != before.get(k, 0)}
    assert traced == {"ternary_packed_gemv"}
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 64 * 2**20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


def test_zamba2_7b_prefill_chunk_compiles_in_place(one_chip, monkeypatch):
    """A 128-token prefill chunk of zamba2-7b as the engine jits it: the
    slot's row gathered from the 8-slot pool (donated), the chunk run over
    the row's 13 K/V caches, the row written back.  It fits next to the
    pool, and no copy of the pool's cache stacks (13 x 8 x 32 x 1,152 x
    256 floats, 3.9 GB each) is made."""
    import dataclasses
    import re
    from repro.core.qtensor import export_packed
    from repro.kernels import dispatch
    from repro.models import transformer as T
    from repro.serve.engine import tree_gather_slot, tree_write_slot

    monkeypatch.setattr(dispatch, "use_pallas", lambda interpret=None: True)
    monkeypatch.setattr(dispatch, "resolve_interpret",
                        lambda interpret=None: False)
    cfg = dataclasses.replace(get_config("zamba2-7b"), dtype="float32")
    put = lambda t: jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), t)
    prm = put(jax.eval_shape(lambda k: export_packed(T.model_init(k, cfg),
                                                     cfg.quant),
                             jax.random.PRNGKey(0)))
    pools = [jax.eval_shape(lambda b=b: T.init_caches(
        cfg, b, 1024, dtype=jnp.float32, per_slot=True)) for b in (8, 1)]
    pool, ref = put(pools[0]), pools[1]
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def prefill_slot(prm, pool, tokens, n, slot):
        sub = tree_gather_slot(pool, ref, slot)
        logits, sub = T.prefill(prm, tokens, sub, cfg, n=n)
        return logits, tree_write_slot(pool, sub, slot)

    compiled = jax.jit(prefill_slot, donate_argnums=(1,)).lower(
        prm, pool, S((1, 128), jnp.int32), S((), jnp.int32),
        S((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    stack = "f32[%d,8," % len(cfg.hybrid_ids)
    assert not [l for l in text.splitlines()
                if stack in l and re.search(r"\bcopy(-start)?\(", l)]
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
