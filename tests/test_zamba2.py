"""The zamba2 hybrid against its plain reference, and the reference
against the published modeling code, at a small size on the CPU: two
shared blocks called in turn, two B/C groups, gate_up adapters, irregular
call sites (before layers 1, 3 and 4 of 6) and the concat input.

Tolerances: every path here computes in float32 (the program's products
at HIGHEST).  The chunked SSD scan and the reference's token-by-token
recurrence, or a prompt split into other chunks, add the same numbers in
another order, so they agree to float32 rounding: 1e-4 of the largest
logit for logits, 1e-4 RMS/RMS for states."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.models import zamba2 as Z  # noqa: E402
from bench.models import zamba2_check as CHECK  # noqa: E402
from bench.models import zamba2_data as DATA  # noqa: E402
from bench.models import zamba2_reference as REF  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import mamba2 as M  # noqa: E402
from repro.models import transformer as T  # noqa: E402

SEED = 2**33 + 17
TOL = 1e-4

SMALL = {"program": "zamba2-7b", "control": "bfloat16", "vocab": 256,
         "vocab_size": 256, "hidden_size": 64, "mamba_expand": 2,
         "n_mamba_heads": 8, "mamba_headdim": 16, "mamba_ngroups": 2,
         "mamba_d_state": 16, "mamba_d_conv": 4, "num_attention_heads": 4,
         "num_key_value_heads": 4, "attention_head_dim": 32,
         "intermediate_size": 128, "adapter_rank": 8,
         "num_hidden_layers": 6, "hybrid_layer_ids": [1, 3, 4],
         "num_mem_blocks": 2, "rms_norm_eps": 1e-5, "rope_theta": 10000,
         "time_step_min": 0.001, "time_step_max": 0.1}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2) / np.mean(b * b)))


def _reference_logits(cfg, seed, tokens):
    s = REF.Stream(cfg, seed, jnp.asarray(tokens),
                   jnp.full(tokens.shape[0], -1, jnp.int32))
    for i in range(cfg["num_hidden_layers"]):
        s.step(i)
    return np.asarray(s.logits())


# --- the reference against transformers' modeling_zamba2 ------------------


def _hf_model(chunk_size):
    """A small `Zamba2ForCausalLM` of SMALL's shape, float32, its weights
    drawn so that every layer stays in a sound range."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    c = SMALL
    types = ["hybrid" if i in c["hybrid_layer_ids"] else "mamba"
             for i in range(c["num_hidden_layers"])]
    hcfg = tf.Zamba2Config(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_hidden_layers=c["num_hidden_layers"], layers_block_type=types,
        mamba_d_state=c["mamba_d_state"], mamba_d_conv=c["mamba_d_conv"],
        mamba_expand=c["mamba_expand"], mamba_ngroups=c["mamba_ngroups"],
        n_mamba_heads=c["n_mamba_heads"],
        intermediate_size=c["intermediate_size"],
        num_attention_heads=c["num_attention_heads"],
        num_key_value_heads=c["num_key_value_heads"],
        num_mem_blocks=c["num_mem_blocks"], adapter_rank=c["adapter_rank"],
        use_shared_attention_adapter=False, use_mem_rope=True,
        rope_theta=c["rope_theta"], rms_norm_eps=c["rms_norm_eps"],
        chunk_size=chunk_size, time_step_min=c["time_step_min"],
        time_step_max=c["time_step_max"], tie_word_embeddings=True,
        attn_implementation="eager")
    torch.manual_seed(0)
    model = tf.Zamba2ForCausalLM(hcfg).float().eval()
    g = torch.Generator().manual_seed(1)
    with torch.no_grad():
        for name, p in model.named_parameters():
            r = torch.rand(p.shape, generator=g)
            if name.endswith("A_log"):
                p.copy_(torch.log(1.0 + 15.0 * r))
            elif name.endswith("dt_bias"):
                p.copy_(-4.0 + 2.0 * r)
            elif p.ndim == 1:            # norms, D, conv bias
                p.copy_(0.8 + 0.4 * r)
            else:
                fan_in = p.shape[-1]
                p.copy_((2.0 * r - 1.0) * (3.0 / fan_in) ** 0.5)
    return model


def _reference_on(model, tokens):
    """The reference's logits over `tokens` with the weights of `model`."""
    m = model.model
    t = lambda x: jnp.asarray(x.detach().numpy())
    lin = lambda mod: (t(mod.weight).T, jnp.ones(mod.weight.shape[0]))
    D = DATA.dims(SMALL)
    we = {"embed": t(m.embed_tokens.weight),
          "final_norm": t(m.final_layernorm.weight)}
    x = jnp.take(we["embed"], jnp.asarray(tokens), axis=0)
    emb = x
    cap = jnp.full(tokens.shape[0], -1, jnp.int32)
    call = 0
    for layer in m.layers:
        tt = jnp.zeros_like(x)
        if hasattr(layer, "shared_transformer"):
            st = layer.shared_transformer
            at, ff = st.self_attn, st.feed_forward
            ad = ff.gate_up_proj_adapter_list[call]
            ws = {"norm1": t(st.input_layernorm.weight),
                  "q": lin(at.q_proj), "k": lin(at.k_proj),
                  "v": lin(at.v_proj), "o": lin(at.o_proj),
                  "norm2": t(st.pre_ff_layernorm.weight),
                  "gate_up": lin(ff.gate_up_proj), "down": lin(ff.down_proj)}
            wc = {"adapter_in": lin(ad[0]), "adapter_out": lin(ad[1]),
                  "linear": lin(layer.linear)}
            tt, _, _ = REF.shared(D, ws, wc, x, emb, REF.dot_highest)
            layer = layer.mamba_decoder
            call += 1
        mx = layer.mamba
        wm = {"norm": t(layer.input_layernorm.weight), "in_proj": lin(mx.in_proj),
              "conv_w": t(mx.conv1d.weight)[:, 0, :].T,
              "conv_b": t(mx.conv1d.bias), "A_log": t(mx.A_log),
              "D": t(mx.D), "dt_bias": t(mx.dt_bias),
              "gnorm": t(mx.norm.weight), "out_proj": lin(mx.out_proj)}
        x, _ = REF.mamba(D, wm, x, tt, cap, REF.dot_highest)
    assert call == len(SMALL["hybrid_layer_ids"])
    return np.asarray(REF.logits(D, we, x, REF.dot_highest))


def test_reference_equals_transformers_zamba2():
    """The reference, given the weights of a small `Zamba2ForCausalLM`,
    reproduces its logits over a whole prompt.  transformers' CPU path
    (`torch_forward`) runs here with one SSD chunk (chunk_size 32 > 20
    tokens): its naive multi-chunk scan gives other logits from the first
    chunk boundary on (with chunk_size 8, up to 0.75 at positions 8-19,
    while chunk_size is only an implementation setting); the next test
    ties the reference to its token-by-token path past that boundary."""
    import torch
    model = _hf_model(32)
    tokens = np.random.default_rng(3).integers(0, SMALL["vocab_size"],
                                               (2, 20))
    ours = _reference_on(model, tokens)
    with torch.no_grad():
        theirs = model(input_ids=torch.tensor(tokens), use_cache=False
                       ).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=0,
                               atol=TOL * np.abs(theirs).max())


def test_reference_equals_transformers_zamba2_steps():
    """The reference over 44 tokens (chunk_size 8 in transformers' config,
    so five chunk boundaries) against `Zamba2ForCausalLM` fed one token at
    a time through its cache: its step path (the recurrence on the carried
    SSM state and conv tail, attention over the cached keys), which no SSD
    chunking touches."""
    import torch
    from transformers.models.zamba2.modeling_zamba2 import (
        Zamba2HybridDynamicCache)
    model = _hf_model(8)
    tokens = np.random.default_rng(4).integers(0, SMALL["vocab_size"],
                                               (2, 44))
    ours = _reference_on(model, tokens)
    cache = Zamba2HybridDynamicCache(model.config, 2, dtype=torch.float32)
    theirs = []
    with torch.no_grad():
        for i in range(tokens.shape[1]):
            out = model(input_ids=torch.tensor(tokens[:, i:i + 1]),
                        past_key_values=cache, use_cache=True,
                        cache_position=torch.tensor([i]))
            theirs.append(out.logits[:, -1].numpy())
    theirs = np.stack(theirs, axis=1)
    np.testing.assert_allclose(ours, theirs, rtol=0,
                               atol=TOL * np.abs(theirs).max())


# --- the program against the reference --------------------------------------


def _program(seed=SEED):
    from repro.serve.recurrent import serving_runtime
    pcfg = Z.program_config(SMALL)
    return pcfg, serving_runtime(pcfg, Z.program_params(SMALL, seed))


def test_runtime_prefill_decode_equals_reference():
    """Packed ternary weights from the seed: prefill in bucket-padded
    chunks, then decode, against the reference's full forward."""
    pcfg, rt = _program()
    tokens = np.random.default_rng(5).integers(0, 256, (1, 24)).astype(
        np.int32)
    ref = _reference_logits(SMALL, SEED, tokens)[0]
    scale = np.abs(ref).max()
    st = rt.init_state(1, 32, per_slot=True)
    got = []
    for lo, hi in ((0, 8), (8, 13), (13, 20)):
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, :hi - lo] = tokens[0, lo:hi]
        lg, st = jax.jit(rt.prefill_chunk)(jnp.asarray(chunk), st,
                                           jnp.int32(hi - lo))
        got.append((hi - 1, lg[0]))
    for i in range(20, 24):
        lg, st = jax.jit(rt.decode_fn)(jnp.asarray(tokens[:, i]), st)
        got.append((i, lg[0]))
    for pos, lg in got:
        np.testing.assert_allclose(np.asarray(lg)[:256], ref[pos], rtol=0,
                                   atol=TOL * scale)


def test_engine_equals_reference():
    """Requests through ServeEngine (bucket-padded chunks of 8, two slots,
    a slot reused after a scrub): every greedy token is the reference's
    argmax (greedy_gap 0), and the SSM states of all layers and the K/V of
    all calls of the slots still decoding match the reference's after the
    same tokens (state_err, RMS/RMS)."""
    from repro.serve.engine import Request, ServeEngine
    pcfg, rt = _program()
    eng = ServeEngine(rt, 256, slots=2, max_context=48, prefill_chunk=8)
    eng.warm()
    rng = np.random.default_rng(9)
    reqs = [Request(prompt=rng.integers(0, 256, n).astype(np.int32),
                    max_tokens=m, temperature=0.0, seed=i, rid=i)
            for i, (n, m) in enumerate(((13, 6), (5, 9), (20, 4)))]
    comps, _ = eng.run(reqs, realtime=False)
    greedy = [(r.prompt, c.tokens) for r, c in
              zip(reqs, sorted(comps, key=lambda c: c.rid))]
    live = [Request(prompt=rng.integers(0, 256, n).astype(np.int32),
                    max_tokens=30, temperature=0.0, seed=7 + n, rid=100 + n)
            for n in (11, 17)]
    for r in live:
        eng.submit(r)
    for _ in range(12):
        eng.step()
    state = Z.read_state(eng)
    rows = []
    for r in live:
        c = eng.cancel(r.rid)
        rows.append((r.prompt, list(c.tokens), c.slot))
    res = CHECK.check(SMALL, SEED, greedy_rows=greedy, slot_rows=rows,
                      state=state, t_max=48, n_greedy=4)
    assert res["slot_rows"] == 2 and res["greedy_tokens"] == 19
    assert res["greedy_gap"] == 0.0, res
    assert res["state_err"] < TOL, res


def test_check_reads_slots_mid_prefill():
    """A window can close while slots are still prefilling: one slot has
    run one 8-token chunk of its prompt (compared at its 8 positions), the
    other none (nothing to compare, not a bookkeeping fault)."""
    from repro.serve.engine import Request, ServeEngine
    _, rt = _program()
    eng = ServeEngine(rt, 256, slots=2, max_context=48, prefill_chunk=8)
    rng = np.random.default_rng(11)
    reqs = [Request(prompt=rng.integers(0, 256, 20).astype(np.int32),
                    max_tokens=4, temperature=0.0, seed=i, rid=i)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.step()
    state = Z.read_state(eng)
    assert sorted(state["pos"].tolist()) == [0, 8]
    rows = [(r.prompt, list(c.tokens), c.slot)
            for r, c in ((r, eng.cancel(r.rid)) for r in reqs)]
    res = CHECK.check(SMALL, SEED, greedy_rows=[], slot_rows=rows,
                      state=state, t_max=48, n_greedy=4)
    assert res["slot_rows"] == 1
    assert res["state_err"] < TOL, res


def test_bucket_padding_is_exact():
    """A prompt prefilled as a full chunk and a padded tail (junk in the
    pad) leaves the same logits and state as the same tokens in one call;
    and a pad token leaves h and the conv tail exactly as they were."""
    cfg = get_config("zamba2-7b").reduced()
    params = T.model_init(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, cfg.vocab)
    c1 = T.init_caches(cfg, 1, 32, dtype=jnp.float32, per_slot=True)
    l1, c1 = T.prefill(params, toks[:, :11], c1, cfg)
    c2 = T.init_caches(cfg, 1, 32, dtype=jnp.float32, per_slot=True)
    _, c2 = T.prefill(params, toks[:, :8], c2, cfg, n=8)
    tail = jnp.concatenate([toks[:, 8:11], toks[:, 11:16]], axis=1)
    l2, c2 = T.prefill(params, tail, c2, cfg, n=3)
    np.testing.assert_allclose(l2, l1, rtol=0, atol=TOL * np.abs(l1).max())
    assert _rel(c2["ssm"].h, c1["ssm"].h) < TOL
    assert _rel(c2["ssm"].conv, c1["ssm"].conv) < TOL
    np.testing.assert_array_equal(c2["ssm"].pos, c1["ssm"].pos)
    np.testing.assert_array_equal(c2["kv"].pos, c1["kv"].pos)
    n = int(c1["kv"].pos[0, 0])
    assert n == 11
    assert _rel(c2["kv"].k[:, :, :, :n], c1["kv"].k[:, :, :, :n]) < TOL

    p = jax.tree.map(lambda a: a[0], params["mamba"])["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, cfg.d_model))
    st = M.state_init(cfg, 1)
    st = st._replace(h=jax.random.normal(jax.random.PRNGKey(3), st.h.shape),
                     conv=jax.random.normal(jax.random.PRNGKey(4),
                                            st.conv.shape))
    _, real = M.mamba2_apply(p, x[:, :5], cfg, state=st, decode=False)
    _, pad = M.mamba2_apply(p, x, cfg, state=st, decode=False, n=5)
    np.testing.assert_allclose(pad.h, real.h, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(pad.conv, real.conv)
    assert int(pad.pos) == int(real.pos) == 5
    _, none = M.mamba2_apply(p, x, cfg, state=st, decode=False, n=0)
    np.testing.assert_array_equal(none.h, st.h)
    np.testing.assert_array_equal(none.conv, st.conv)


def test_published_widths_shape_count():
    """zamba2-7b at its published widths, shapes only: 7.24 B ternary
    matmul weights and 0.11 B of embedding make 7.35 B; norms, the conv
    and the channel scales add 6 M.  The weights pack into 1.81 GB of
    codes."""
    from repro.core.qtensor import export_packed, is_qtensor
    cfg = get_config("zamba2-7b")
    shapes = jax.eval_shape(lambda k: T.model_init(k, cfg),
                            jax.random.PRNGKey(0))
    total = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
    packed = jax.eval_shape(lambda k: export_packed(T.model_init(k, cfg),
                                                    cfg.quant),
                            jax.random.PRNGKey(0))
    qts = [l for l in jax.tree.leaves(packed, is_leaf=is_qtensor)
           if is_qtensor(l)]
    weights = sum(int(np.prod(q.shape)) for q in qts)
    codes = sum(int(np.prod(q.codes.shape)) * 4 for q in qts)
    embed = int(np.prod(shapes["embed"].shape))
    assert round(weights / 1e9, 2) == 7.24
    assert round((weights + embed) / 1e9, 2) == 7.35
    assert 0 < total - weights - embed < 7e6
    assert round(codes / 1e9, 2) == 1.81
    assert len(cfg.hybrid_ids) == 13 and cfg.n_layers == 81
