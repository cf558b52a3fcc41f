"""End-to-end behaviour of the whole system: quantized training improves a
real (synthetic-corpus) LM, the launcher round-trips through preemption, and
the roofline/analysis plumbing is self-consistent."""
import json
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.shapes import SHAPES, ShapeSpec
from repro.configs import get_config
from repro.launch.roofline import (Roofline, analytic_hbm_bytes,
                                   collective_wire_bytes, model_flops,
                                   param_counts)

REPO = Path(__file__).resolve().parents[1]


def test_train_launcher_end_to_end():
    """The public CLI trains a reduced arch on synthetic data and the loss
    decreases (example app (b) requirement exercised in CI)."""
    from repro.launch.train import main
    state = main(["--arch", "qwen3-0.6b", "--reduced", "--steps", "12",
                  "--batch", "4", "--seq", "32", "--log-every", "6"])
    assert state is not None


def test_train_launcher_resume_roundtrip(tmp_path):
    from repro.launch.train import main
    args = ["--arch", "qwen3-0.6b", "--reduced", "--batch", "4", "--seq",
            "16", "--ckpt-dir", str(tmp_path), "--ckpt-every", "5",
            "--log-every", "100"]
    main(args + ["--steps", "6"])
    # second run resumes from step 6's checkpoint and continues
    main(args + ["--steps", "10", "--resume", "auto"])
    from repro.train import checkpoint as CK
    assert CK.latest_step(tmp_path) == 10


def test_quantized_beats_random_on_structured_corpus():
    """Ternary model learns a Markov corpus well below uniform entropy."""
    from repro.core import bnlstm as BL
    from repro.core.quantize import QuantSpec
    from repro.data.synth import markov_bytes
    from repro.data.text import ByteCorpus
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import (make_rnn_train_step, make_rnn_eval,
                                        train_state_init)

    data = markov_bytes(40_000, vocab=32, seed=0)
    corpus = ByteCorpus.from_bytes(bytes(bytearray(np.asarray(data) % 256)))
    cfg = BL.RNNConfig(vocab=corpus.vocab, d_hidden=64,
                       quant=QuantSpec(mode="ternary", norm="batch"))
    var = BL.rnn_lm_init(jax.random.PRNGKey(0), cfg)
    st = train_state_init(var["params"], OptConfig(lr=5e-3),
                          jax.random.PRNGKey(1), bn_state=var["state"])
    step = jax.jit(make_rnn_train_step(cfg, OptConfig(lr=5e-3)))
    for i in range(60):
        b = {k: jnp.asarray(v) for k, v in
             corpus.batch("train", i, 16, 32).items()}
        st, m = step(st, b)
    ev = jax.jit(make_rnn_eval(cfg))
    b = {k: jnp.asarray(v) for k, v in corpus.batch("valid", 0, 16, 32).items()}
    bpc = float(ev(st, b)["bpc"])
    uniform = np.log2(corpus.vocab)
    assert bpc < uniform * 0.8, f"bpc {bpc} vs uniform {uniform}"


# --- roofline plumbing -------------------------------------------------------

def test_collective_parser_on_synthetic_hlo():
    hlo = """
  %all-reduce.1 = f32[128,1024]{1,0} all-reduce(%dot), replica_groups=[2,4]<=[8]
  %ag = bf16[64,64]{1,0} all-gather(%x), replica_groups=[16,16]<=[256]
  %all-reduce-done.1 = f32[128,1024]{1,0} all-reduce-done(%ar)
"""
    out = collective_wire_bytes(hlo)
    assert out["all-reduce"] == pytest.approx(2 * 128 * 1024 * 4 * 3 / 4)
    assert out["all-gather"] == pytest.approx(64 * 64 * 2 * 15 / 16)


def test_param_counts_sane():
    total, active = param_counts(get_config("llama3-8b"))
    assert 7.5e9 < total < 9e9 and total == active
    total, active = param_counts(get_config("mixtral-8x7b"))
    assert 44e9 < total < 50e9 and 11e9 < active < 15e9
    total, active = param_counts(get_config("qwen3-moe-30b-a3b"))
    assert 28e9 < total < 33e9 and 2.5e9 < active < 4.5e9
    total, active = param_counts(get_config("llama-3.2-vision-90b"))
    assert 80e9 < total < 100e9
    # zamba2-7b holds two shared blocks and runs them at 13 calls a token
    total, active = param_counts(get_config("zamba2-7b"))
    assert 7.3e9 < total < 7.4e9 and 10.9e9 < active < 11.1e9


def test_model_flops_conventions():
    cfg = get_config("llama3-8b")
    tr = model_flops(cfg, SHAPES["train_4k"], 256)
    de = model_flops(cfg, SHAPES["decode_32k"], 256)
    _, active = param_counts(cfg)
    assert tr == pytest.approx(6 * active * 256 * 4096 / 256)
    assert de == pytest.approx(2 * active * 128 / 256)


def test_analytic_memory_packed_weights_shrink_decode():
    """The paper's claim, translated: packed 2-bit weights cut decode HBM
    traffic (weight stream) ~16x vs bf16 when weights dominate."""
    cfg = get_config("qwen3-1.7b")
    sh = ShapeSpec("decode_small", 1024, 1, "decode")
    full = analytic_hbm_bytes(cfg, sh, 1, weight_bits=16)
    packed = analytic_hbm_bytes(cfg, sh, 1, weight_bits=2)
    assert full / packed > 5  # weight-dominated at short context / batch 1


def test_roofline_dataclass_terms():
    r = Roofline(flops=197e12, hbm_bytes=819e9, wire_bytes=25e9,
                 collectives={"all-gather": 25e9}, model_flops=98.5e12)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(1.0)
    assert r.t_collective == pytest.approx(0.5)
    assert r.dominant in ("compute", "memory")
    assert r.useful_flop_ratio == pytest.approx(0.5)


def test_dryrun_results_if_present():
    """Validate any dry-run cells already produced by the sweep."""
    outdir = REPO / "results" / "dryrun"
    if not outdir.exists():
        pytest.skip("no dry-run results yet")
    cells = [json.loads(p.read_text()) for p in outdir.glob("*.json")]
    if not cells:
        pytest.skip("no cells yet")
    for c in cells:
        assert c["status"] in ("ok", "skipped", "error")
        if c["status"] == "ok":
            assert c["flops"] > 0
            assert c["roofline"]["dominant"] in ("compute", "memory",
                                                 "collective")
