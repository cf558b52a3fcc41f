import pytest

from bench import peaks as PK
from bench import spec as SPEC
from bench import work as WK


# word-level PTB large (Table 3): 2 layers of 1500, vocab 10,000; not a
# cell yet (PERF.md, Open questions), its shapes checked here all the same
WORD_PTB_LARGE = {"vocab": 10000, "d_hidden": 1500, "n_layers": 2,
                  "cell": "lstm", "quant": "ternary"}


def test_model_flops_at_published_shapes():
    char = SPEC.config("char-ptb")
    word = WORD_PTB_LARGE
    # 2 (4 H^2 + H V): one layer of H=1000, vocab 50
    assert WK.model_flops_per_token(char) == 2 * (4 * 1000 * 1000 + 1000 * 50)
    # 2 (3 * 4 H^2 + H V): h-side of both layers and layer 1's input side
    assert WK.model_flops_per_token(word) == \
        2 * (3 * 4 * 1500 * 1500 + 1500 * 10000)
    assert WK.recurrent_flops_per_token(word) == 2 * 3 * 4 * 1500 * 1500


def test_decode_tick_work_char_ptb():
    cfg = SPEC.config("char-ptb")
    ops, nbytes = WK.decode_tick_work(cfg, rows=8)
    assert ops == 2 * 8 * 1000 * 4000
    codes = 1000 * 4000 * 2 / 8            # 2-bit ternary codes, 1 MB
    state = 4 * 1 * 8 * 1000 * 4           # h, c read and written
    act = 8 * 4000 * 4                     # gathered layer-0 pre-activation
    assert nbytes == codes + state + act
    peak = PK.peaks("TPU v5 lite")
    least = WK.least_seconds((ops, nbytes), peak)
    assert least == pytest.approx(nbytes / 819e9)   # bytes bound it
    assert 1e-6 < least < 2e-6


def test_gemv_and_fp_work():
    cfg = SPEC.config("char-ptb")
    ops, nbytes = WK.gemv_work(cfg, rows=1)
    assert ops == 2 * 1000 * 4000
    assert nbytes == 1000 * 4000 / 4 + (1000 + 4000) * 4
    fp = dict(cfg, quant="none")
    _, fbytes = WK.decode_tick_work(fp, rows=8)
    assert fbytes > 16e6                     # float32 weights, 16 MB


def test_peaks_table():
    p = PK.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        PK.peaks("TPU v4")
