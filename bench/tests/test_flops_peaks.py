"""Operations and bytes from shapes, checked against hand counts at the
two configurations' published widths and against the counts the
benchmark read before the yardstick moved into the family modules, and
the peaks table."""
import pytest

from bench import families, flops, harness, scopes
from bench.families import llama
from bench.peaks import PEAKS, peaks_for


def shape(name):
    return llama.Shape.from_config(harness.load_config(name)["config"])


def test_parameter_counts():
    m = shape("minicpm-2b")
    # 40 x (2304 x 6912 + 2304^2 + 3 x 2304 x 5760) + 122753 x 2304 tied
    assert m.layer_matmul_params == 61_046_784
    assert m.params == pytest.approx(2.72e9, rel=0.002)
    assert m.kv_bytes_per_token == 368_640
    y = shape("yi-34b-s6")
    assert y.layer_matmul_params == 557_842_432
    # 6 layers + embedding + untied head: 7.94 GiB in bf16
    assert y.params * 2 / 2**30 == pytest.approx(7.94, abs=0.01)
    assert y.kv_bytes_per_token == 24_576


@pytest.mark.parametrize("name,page,want_bytes,want_flops", [
    # one row at position 100: 7 pages of 16; K+V 7 x 2 x 16 x 2304 x 2 B,
    # q and o 36 x 64 x 2 B each, 7 table entries and the position
    ("minicpm-2b", 16, 1_032_192 + 9_216 + 32, 4 * 36 * 64 * 7 * 16),
    # 4 pages of 32, Hkv*D = 1024; 56 query heads of 128
    ("yi-34b-s6", 32, 4 * 2 * 32 * 1024 * 2 + 2 * 56 * 128 * 2 + 20,
     4 * 56 * 128 * 4 * 32),
])
def test_paged_attention_hand_count(name, page, want_bytes, want_flops):
    got = shape(name).paged_attention_call([100], page)
    assert got == (want_bytes, want_flops)
    two = shape(name).paged_attention_call([100, 100], page)
    assert two == (2 * want_bytes, 2 * want_flops)
    assert shape(name).paged_attention_call([], page) == (0, 0)


def test_model_flops_per_token():
    m = shape("minicpm-2b")
    # a decoded token: 2 x (matmul params + head) + 4 x L x H x D x (p+1)
    head = 2304 * 122753
    assert m.decode_token_flops(0) == (
        2 * (40 * 61_046_784 + head) + 4 * 40 * 36 * 64)
    # about 2 x 2.72e9 flops a token at short context
    assert m.decode_token_flops(0) == pytest.approx(2 * m.params, rel=0.01)
    # a chunk of n tokens from 0: n matmul passes, causal n(n+1)/2 keys,
    # one head row
    n = 512
    assert m.prefill_chunk_flops(0, n) == (
        2 * 40 * 61_046_784 * n + 4 * 40 * 36 * 64 * n * (n + 1) // 2
        + 2 * head)
    # two chunks add up to one
    assert (m.prefill_chunk_flops(0, 256) + m.prefill_chunk_flops(256, 256)
            == m.prefill_chunk_flops(0, 512) + 2 * head)


# what the benchmark's yardstick read for each configuration before it
# moved into bench/families/llama.py (bench/flops.py's Shape and functions,
# bench/scopes.py's pool_dims): every count must stay the same integer
PINNED = {
    "yi-34b-s6": dict(
        params=4_264_651_776, decode_0=7_611_785_216,
        decode_1000=7_783_817_216, prefill_512_512=3_495_991_181_312,
        paged_attention=(4_833_440, 33_030_144),
        pools=((6, 2561, 32, 1024), (2561, 32, 1024))),
    "minicpm-2b": dict(
        params=2_724_880_896, decode_0=5_449_757_184,
        decode_1000=5_818_397_184, prefill_512_512=2_646_091_436_544,
        paged_attention=(10_211_620, 10_174_464),
        pools=((40, 577, 16, 2304), (577, 16, 2304))),
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_yardstick_counts_are_pinned(name):
    conf = harness.load_config(name)
    want = PINNED[name]
    s = families.shape(conf)
    assert type(s) is llama.Shape
    assert s.params == want["params"]
    assert s.decode_token_flops(0) == want["decode_0"]
    assert s.decode_token_flops(1000) == want["decode_1000"]
    assert s.prefill_chunk_flops(512, 512) == want["prefill_512_512"]
    page = conf["engine"]["page_size"]
    assert s.paged_attention_call([0, 31, 32, 1000], page) == \
        want["paged_attention"]
    assert scopes.pool_dims(conf) == want["pools"]


def test_least_time_names_its_bound():
    p = peaks_for("TPU v5 lite")
    t, which = flops.least_time(819e9, 1.0, p)
    assert which == "memory" and t == pytest.approx(1.0)
    t, which = flops.least_time(1.0, 197e12, p)
    assert which == "compute" and t == pytest.approx(1.0)
    assert p.ridge == pytest.approx(240.5, abs=0.1)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v99")
    assert peaks_for("TPU v5 lite").hbm_bytes_s == 819e9
    assert all(p.source for p in PEAKS.values())
