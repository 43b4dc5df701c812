"""Operations and bytes from shapes, checked against hand counts at the
two configurations' published widths, and the peaks table."""
import json
from pathlib import Path

import pytest

from bench import flops
from bench.peaks import PEAKS, peaks_for

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def shape(name):
    return flops.Shape.from_config(
        json.loads((CONFIGS / f"{name}.json").read_text())["config"])


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
    got = flops.paged_attention_call(shape(name), [100], page)
    assert got == (want_bytes, want_flops)
    two = flops.paged_attention_call(shape(name), [100, 100], page)
    assert two == (2 * want_bytes, 2 * want_flops)
    assert flops.paged_attention_call(shape(name), [], page) == (0, 0)


def test_model_flops_per_token():
    m = shape("minicpm-2b")
    # a decoded token: 2 x (matmul params + head) + 4 x L x H x D x (p+1)
    head = 2304 * 122753
    assert flops.decode_token_flops(m, 0) == (
        2 * (40 * 61_046_784 + head) + 4 * 40 * 36 * 64)
    # about 2 x 2.72e9 flops a token at short context
    assert flops.decode_token_flops(m, 0) == pytest.approx(2 * m.params,
                                                           rel=0.01)
    # a chunk of n tokens from 0: n matmul passes, causal n(n+1)/2 keys,
    # one head row
    n = 512
    assert flops.prefill_chunk_flops(m, 0, n) == (
        2 * 40 * 61_046_784 * n + 4 * 40 * 36 * 64 * n * (n + 1) // 2
        + 2 * head)
    # two chunks add up to one
    assert (flops.prefill_chunk_flops(m, 0, 256)
            + flops.prefill_chunk_flops(m, 256, 256)
            == flops.prefill_chunk_flops(m, 0, 512) + 2 * head)


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
