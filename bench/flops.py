"""Operations and bytes computed from shapes: the yardstick that the
roofline shares and the MFU are measured against.  Nothing here reads the
program; the shapes come from a configuration file's published keys.

Conventions: a multiply-add is 2 flops; bf16 is 2 bytes; attention is
counted causally, a token at position p attending p + 1 keys.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

BF16 = 2
INT32 = 4


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool

    @classmethod
    def from_config(cls, hf: dict) -> "Shape":
        """From a configuration file's `config` (Hugging Face keys)."""
        d, h = hf["hidden_size"], hf["num_attention_heads"]
        return cls(n_layers=hf["num_hidden_layers"], d_model=d, n_heads=h,
                   n_kv_heads=hf.get("num_key_value_heads", h),
                   head_dim=hf.get("head_dim", d // h),
                   d_ff=hf["intermediate_size"], vocab=hf["vocab_size"],
                   tied=bool(hf.get("tie_word_embeddings", False)))

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies through in one layer: q, k, v, o
        and the gated MLP's up, gate and down."""
        d, hd, kvd = self.d_model, self.n_heads * self.head_dim, \
            self.n_kv_heads * self.head_dim
        return d * (hd + 2 * kvd) + hd * d + 3 * d * self.d_ff

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def params(self) -> int:
        """Every parameter: layers with their two norms, the embedding (and
        an untied head), the final norm."""
        emb = self.vocab * self.d_model * (1 if self.tied else 2)
        return (self.n_layers * (self.layer_matmul_params + 2 * self.d_model)
                + emb + self.d_model)

    @property
    def kv_bytes_per_token(self) -> int:
        return self.n_layers * 2 * self.n_kv_heads * self.head_dim * BF16

    def attention_flops(self, pos: int) -> int:
        """QK^T and PV of one query at position `pos`, all layers."""
        return 4 * self.n_layers * self.n_heads * self.head_dim * (pos + 1)


def decode_token_flops(s: Shape, pos: int) -> int:
    """Model flops of one decoded token at position `pos`: every layer's
    matmuls, the LM head and causal attention."""
    return 2 * (s.n_layers * s.layer_matmul_params + s.head_params) \
        + s.attention_flops(pos)


def prefill_chunk_flops(s: Shape, start: int, n: int) -> int:
    """Model flops of a prefill chunk of `n` real tokens at positions
    [start, start + n): matmuls and attention of every token, and the LM
    head for the chunk's last position only (the one whose logits are
    read)."""
    attn = 4 * s.n_layers * s.n_heads * s.head_dim * (
        n * (start + 1) + n * (n - 1) // 2)
    return 2 * s.n_layers * s.layer_matmul_params * n + attn \
        + 2 * s.head_params


def paged_attention_call(s: Shape, positions: Iterable[int], page: int):
    """(bytes, flops) that one paged-attention call needs for one layer:
    each decoding row at position p reads its ceil((p + 1) / page) live
    pages of K and of V, its query and its output, and its page-table
    entries; it computes QK^T and PV over those pages.  Rows that decode
    nothing (free, or still in prefill) need nothing."""
    hkv_d = s.n_kv_heads * s.head_dim
    nbytes = flops = 0
    for p in positions:
        pages = p // page + 1
        nbytes += pages * 2 * page * hkv_d * BF16          # K and V pages
        nbytes += 2 * s.n_heads * s.head_dim * BF16         # q in, o out
        nbytes += (pages + 1) * INT32                       # table, pos
        flops += 4 * s.n_heads * s.head_dim * pages * page
    return nbytes, flops


def least_time(nbytes: float, flops: float, peaks) -> tuple:
    """(seconds, bound): the larger of bytes over bandwidth and flops over
    the bf16 peak, and which of the two it is."""
    t_mem = nbytes / peaks.hbm_bytes_s
    t_cmp = flops / peaks.bf16_flops
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
