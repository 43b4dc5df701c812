"""The dense Llama family (Hugging Face `LlamaForCausalLM` keys; MiniCPM
without its muP scales): GQA attention and a gated MLP in every layer,
one scanned stack of layers.  The interface is `bench/families`'s; the
yardstick's conventions are `bench/flops.py`'s.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

from bench.flops import BF16, INT32
from bench.weights import padded_vocab


def model_config(name: str, hf: dict):
    """The engine's `ModelConfig` for a dense Llama-family configuration
    (Hugging Face keys)."""
    from repro.nn.config import ModelConfig

    d, h = hf["hidden_size"], hf["num_attention_heads"]
    return ModelConfig(
        name=name, family="dense", n_layers=hf["num_hidden_layers"],
        d_model=d, n_heads=h, n_kv_heads=hf.get("num_key_value_heads", h),
        head_dim=hf.get("head_dim", d // h), d_ff=hf["intermediate_size"],
        vocab=hf["vocab_size"], rope_theta=float(hf.get("rope_theta", 1e4)),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
        scan_layers=True)


def leaves(hf: dict):
    """(name, shape, kind) of every canonical leaf, in key order."""
    s = Shape.from_config(hf)
    L, d, f = s.n_layers, s.d_model, s.d_ff
    hd, kvd = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    v = padded_vocab(s.vocab)
    out = [("embed", (v, d), "embed")]
    if not s.tied:
        out.append(("head", (d, v), "matmul"))
    out += [("attn_norm", (L, d), "norm"), ("wq", (L, d, hd), "matmul"),
            ("wk", (L, d, kvd), "matmul"), ("wv", (L, d, kvd), "matmul"),
            ("wo", (L, hd, d), "matmul"), ("mlp_norm", (L, d), "norm"),
            ("w_gate", (L, d, f), "matmul"), ("w_up", (L, d, f), "matmul"),
            ("w_down", (L, f, d), "matmul"), ("final_norm", (d,), "norm")]
    return out


def engine_params(w: dict, model_cfg) -> dict:
    """The engine's parameter tree for a dense, scanned stack: one
    segment."""
    embedding = {"table": w["embed"]}
    if "head" in w:
        embedding["head"] = w["head"]
    return {
        "embedding": embedding,
        "stack": {"segments": [{
            "norm1": {"scale": w["attn_norm"]},
            "attn": {"w_q": w["wq"], "w_k": w["wk"], "w_v": w["wv"],
                     "w_o": w["wo"]},
            "norm2": {"scale": w["mlp_norm"]},
            "mlp": {"w_up": w["w_up"], "w_down": w["w_down"],
                    "w_gate": w["w_gate"]},
        }]},
        "final_norm": {"scale": w["final_norm"]},
    }


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

    def decode_token_flops(self, pos: int) -> int:
        """Model flops of one decoded token at position `pos`: every
        layer's matmuls, the LM head and causal attention."""
        return 2 * (self.n_layers * self.layer_matmul_params
                    + self.head_params) + self.attention_flops(pos)

    def prefill_chunk_flops(self, start: int, n: int) -> int:
        """Model flops of a prefill chunk of `n` real tokens at positions
        [start, start + n): matmuls and attention of every token, and the
        LM head for the chunk's last position only (the one whose logits
        are read)."""
        attn = 4 * self.n_layers * self.n_heads * self.head_dim * (
            n * (start + 1) + n * (n - 1) // 2)
        return 2 * self.n_layers * self.layer_matmul_params * n + attn \
            + 2 * self.head_params

    def paged_attention_call(self, positions: Iterable[int], page: int):
        """(bytes, flops) that one paged-attention call needs for one
        layer: each decoding row at position p reads its ceil((p + 1) /
        page) live pages of K and of V, its query and its output, and its
        page-table entries; it computes QK^T and PV over those pages.
        Rows that decode nothing (free, or still in prefill) need
        nothing."""
        hkv_d = self.n_kv_heads * self.head_dim
        nbytes = flops = 0
        for p in positions:
            pages = p // page + 1
            nbytes += pages * 2 * page * hkv_d * BF16       # K and V pages
            nbytes += 2 * self.n_heads * self.head_dim * BF16   # q in, o out
            nbytes += (pages + 1) * INT32                   # table, pos
            flops += 4 * self.n_heads * self.head_dim * pages * page
        return nbytes, flops

    def pool_dims(self, pages: int, page: int):
        """Dims of one K or V page pool, stacked over the scanned layers
        and for one layer: [L, pages, page, Hkv*D] and [pages, page,
        Hkv*D]."""
        layer = (pages, page, self.n_kv_heads * self.head_dim)
        return (self.n_layers,) + layer, layer
