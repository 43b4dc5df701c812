"""The DeepSeek-V2 family (Hugging Face `DeepseekV2ForCausalLM` keys):
multi-head latent attention (MLA) without q-LoRA in every layer, YaRN
rotary scaling, a dense gated MLP in the leading `first_k_dense_replace`
layers and a mixture of experts in the rest (a softmax router over every
routed expert, greedy top-k, shared experts beside them).

A configuration may hold one device's share of each expert layer: its
`n_routed_experts` then counts the experts held here (ids 0 to n - 1) and
`router_outputs` the router's published width, which routing keeps.  The
interface is `bench/families`'s; the yardstick's conventions are
`bench/flops.py`'s.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable

from bench.flops import BF16, INT32
from bench.weights import padded_vocab

F32 = 4
LANES = 128     # the TPU's lane tile: the rope-key page pool's width unit


def _checked(hf: dict) -> dict:
    """The keys this family does not model must hold the values it
    assumes; anything else is refused rather than silently mis-served."""
    want = {"q_lora_rank": None, "moe_layer_freq": 1, "n_group": 1,
            "topk_group": 1, "topk_method": "greedy",
            "scoring_func": "softmax", "hidden_act": "silu",
            "attention_bias": False}
    for k, v in want.items():
        if hf.get(k, v) != v:
            raise ValueError(f"deepseek_v2 family: {k}={hf[k]!r} is not "
                             f"modelled (only {v!r})")
    return hf


def _yarn(hf: dict):
    from repro.nn.config import YarnScaling

    rs = hf.get("rope_scaling")
    if not rs:
        return None
    if rs.get("type") != "yarn":
        raise ValueError(f"deepseek_v2 family: rope_scaling {rs!r} is not "
                         "modelled (only yarn)")
    return YarnScaling(
        factor=float(rs["factor"]),
        original_max_position=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs.get("beta_fast", 32)),
        beta_slow=float(rs.get("beta_slow", 1)),
        mscale=float(rs.get("mscale", 1)),
        mscale_all_dim=float(rs.get("mscale_all_dim", 0)))


def model_config(name: str, hf: dict):
    """The engine's `ModelConfig` (Hugging Face keys)."""
    from repro.nn.config import ModelConfig

    s = Shape.from_config(_checked(hf))
    return ModelConfig(
        name=name, family="moe", n_layers=s.n_layers, d_model=s.d_model,
        n_heads=s.n_heads, n_kv_heads=s.n_heads,
        head_dim=s.nope + s.rope, d_ff=s.d_ff, vocab=s.vocab,
        attn_type="mla", kv_lora_rank=s.kv_rank, qk_nope_dim=s.nope,
        qk_rope_dim=s.rope, v_head_dim=s.v_dim,
        rope_theta=float(hf.get("rope_theta", 1e4)),
        rope_scaling=_yarn(hf), norm_eps=float(hf["rms_norm_eps"]),
        n_experts=s.n_experts, n_experts_held=s.n_held,
        n_shared_experts=s.n_shared, moe_topk=s.topk,
        d_ff_expert=s.d_ff_expert, first_dense_layers=s.first_dense,
        norm_topk_prob=bool(hf["norm_topk_prob"]),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        tie_embeddings=s.tied, scan_layers=True)


def _attn_leaves(s: "Shape", lead: tuple):
    d, H, R = s.d_model, s.n_heads, s.kv_rank
    return [("attn_norm", lead + (d,), "norm"),
            ("wq", lead + (d, H * (s.nope + s.rope)), "matmul"),
            ("w_dkv", lead + (d, R), "matmul"),
            ("kv_norm", lead + (R,), "norm"),
            ("w_kr", lead + (d, s.rope), "matmul"),
            ("w_uk", lead + (R, H * s.nope), "matmul_f32"),
            ("w_uv", lead + (R, H * s.v_dim), "matmul_f32"),
            ("wo", lead + (H * s.v_dim, d), "matmul"),
            ("mlp_norm", lead + (d,), "norm")]


def leaves(hf: dict):
    """(name, shape, kind) of every canonical leaf, in key order: the
    embedding and head, each leading dense layer (`dense<i>/...`), the
    expert layers stacked (`moe/...`), the final norm."""
    s = Shape.from_config(hf)
    d, v = s.d_model, padded_vocab(s.vocab)
    out = [("embed", (v, d), "embed")]
    if not s.tied:
        out.append(("head", (d, v), "matmul"))
    for i in range(s.first_dense):
        out += [(f"dense{i}/{n}", sh, k) for n, sh, k in _attn_leaves(s, ())]
        out += [(f"dense{i}/w_gate", (d, s.d_ff), "matmul"),
                (f"dense{i}/w_up", (d, s.d_ff), "matmul"),
                (f"dense{i}/w_down", (s.d_ff, d), "matmul")]
    L, E, f = s.n_moe, s.n_held, s.d_ff_expert
    fs = f * s.n_shared
    out += [(f"moe/{n}", sh, k) for n, sh, k in _attn_leaves(s, (L,))]
    out += [("moe/router", (L, d, s.n_experts), "matmul_f32"),
            ("moe/w_gate", (L, E, d, f), "matmul"),
            ("moe/w_up", (L, E, d, f), "matmul"),
            ("moe/w_down", (L, E, f, d), "matmul")]
    if s.n_shared:
        out += [("moe/shared_w_gate", (L, d, fs), "matmul"),
                ("moe/shared_w_up", (L, d, fs), "matmul"),
                ("moe/shared_w_down", (L, fs, d), "matmul")]
    out.append(("final_norm", (d,), "norm"))
    return out


def _block(w: dict, p: str, mlp: dict) -> dict:
    return {
        "norm1": {"scale": w[p + "attn_norm"]},
        "attn": {"w_q": w[p + "wq"], "w_dkv": w[p + "w_dkv"],
                 "w_kr": w[p + "w_kr"], "w_uk": w[p + "w_uk"],
                 "w_uv": w[p + "w_uv"], "w_o": w[p + "wo"],
                 "kv_norm": {"scale": w[p + "kv_norm"]}},
        "norm2": {"scale": w[p + "mlp_norm"]},
        **mlp,
    }


def engine_params(w: dict, model_cfg) -> dict:
    """The engine's tree: one unscanned segment per leading dense layer,
    then the expert layers as one scanned segment."""
    segments = [_block(w, f"dense{i}/", {"mlp": {
        "w_up": w[f"dense{i}/w_up"], "w_down": w[f"dense{i}/w_down"],
        "w_gate": w[f"dense{i}/w_gate"]}})
        for i in range(model_cfg.first_dense_layers)]
    moe = {"router": w["moe/router"], "w_gate": w["moe/w_gate"],
           "w_up": w["moe/w_up"], "w_down": w["moe/w_down"]}
    if "moe/shared_w_up" in w:
        moe["shared"] = {"w_up": w["moe/shared_w_up"],
                         "w_down": w["moe/shared_w_down"],
                         "w_gate": w["moe/shared_w_gate"]}
    segments.append(_block(w, "moe/", {"moe": moe}))
    embedding = {"table": w["embed"]}
    if "head" in w:
        embedding["head"] = w["head"]
    return {"embedding": embedding, "stack": {"segments": segments},
            "final_norm": {"scale": w["final_norm"]}}


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    nope: int           # qk_nope_head_dim
    rope: int           # qk_rope_head_dim
    v_dim: int
    kv_rank: int        # kv_lora_rank: the cached latent's width
    d_ff: int           # the dense layers' MLP width
    d_ff_expert: int
    n_experts: int      # the router's outputs
    n_held: int         # experts held here of each expert layer
    n_shared: int
    topk: int
    first_dense: int
    vocab: int
    tied: bool

    @classmethod
    def from_config(cls, hf: dict) -> "Shape":
        """From a configuration file's `config` (Hugging Face keys)."""
        held = hf["n_routed_experts"]
        return cls(
            n_layers=hf["num_hidden_layers"], d_model=hf["hidden_size"],
            n_heads=hf["num_attention_heads"],
            nope=hf["qk_nope_head_dim"], rope=hf["qk_rope_head_dim"],
            v_dim=hf["v_head_dim"], kv_rank=hf["kv_lora_rank"],
            d_ff=hf["intermediate_size"],
            d_ff_expert=hf["moe_intermediate_size"],
            n_experts=hf.get("router_outputs", held), n_held=held,
            n_shared=hf.get("n_shared_experts") or 0,
            topk=hf["num_experts_per_tok"],
            first_dense=hf["first_k_dense_replace"],
            vocab=hf["vocab_size"],
            tied=bool(hf.get("tie_word_embeddings", False)))

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.first_dense

    @property
    def routed_share(self) -> float:
        """Expert slots a token sends to the experts held here, expected
        under uniform routing: topk · held / router outputs (0.75 for 6 of
        64 experts, 8 held).  The yardstick's MoE flops use it."""
        return self.topk * self.n_held / self.n_experts

    # ----------------------------------------------------------- weights
    def _attn_params(self) -> int:
        d, H, R = self.d_model, self.n_heads, self.kv_rank
        return (d * H * (self.nope + self.rope) + d * R + d * self.rope
                + R * H * (self.nope + self.v_dim) + H * self.v_dim * d)

    @property
    def params(self) -> int:
        """Every parameter held: the layers with their norms (kv_norm
        too), the router, the held and shared experts, the embedding (and
        an untied head), the final norm."""
        d, f = self.d_model, self.d_ff_expert
        layer = self._attn_params() + 2 * d + self.kv_rank
        dense = self.first_dense * (layer + 3 * d * self.d_ff)
        moe = self.n_moe * (layer + d * self.n_experts
                            + 3 * d * f * (self.n_held + self.n_shared))
        emb = self.vocab * d * (1 if self.tied else 2)
        return dense + moe + emb + d

    # ------------------------------------------------------------- flops
    def _mlp_token_flops(self) -> float:
        """One token through every layer's MLP or expert layer: the
        router, the shared experts, and the held experts at their expected
        share (`routed_share`)."""
        d, f = self.d_model, self.d_ff_expert
        dense = self.first_dense * 2 * 3 * d * self.d_ff
        moe = self.n_moe * 2 * (d * self.n_experts
                                + 3 * d * f * (self.n_shared
                                               + self.routed_share))
        return dense + moe

    def _decode_attn_proj_flops(self) -> int:
        """One decoded token's attention matmuls in absorbed form, one
        layer: q, the latent and rope-key projections, q_nope W_ukᵀ, the
        context times W_uv, and W_o."""
        d, H, R = self.d_model, self.n_heads, self.kv_rank
        return 2 * (d * H * (self.nope + self.rope) + d * R + d * self.rope
                    + H * self.nope * R + H * R * self.v_dim
                    + H * self.v_dim * d)

    def attention_flops(self, pos: int) -> int:
        """Absorbed latent attention of one query at position `pos`, all
        layers: scores over the latent and rope parts, and the weighted
        sum of latents, for pos + 1 keys."""
        per_key = 2 * self.n_heads * (2 * self.kv_rank + self.rope)
        return self.n_layers * per_key * (pos + 1)

    def decode_token_flops(self, pos: int) -> float:
        """Model flops of one decoded token at position `pos`: every
        layer's absorbed attention matmuls and attention, its MLP or
        expert layer (held experts at their expected share), the LM
        head."""
        return (self.n_layers * self._decode_attn_proj_flops()
                + self._mlp_token_flops()
                + 2 * self.d_model * self.vocab + self.attention_flops(pos))

    def prefill_chunk_flops(self, start: int, n: int) -> float:
        """Model flops of a prefill chunk of `n` real tokens at positions
        [start, start + n), in the expanded form a prefill computes: each
        token's projections with its own K and V from its latent (W_uk,
        W_uv), causal attention over nope + rope scores and v-wide sums,
        the MLP or expert layer; the LM head for the chunk's last
        position only."""
        d, H, R = self.d_model, self.n_heads, self.kv_rank
        proj = 2 * (d * H * (self.nope + self.rope) + d * R + d * self.rope
                    + R * H * (self.nope + self.v_dim) + H * self.v_dim * d)
        keys = n * (start + 1) + n * (n - 1) // 2
        attn = 2 * H * (self.nope + self.rope + self.v_dim) * keys
        return (n * (self.n_layers * proj + self._mlp_token_flops())
                + self.n_layers * attn + 2 * self.d_model * self.vocab)

    # ---------------------------------------------------------- the kernel
    def paged_attention_call(self, positions: Iterable[int], page: int):
        """(bytes, flops) that one layer's latent decode call
        (`mla_decode`) needs: each decoding row at position p reads its
        ceil((p + 1) / page) live pages of latents (kv_rank wide) and rope
        keys (rope wide; the pool's zero lanes past it are not counted),
        its latent and rope queries, writes its float32 latent context,
        and reads its page-table entries and position; it computes the
        scores over both parts and the weighted sum of latents over those
        pages.  Rows that decode nothing need nothing."""
        H, R = self.n_heads, self.kv_rank
        nbytes = flops = 0
        for p in positions:
            pages = p // page + 1
            nbytes += pages * page * (R + self.rope) * BF16
            nbytes += H * (R + self.rope) * BF16 + H * R * F32
            nbytes += (pages + 1) * INT32
            flops += 2 * H * (2 * R + self.rope) * pages * page
        return nbytes, flops

    def pool_dims(self, pages: int, page: int):
        """Dims of every latent page pool the decode program holds: the
        c_kv and rope-key pools of the scanned expert layers, [L, pages,
        page, width], and of each leading dense layer, [pages, page,
        width] and, as the layer loop passes it, [1, pages, page, width]."""
        lanes = -(-self.rope // LANES) * LANES   # a TPU pool: whole tiles
        one = [(pages, page, self.kv_rank), (pages, page, lanes)]
        return tuple([(self.n_moe,) + d for d in one]
                     + one + [(1,) + d for d in one])
