"""Model-architecture description (paper Eq. 5-6: parsed model architecture M).

One dataclass describes every family this framework supports: dense / MoE /
SSM / hybrid / encoder-decoder / VLM-backbone LMs. The Astra cost & memory
models consume this census-level description; the executable JAX models in
:mod:`repro.models` are built from the same object, so the searched strategy
and the executed model can never drift apart.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


# the fields an interleaved model sets: left at their defaults they are not
# serialized, so the wire bytes and cache keys of every other model stay
# those it had before the fields existed
_SPARSE_FIELDS = ("layer_types", "rms_norm_eps", "attention_multiplier",
                  "embedding_multiplier", "residual_multiplier", "logits_scaling",
                  "rope", "ssm_gated_norm")


@dataclasses.dataclass(frozen=True)
class ModelArch:
    name: str
    family: str  # dense | moe | ssm | hybrid | interleaved | encdec | vlm
    num_layers: int
    hidden: int
    heads: int
    kv_heads: int
    ffn: int
    vocab: int
    head_dim: Optional[int] = None  # default hidden // heads
    qk_norm: bool = False
    tie_embeddings: bool = False
    # MoE
    num_experts: int = 0
    top_k: int = 0
    moe_ffn: Optional[int] = None  # expert ffn width (d_ff above is dense-path)
    shared_expert: bool = False
    # SSM (mamba2-style)
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_expand: int = 2
    ssm_chunk: int = 256
    # hybrid: fraction of per-layer compute in the SSM branch (hymba: parallel heads)
    hybrid_parallel_ssm: bool = False
    # encoder-decoder
    encoder_layers: int = 0
    encoder_seq: int = 0  # fixed encoder length (whisper: 1500 frames)
    # modality frontend stub: inputs arrive as precomputed embeddings
    frontend_stub: bool = False
    frontend_seq: int = 0  # e.g. ViT patch tokens prepended to text
    # attention flavor for long context
    sliding_window: int = 0  # 0 => full attention
    # interleaved: the kind of each layer in order, "attention" or "mamba"
    # (a GQA attention or a Mamba-2 mixer, each followed by the SwiGLU MLP
    # every layer has); empty for the families whose layers are all alike
    layer_types: tuple = ()
    rms_norm_eps: float = 1e-6
    # softmax scale of attention; None => 1 / sqrt(head_dim)
    attention_multiplier: Optional[float] = None
    embedding_multiplier: float = 1.0  # embeddings are multiplied by it
    residual_multiplier: float = 1.0  # each residual branch is multiplied by it
    logits_scaling: float = 1.0  # logits are divided by it
    rope: bool = True  # False => no position embedding (NoPE)
    # Mamba-2's gated RMSNorm, rmsnorm(y * silu(z)), before the out projection
    ssm_gated_norm: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden // max(self.heads, 1))
        # a configuration file gives a list; a tuple keeps the arch hashable
        object.__setattr__(self, "layer_types", tuple(self.layer_types))

    def to_dict(self) -> dict:
        """The fields as a dict for the wire (``ModelArch(**d)`` reads it
        back), the interleaved family's left out where at their defaults."""
        d = dataclasses.asdict(self)
        for name in _SPARSE_FIELDS:
            if d[name] == ModelArch.__dataclass_fields__[name].default:
                del d[name]
        if "layer_types" in d:
            d["layer_types"] = list(d["layer_types"])
        return d

    # -- census helpers (used by memory/cost models and roofline) ----------
    @property
    def attn_q_dim(self) -> int:
        return self.heads * self.head_dim

    @property
    def attn_kv_dim(self) -> int:
        return self.kv_heads * self.head_dim

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic in sequence length => long_500k shape is runnable."""
        return self.family in ("ssm", "hybrid") or self.sliding_window > 0

    def kind_params(self, kind: str) -> dict[str, float]:
        """Parameter counts of one layer of ``kind`` ("attention" or "mamba")
        of an interleaved model, split by component: its mixer (the Mamba-2
        mixer with its convolution bias, dt bias, A, D and gated norm gain),
        the MLP and the two norms."""
        h = self.hidden
        out: dict[str, float] = {}
        if kind == "attention":
            out["attn"] = h * (self.attn_q_dim + 2 * self.attn_kv_dim) + self.attn_q_dim * h
        else:
            d_inner = self.ssm_expand * h
            conv_dim = d_inner + 2 * self.ssm_state
            out["ssm"] = (
                h * (2 * d_inner + 2 * self.ssm_state + self.ssm_heads)
                + d_inner * h
                + 5 * conv_dim  # width-4 convolution and its bias
                + 3 * self.ssm_heads  # dt_bias, A_log, D
                + (d_inner if self.ssm_gated_norm else 0)
            )
        out["mlp"] = 3 * h * self.ffn
        out["norms"] = 2 * h
        return out

    def layer_params(self) -> dict[str, float]:
        """Parameter counts per decoder layer, split by component (an
        interleaved model's: the mean over its layers)."""
        if self.layer_types:
            out: dict[str, float] = {}
            for kind in self.layer_types:
                for k, v in self.kind_params(kind).items():
                    out[k] = out.get(k, 0.0) + v / len(self.layer_types)
            return out
        h, ffn = self.hidden, self.ffn
        out: dict[str, float] = {}
        if not self.is_attention_free:
            out["attn"] = h * (self.attn_q_dim + 2 * self.attn_kv_dim) + self.attn_q_dim * h
        if self.family == "moe":
            eff = self.moe_ffn or ffn
            out["moe_experts"] = self.num_experts * 3 * h * eff
            if self.shared_expert:
                out["moe_shared"] = 3 * h * eff
            out["router"] = h * self.num_experts
        elif ffn > 0:
            out["mlp"] = 3 * h * ffn  # gated (SwiGLU-family): up+gate+down
        if self.family in ("ssm", "hybrid"):
            d_inner = self.ssm_expand * h
            nheads = self.ssm_heads or max(d_inner // 64, 1)
            # in_proj (z,x,B,C,dt) + out_proj + conv + A,D
            out["ssm"] = (
                h * (2 * d_inner + 2 * self.ssm_state + nheads)
                + d_inner * h
                + 4 * (d_inner + 2 * self.ssm_state)
                + 2 * nheads
            )
        out["norms"] = 2 * h
        return out

    def params_per_layer(self) -> float:
        return float(sum(self.layer_params().values()))

    def active_params_per_layer(self) -> float:
        """Per-token activated parameters (MoE: top_k experts, not all)."""
        p = dict(self.layer_params())
        if self.family == "moe":
            eff = self.moe_ffn or self.ffn
            p["moe_experts"] = self.top_k * 3 * self.hidden * eff
        return float(sum(p.values()))

    def embedding_params(self) -> float:
        n = self.vocab * self.hidden
        return float(n if self.tie_embeddings else 2 * n)

    def _layers_params(self) -> float:
        """Parameters of all decoder layers, each counted by its kind."""
        if self.layer_types:
            return float(sum(sum(self.kind_params(k).values()) for k in self.layer_types))
        return self.num_layers * self.params_per_layer()

    def total_params(self) -> float:
        n = self._layers_params() + self.embedding_params()
        n += self.encoder_layers * self.params_per_layer()  # enc-dec: same width
        n += self.hidden  # final norm
        return float(n)

    def total_active_params(self) -> float:
        if self.layer_types:  # no experts: every parameter is active
            return self.total_params()
        n = self.num_layers * self.active_params_per_layer() + self.embedding_params()
        n += self.encoder_layers * self.active_params_per_layer()
        n += self.hidden
        return float(n)


@dataclasses.dataclass(frozen=True)
class InputShape:
    """One assigned workload shape."""

    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"

    @property
    def tokens_per_step(self) -> int:
        if self.kind == "decode":
            return self.global_batch  # one new token per sequence
        return self.seq_len * self.global_batch


TRAIN_4K = InputShape("train_4k", 4096, 256, "train")
PREFILL_32K = InputShape("prefill_32k", 32768, 32, "prefill")
DECODE_32K = InputShape("decode_32k", 32768, 128, "decode")
LONG_500K = InputShape("long_500k", 524288, 1, "decode")
ASSIGNED_SHAPES = (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
