"""Model configuration dataclasses and the architecture registry.

Every assigned architecture is a ``ModelConfig``, registered with a
reduced smoke-size twin (``get_config`` / ``get_smoke_config``).
"""
from __future__ import annotations

import dataclasses
from typing import Literal


Kind = Literal["dense", "moe", "ssm", "hybrid", "encdec", "vlm", "audio"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    dense_residual: bool = False      # arctic: dense MLP in parallel with MoE
    capacity_factor: float = 1.25


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    head_dim: int = 64
    expand: int = 2                   # d_inner = expand * d_model
    conv_width: int = 4
    chunk: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    kind: Kind
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                 # 0 -> d_model // n_heads
    moe: MoEConfig | None = None
    ssm: SSMConfig | None = None
    # Sliding-window pattern: every `global_every`-th layer is global, others
    # use a `window`-token local attention (gemma3: 5 local : 1 global).
    window: int = 0                   # 0 -> full attention everywhere
    global_every: int = 6
    # Hybrid, zamba2 form: Mamba2 layers with one shared attention block
    # (weights shared across applications, its own MLP) applied after every
    # `shared_attn_every` layers.
    shared_attn_every: int = 0
    # Hybrid, granite form: layer i is a GQA attention layer when
    # i % attn_every == attn_offset and a Mamba2 layer otherwise; every
    # layer is followed by its own MLP. n_layers is whole periods.
    attn_every: int = 0
    attn_offset: int = 0
    # Encoder-decoder (whisper): number of encoder layers; frontend stub emits
    # `enc_len` precomputed frame embeddings.
    n_enc_layers: int = 0
    enc_len: int = 0
    # VLM (internvl): first `n_patches` positions come from the vision stub.
    n_patches: int = 0
    rope_theta: float = 10_000.0
    rope: bool = True                 # False: no position embedding (NoPE)
    # Multipliers (granite): token embeddings are scaled by
    # `embedding_multiplier`, each residual branch by `residual_multiplier`,
    # attention scores by `attention_multiplier` (0 -> 1/sqrt(head_dim)),
    # and logits are divided by `logits_scaling`.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_scaling: float = 1.0
    norm_eps: float = 1e-6
    tie_embeddings: bool = True
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    # Attention implementation: 'xla_flash' (chunked, lowerable everywhere),
    # 'pallas' (TPU kernel), 'naive' (small tests only).
    attn_impl: str = "xla_flash"
    remat: bool = True
    scan_layers: bool = True

    # ------------------------------------------------------------- derived
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 128 (Megatron-style padding) so
        the embedding table shards evenly on the model axis; the loss and
        sampler mask the padding columns."""
        return ((self.vocab + 127) // 128) * 128

    @property
    def layer_period(self) -> int:
        """Layers in one period of the layer pattern; 0 for a uniform
        stack."""
        if self.window > 0:
            return self.global_every
        if self.kind == "hybrid":
            return self.shared_attn_every or self.attn_every
        return 0

    def layer_types(self) -> tuple[str, ...]:
        """Each layer's mixer, ``"mamba"`` or ``"attention"``, in order (a
        zamba2-form shared block is applied between layers, not one)."""
        if self.kind == "ssm" or (self.kind == "hybrid"
                                  and not self.attn_every):
            return ("mamba",) * self.n_layers
        if self.kind == "hybrid":
            return tuple("attention" if i % self.attn_every == self.attn_offset
                         else "mamba" for i in range(self.n_layers))
        return ("attention",) * self.n_layers

    def is_global_layer(self, i: int) -> bool:
        if self.window <= 0:
            return True
        return (i % self.global_every) == self.global_every - 1

    def layer_window(self, i: int) -> int:
        """0 means full/global attention for layer i."""
        return 0 if self.is_global_layer(i) else self.window

    # --------------------------------------------------- parameter counting
    def param_count(self) -> tuple[int, int]:
        """(total params, active params) — analytic, matches init_params."""
        d, v = self.d_model, self.vocab
        embed = v * d
        head = 0 if self.tie_embeddings else v * d
        total = embed + head + d  # final norm
        active = total

        def attn_params() -> int:
            return d * (self.n_heads * self.hd) + 2 * d * (self.n_kv_heads * self.hd) \
                + (self.n_heads * self.hd) * d + 2 * d  # qkv, o, 2 norms

        def mlp_params(ff: int) -> int:
            return 3 * d * ff  # SwiGLU: gate, up, down

        def ssm_params() -> int:
            s = self.ssm
            di = s.d_inner(d)
            nh = s.n_heads(d)
            # in_proj (x, z, B, C, dt), conv, A, D, dt_bias, norm, out_proj
            in_proj = d * (2 * di + 2 * s.d_state + nh)
            return in_proj + s.conv_width * (di + 2 * s.d_state) + 3 * nh + di \
                + di * d + d

        if self.kind == "ssm":
            total += self.n_layers * ssm_params()
            active = total
            return total, active

        if self.kind == "hybrid" and self.attn_every:
            kinds = self.layer_types()
            n_attn = kinds.count("attention")
            # the Mamba2 layers' MLP norm; attn_params counts both norms
            total += n_attn * attn_params() \
                + (len(kinds) - n_attn) * (ssm_params() + d) \
                + len(kinds) * mlp_params(self.d_ff)
            return total, total

        if self.kind == "hybrid":
            per = ssm_params()  # the MLP lives in the shared block only
            total += self.n_layers * per
            if self.shared_attn_every:
                total += attn_params() + mlp_params(self.d_ff)
            active = total
            return total, active

        per_dense = attn_params() + mlp_params(self.d_ff)
        if self.kind in ("encdec", "audio"):
            # encoder blocks + decoder blocks with cross attention + enc norm
            cross = attn_params() - 2 * d + d  # cross qkv/o + its norm
            total += self.n_enc_layers * per_dense \
                + self.n_layers * (per_dense + cross) + d
            return total, total
        if self.moe is None:
            total += self.n_layers * per_dense
            return total, total

        m = self.moe
        router = d * m.n_experts
        expert = 3 * d * m.d_ff_expert
        per_moe = attn_params() + router + m.n_experts * expert
        per_moe_active = attn_params() + router + m.top_k * expert
        if m.dense_residual:
            per_moe += mlp_params(self.d_ff)
            per_moe_active += mlp_params(self.d_ff)
        total += self.n_layers * per_moe
        active = embed + head + d + self.n_layers * per_moe_active
        return total, active


_REGISTRY: dict[str, "ModelConfig"] = {}
_SMOKE: dict[str, "ModelConfig"] = {}


def register(cfg: ModelConfig, smoke: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    _SMOKE[cfg.name] = smoke
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    return _REGISTRY[name]


def get_smoke_config(name: str) -> ModelConfig:
    _ensure_loaded()
    return _SMOKE[name]


def list_archs() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def _ensure_loaded() -> None:
    if _REGISTRY:
        return
    # Importing repro.configs registers every assigned architecture.
    import repro.configs  # noqa: F401


def human(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000:
            return f"{n:.1f}{unit}"
        n /= 1000
    return f"{n:.1f}P"
