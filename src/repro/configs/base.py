"""ModelConfig — the single config dataclass all 10 architectures instantiate.

Every knob any assigned architecture needs is a first-class field; configs are
frozen dataclasses so they hash (jit static args) and print reproducibly.
`reduced()` returns the same *family* at smoke-test scale (small width/depth,
few experts, tiny vocab) per the assignment contract.
"""

from __future__ import annotations

import dataclasses

# A pattern letter's layer kind: its parameter, state and reuse-site group.
LAYER_KINDS = {"M": "mamba", "-": "mlp", "*": "attn"}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    # --- attention pattern ---
    attn_kind: str = "full"      # full | swa | local_global | none
    window: int = 4096           # swa / local-layer window
    local_ratio: int = 0         # local_global: N local layers per 1 global
    causal: bool = True          # False => encoder (bidirectional)
    qk_norm: bool = False
    qkv_bias: bool = False
    rope: str = "rope"           # rope | mrope | none
    rope_theta: float = 1_000_000.0

    # --- mlp ---
    mlp_kind: str = "swiglu"     # swiglu | gelu | relu2

    # --- moe ---
    n_experts: int = 0
    top_k: int = 1
    shared_expert: bool = False
    capacity_factor: float = 1.25

    # --- ssm / hybrid ---
    ssm_kind: str = "none"       # rwkv6 | mamba2
    ssm_state: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 1          # mamba2: B/C groups; head h reads h // (H/G)
    hybrid_attn_every: int = 0   # zamba2: one shared attn block per N ssm blocks
    # One letter per layer, in order (`hybrid_override_pattern`): "M" a
    # Mamba2 mixer, "-" an MLP, "*" attention, each with its own pre-norm
    # and residual. Empty: the stack is uniform superblocks.
    layer_pattern: str = ""

    # --- misc ---
    tie_embeddings: bool = True
    norm_eps: float = 1e-5
    max_seq_len: int = 131072
    frontend: str = "none"       # none | audio | vision
    # bf16 params/compute for the TPU target; smoke tests execute in f32
    # because XLA:CPU cannot *execute* bf16xbf16->f32 dots (it compiles fine).
    param_dtype: str = "bfloat16"

    # --- execution knobs (not architecture) ---
    # §Perf levers for decode memory (see EXPERIMENTS.md):
    # duplicate KV heads up to this count so the cache's head dim divides the
    # TP axis and shards 16-way instead of replicating (vLLM-style GQA
    # replication, but for sharding). 0 = off.
    kv_head_pad_to: int = 0
    # store the KV cache as int8 codes with a fixed scale (halves KV bytes;
    # consistent with the paper's int8 inference setting). off by default.
    kv_cache_quant: bool = False
    kv_quant_scale: float = 0.05
    attn_chunk_q: int = 512      # blockwise-attention query chunk
    attn_chunk_kv: int = 1024    # blockwise-attention kv chunk
    loss_chunk: int = 512        # chunked-xent sequence chunk
    remat: bool = True           # remat each block in training
    # "full": recompute everything in backward (min memory, +1 fwd pass of
    # FLOPs AND of TP all-reduces). "dots": save matmul/psum outputs —
    # backward skips both the recompute FLOPs and the re-communication
    # (§Perf iteration 3 for collective-bound training).
    remat_policy: str = "full"
    scan_layers: bool = True     # scan over stacked superblocks

    def __post_init__(self):
        if self.layer_pattern and (
                len(self.layer_pattern) != self.n_layers
                or set(self.layer_pattern) - set(LAYER_KINDS)):
            raise ValueError(
                f"{self.name}: layer_pattern {self.layer_pattern!r} must be "
                f"{self.n_layers} letters of {''.join(LAYER_KINDS)!r}")

    # ---- derived ----
    @property
    def dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16 if self.param_dtype == "bfloat16" else jnp.float32

    @property
    def d_inner(self) -> int:          # mamba2 expansion
        return 2 * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        if self.ssm_kind == "mamba2":
            return self.d_inner // self.ssm_head_dim
        if self.ssm_kind == "rwkv6":
            return self.d_model // self.ssm_head_dim
        return 0

    @property
    def superblock_layers(self) -> int:
        """How many network layers one scanned superblock covers."""
        if self.attn_kind == "local_global" and self.local_ratio:
            return self.local_ratio + 1
        if self.hybrid_attn_every:
            return self.hybrid_attn_every
        return 1

    @property
    def n_superblocks(self) -> int:
        assert self.n_layers % self.superblock_layers == 0, (
            self.n_layers, self.superblock_layers)
        return self.n_layers // self.superblock_layers

    def kind_layers(self, letter: str) -> int:
        """How many layers of a pattern stack are of the kind `letter`."""
        return self.layer_pattern.count(letter)

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def kv_heads_eff(self) -> int:
        """KV heads as laid out in the cache (after §Perf duplication)."""
        return max(self.n_kv_heads, self.kv_head_pad_to)

    def param_count(self) -> int:
        """Total parameters (used for MODEL_FLOPS = 6·N·D roofline bookkeeping)."""
        d, f, v = self.d_model, self.d_ff, self.vocab
        n = v * d  # embed
        if not self.tie_embeddings:
            n += v * d
        per_attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.mlp_kind == "swiglu":
            per_mlp = 3 * d * f
        else:
            per_mlp = 2 * d * f
        di = self.d_inner
        per_ssm = (d * (2 * di + 2 * self.ssm_groups * self.ssm_state
                        + self.n_ssm_heads) + di * d)
        if self.layer_pattern:
            n += (self.kind_layers("M") * per_ssm
                  + self.kind_layers("-") * per_mlp
                  + self.kind_layers("*") * per_attn)
        elif self.ssm_kind == "rwkv6":
            per_layer = 5 * d * d + d * d + per_mlp  # r,k,v,g,w(+lora approx) + out
            n += self.n_layers * per_layer
        elif self.ssm_kind == "mamba2":
            n_ssm_layers = self.n_layers
            n += n_ssm_layers * per_ssm
            if self.hybrid_attn_every:
                # one shared attn+mlp block reused across applications
                n += per_attn + per_mlp
        else:
            per_layer = per_attn + per_mlp
            if self.n_experts:
                per_layer = per_attn + self.n_experts * per_mlp
                per_layer += d * self.n_experts  # router
                if self.shared_expert:
                    per_layer += per_mlp
            n += self.n_layers * per_layer
        return n

    def active_param_count(self) -> int:
        """Activated parameters per token (MoE: routed top_k + shared)."""
        if not self.n_experts:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        per_mlp = 3 * d * f if self.mlp_kind == "swiglu" else 2 * d * f
        total = self.param_count()
        inactive = self.n_layers * (self.n_experts - self.top_k) * per_mlp
        return total - inactive

    def with_layers(self, n_layers: int) -> "ModelConfig":
        """The same model cut to its first `n_layers` layers. Only depth
        changes: every width stays as published, so per-layer shapes, kernel
        tiles and per-token memory traffic per layer are the real ones."""
        if n_layers < 1 or n_layers % self.superblock_layers:
            raise ValueError(
                f"{self.name}: n_layers={n_layers} must be a positive "
                f"multiple of its {self.superblock_layers}-layer superblock")
        return dataclasses.replace(
            self, n_layers=n_layers,
            layer_pattern=self.layer_pattern[:n_layers])

    def reduced(self) -> "ModelConfig":
        """Same family, smoke-test scale. Keeps every structural feature."""
        # a pattern stack keeps every kind, two Mamba2 layers among them
        pattern = "M-M*-" if self.layer_pattern else ""
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=len(pattern) or 2 * self.superblock_layers,
            layer_pattern=pattern,
            ssm_groups=min(self.ssm_groups, 2),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            head_dim=32,
            d_ff=256,
            vocab=512,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            window=min(self.window, 64),
            max_seq_len=256,
            attn_chunk_q=32,
            attn_chunk_kv=32,
            loss_chunk=32,
            ssm_head_dim=32,
            ssm_state=16,
            param_dtype="float32",
        )
