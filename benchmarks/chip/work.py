"""Operations and bytes the served work needs, computed from shapes.

These counts are the yardstick of every roofline share and MFU the benchmark
reports. They count the work the algorithm needs, not what an implementation
happens to do: a skipped tile is not work done, and a weight copy the
implementation makes is not work needed.
"""

from __future__ import annotations

import dataclasses

from chip.peaks import Peak

BF16, INT8, F32 = 2, 1, 4


@dataclasses.dataclass(frozen=True)
class Job:
    flops: float
    bytes: float

    def least_seconds(self, peak: Peak) -> float:
        """The least time the chip could take: the larger of the operations
        over peak FLOP/s and the bytes over peak bandwidth."""
        return max(self.flops / peak.bf16_flops,
                   self.bytes / peak.hbm_bytes_per_s)


def reuse_site_job(m: int, k: int, n: int, live: float) -> Job:
    """One reuse-site call, O = O_prev + delta . W, for M rows:

    read x (bf16) and prev_q (int8), write the new codes (int8); read the
    live share of the weight tiles (bf16); read prev_out (f32) and write the
    output (f32); 2.M.K.N.live operations."""
    return Job(
        flops=2.0 * m * k * n * live,
        bytes=(BF16 * m * k + INT8 * m * k + INT8 * m * k
               + BF16 * k * n * live + F32 * m * n + F32 * m * n),
    )


def reuse_gemm_job(m: int, k: int, n: int, live: float) -> Job:
    """The GEMM half of a reuse-site call alone: read the delta (bf16) and
    the live weight tiles (bf16), read prev_out and write the output (f32)."""
    return Job(
        flops=2.0 * m * k * n * live,
        bytes=BF16 * m * k + BF16 * k * n * live + F32 * m * n + F32 * m * n,
    )


@dataclasses.dataclass(frozen=True)
class ModelShape:
    """The sizes of a dense decoder that the model FLOP count needs, read
    from a configuration file's published keys."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    gated: bool

    @classmethod
    def from_published(cls, pub: dict) -> "ModelShape":
        return cls(
            layers=pub["num_hidden_layers"], d_model=pub["hidden_size"],
            heads=pub["num_attention_heads"],
            kv_heads=pub["num_key_value_heads"], head_dim=pub["head_dim"],
            ffn=pub["intermediate_size"], vocab=pub["vocab_size"],
            gated=pub["hidden_act"] == "silu")

    def layer_params(self) -> int:
        """Weights one token multiplies through in the layers."""
        d, q, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        attn = d * (q + 2 * kv) + q * d
        mlp = (3 if self.gated else 2) * d * self.ffn
        return self.layers * (attn + mlp)

    def linear_params(self) -> int:
        """Weights a token multiplies through, the output head included."""
        return self.layer_params() + self.d_model * self.vocab

    def attention_flops(self, keys: int) -> float:
        """QK^T and PV for one query over `keys` positions, all layers."""
        return 4.0 * self.layers * self.heads * self.head_dim * keys

    def decode_flops(self, batch: int, position: int) -> float:
        """One decode step of `batch` rows whose new token sits at
        `position` (0-based), so it attends over position + 1 keys."""
        return batch * (2.0 * self.linear_params()
                        + self.attention_flops(position + 1))

    def prefill_flops(self, batch: int, length: int) -> float:
        """A causal prefill of `batch` prompts of `length` tokens, with
        logits for each prompt's last position only."""
        keys = length * (length + 1) / 2
        return batch * (2.0 * self.layer_params() * length
                        + 2.0 * self.d_model * self.vocab
                        + self.attention_flops(1) * keys)
