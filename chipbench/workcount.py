"""The work a step needs, from its shapes alone: the yardstick of every
roofline and utilization metric.

- Operations are 2*M*K*N per GEMM, however many MXU passes an implementation
  makes; elementwise work is not counted.
- Bytes are the operands at the least storage the requested mode allows:
  weights at 2 bytes per bf16 limb the mode needs, capped at the 4 bytes of
  f32, plus the live KV cache or SSM state that the step reads and writes.

So the shares read the same work whatever implements it, and no
implementation on bf16 storage and the bf16 peak can push them past 100%.
A change that moves GEMMs to the int8 rate needs the peak restated first.
"""
from __future__ import annotations

import dataclasses

#: bf16 limbs each f32-ladder mode splits an operand into
LIMBS = {"M8": 1, "M16": 2, "M24": 3}
KV_BYTES = {"bfloat16": 2, "int8": 1, "float32": 4}


def weight_bytes_per_elem(mode: str) -> int:
    if mode not in LIMBS:
        raise ValueError(f"no storage rule for mode {mode!r}")
    return min(2 * LIMBS[mode], 4)


def gemm_flops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes the work depends on (the program's ArchConfig fields)."""

    family: str
    n_layers: int
    d_model: int
    vocab: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    ssm_state: int = 0
    ssm_head_dim: int = 0
    ssm_expand: int = 0
    ssm_conv: int = 0
    kv_cache_dtype: str = "bfloat16"

    @classmethod
    def of(cls, cfg) -> "Dims":
        return cls(**{f.name: getattr(cfg, f.name) for f in dataclasses.fields(cls)})

    # -- weights per op class: (K, N) of each GEMM a token goes through ------

    def gemms(self) -> dict[str, list[tuple[int, int]]]:
        """Per op class, the (K, N) of every GEMM of one token's forward
        pass, all layers and the vocabulary head included."""
        d, L = self.d_model, self.n_layers
        if self.family == "dense":
            q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
            return {
                "qkv": [(d, q), (d, kv), (d, kv)] * L,
                "out": [(q, d)] * L,
                "mlp_up": [(d, self.d_ff)] * 2 * L,
                "mlp_down": [(self.d_ff, d)] * L,
                "logits": [(d, self.vocab)],
            }
        if self.family == "ssm":
            di = self.ssm_expand * d
            h = di // self.ssm_head_dim
            return {
                "ssm_in": [(d, 2 * di + 2 * self.ssm_state + h)] * L,
                "ssm_out": [(di, d)] * L,
                "logits": [(d, self.vocab)],
            }
        raise ValueError(f"no work count for family {self.family!r}")

    def matmul_params(self) -> int:
        return sum(k * n for shapes in self.gemms().values() for k, n in shapes)

    def weight_bytes(self, mode_for) -> int:
        """Bytes of every GEMM weight at the storage its op's mode allows;
        ``mode_for(op) -> "M8" | "M16" | "M24"``."""
        return sum(k * n * weight_bytes_per_elem(mode_for(op))
                   for op, shapes in self.gemms().items() for k, n in shapes)

    # -- per-token state --------------------------------------------------------

    def kv_bytes_per_token(self) -> int:
        """KV cache bytes one cached token holds over all layers."""
        return (2 * self.n_layers * self.n_kv_heads * self.head_dim
                * KV_BYTES[self.kv_cache_dtype])

    def ssm_state_bytes(self) -> int:
        """f32 SSM and conv state one sequence holds over all layers."""
        di = self.ssm_expand * self.d_model
        h = di // self.ssm_head_dim
        ssm = h * self.ssm_head_dim * self.ssm_state
        conv = (self.ssm_conv - 1) * (di + 2 * self.ssm_state)
        return 4 * self.n_layers * (ssm + conv)

    # -- flops ------------------------------------------------------------------

    def attn_flops(self, q_tokens: int, context: int) -> int:
        """Scores and weighted values of ``q_tokens`` queries against
        ``context`` keys each, all layers (dense family)."""
        if self.family != "dense":
            return 0
        return 2 * 2 * self.n_layers * self.n_heads * self.head_dim * q_tokens * context

    def ssd_flops(self, tokens: int) -> int:
        """State readout and update of the recurrent form, all layers: the
        linear-time work per token (SSM family)."""
        if self.family != "ssm":
            return 0
        di = self.ssm_expand * self.d_model
        return 2 * 2 * self.n_layers * di * self.ssm_state * tokens

    def decode_flops(self, contexts: list[int]) -> int:
        """One decode token for each row, row ``i`` attending over
        ``contexts[i]`` cached tokens (its own included)."""
        rows = len(contexts)
        return (2 * rows * self.matmul_params()
                + sum(self.attn_flops(1, c) for c in contexts)
                + self.ssd_flops(rows))

    def prefill_flops(self, prompt_len: int) -> int:
        """A causal forward pass over one prompt, with the logits of its
        last position only: the one a request needs."""
        causal_pairs = prompt_len * (prompt_len + 1) // 2
        head = self.d_model * self.vocab
        return (2 * prompt_len * (self.matmul_params() - head) + 2 * head
                + self.attn_flops(1, causal_pairs)
                + self.ssd_flops(prompt_len))

    def decode_bytes(self, contexts: list[int], mode_for) -> int:
        """Least bytes of one decode step: the weights once, plus each live
        row's cache read and its new entry written (dense), or its state
        read and written (SSM)."""
        b = self.weight_bytes(mode_for)
        if self.family == "dense":
            b += sum(c for c in contexts) * self.kv_bytes_per_token()
        else:
            b += 2 * len(contexts) * self.ssm_state_bytes()
        return b


def least_seconds(flops: float, nbytes: float, peaks) -> tuple[float, str]:
    """Roofline bound of some work on a chip: the larger of the compute and
    the memory time, and which of the two bounds it."""
    tc, tm = flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes
    return (tc, "compute") if tc >= tm else (tm, "memory")
