"""Operations and bytes the model needs, counted from shapes.

Every function here counts the work the algorithm *needs* for the tokens
the window served: valid rows only, and attention over each query's live
context only.  It never counts what the program happens to compute: the
masked rows of a prefill call that few slots use, the padded vocabulary,
or the unmapped pages a paged step gathers.  So a later change that stops
computing waste raises a share measured against this count, where a count
of what the program computes would only go stale.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int  # the published vocabulary, not the padded one
    page_size: int
    code_bytes: int = 1  # bytes per stored KV element (int8 codes: 1)
    q_bytes: int = 4  # bytes per query / output element (f32)


def linear_flops(s: Shape) -> int:
    """Multiply-adds x 2 of every projection one token passes through:
    q, k, v, o and the three SwiGLU matrices, in every layer."""
    attn = s.d_model * s.head_dim * (s.n_heads + 2 * s.n_kv) \
        + s.n_heads * s.head_dim * s.d_model
    return 2 * s.n_layers * (attn + 3 * s.d_model * s.d_ff)


def attention_flops(s: Shape, ctx: int) -> int:
    """QK^T and PV of one query over ``ctx`` live positions, all layers."""
    return 4 * s.n_layers * s.n_heads * s.head_dim * ctx


def readout_flops(s: Shape) -> int:
    """The readout of one token that emits: d_model x vocab."""
    return 2 * s.d_model * s.vocab


def kernel_bytes(s: Shape, n_queries: int, ctx: int) -> int:
    """Bytes the attention kernel needs for one row of one step: the K and
    V codes and per-(page, head) scales of the row's live context, read
    once, and its ``n_queries`` queries and outputs."""
    pages = math.ceil(ctx / s.page_size)
    kv = 2 * s.n_layers * s.n_kv * (s.head_dim * ctx * s.code_bytes
                                    + pages * 4)
    return kv + 2 * s.n_layers * n_queries * s.n_heads * s.head_dim \
        * s.q_bytes


class WorkCounter:
    """Needed model FLOPs, and the attention kernel's FLOPs and bytes, of
    the rows a window's step calls served."""

    def __init__(self, shape: Shape):
        self.s = shape
        self.model_flops = 0
        self.kernel_flops = 0
        self.kernel_bytes = 0

    def prefill(self, start: int, stop: int, emits: bool) -> None:
        """Prompt positions ``[start, stop)`` of one row in one call;
        ``emits``: the chunk ends the prompt, so its last row is read out."""
        s, n = self.s, stop - start
        # sum of contexts start+1 .. stop, one per query row
        ctx_sum = (start + 1 + stop) * n // 2
        attn = 4 * s.n_layers * s.n_heads * s.head_dim * ctx_sum
        self.model_flops += n * linear_flops(s) + attn \
            + (readout_flops(s) if emits else 0)
        self.kernel_flops += attn
        self.kernel_bytes += kernel_bytes(s, n, stop)

    def decode(self, pos: int) -> None:
        """One decoded row: the token at ``pos`` attends to ``pos + 1``
        positions and its logits are read out."""
        s = self.s
        attn = attention_flops(s, pos + 1)
        self.model_flops += linear_flops(s) + attn + readout_flops(s)
        self.kernel_flops += attn
        self.kernel_bytes += kernel_bytes(s, 1, pos + 1)
