"""Operations that the algorithm needs, as functions of shapes.

Kept with the benchmark so that a share of the peak reads the same work
whatever later implements it: these count what the mathematics requires,
not what an implementation spends (recomputation, padding to a bucket and
split kernels do not count). Nothing here knows an architecture, and the
harness asks none of this of it: a model's FLOPs are its stack's to give
(`forward_flops`, `prefill_flops`, `decode_flops` of
`benchmark/stacks/<stack>.py`), and a kernel's count is a file of its own
under `benchmark/work/`. What is here is the arithmetic that stacks of
dense causal attention share, which such a stack calls with its
`dims(cfg)`: `layers`, `heads`, `kv_heads`, `head_dim`, `width`, `vocab`
and `matmul_params`, the parameters of the matrix products a token passes
through. A stack with experts, latent attention or layers without
attention writes its own.
"""
from __future__ import annotations


def dense_forward_flops(d: dict, new: int, ctx_before: int = 0):
    """Forward FLOPs of `new` tokens of one sequence that already holds
    `ctx_before` tokens: 2 per matrix-product parameter and token, plus
    causal attention (QK^T and PV, 2*dh each per query-key pair and head;
    token j sees ctx_before + j + 1 keys)."""
    pairs = new * ctx_before + new * (new + 1) // 2
    return 2 * d["matmul_params"] * new \
        + d["layers"] * d["heads"] * 4 * d["head_dim"] * pairs


def dense_prefill_flops(d: dict, n: int) -> float:
    """Forward FLOPs of a prompt of `n` tokens as a server needs them: the
    head is applied to the last position only."""
    return dense_forward_flops(d, n) - 2 * d["vocab"] * d["width"] * (n - 1)


def dense_decode_flops(d: dict, rows: int, sum_ctx: int) -> float:
    """Forward FLOPs of one new token for each of `rows` streams whose
    contexts, the new token included, sum to `sum_ctx`."""
    return 2 * d["matmul_params"] * rows \
        + d["layers"] * d["heads"] * 4 * d["head_dim"] * sum_ctx


def train_flops_per_token(forward_flops_of_seq: float, seq: int) -> float:
    """Forward and backward (3 x forward) of one token in a sequence of
    `seq` whose forward pass the stack counts; recomputation is not
    counted."""
    return 3.0 * forward_flops_of_seq / seq


def roofline_seconds(flops: float, bytes_: float, peaks: dict):
    """(least seconds the chip could take, which bound sets it)."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
