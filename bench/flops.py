"""Operations and bytes the algorithm needs, from shapes and live counts.

Never from tiles or padding: a kernel that stops re-streaming weights or
computing padded rows shows a higher roofline share, and no reading can
pass 100%. ``dims`` is ``model.dims_of(config)``; ``item`` is the bytes
of one element of the stored tensors (4 for float32). The kernel counts
read only the shared keys of ``dims`` (``bench/families/granite.py``
lists them); a family's new kernel brings its counts in its own reader.

Conventions: a matmul of (m, k) by (k, n) is 2mkn operations. Causal
attention over S positions costs half the score matrix: one QK^T or PV
product of a head is ``S * S * dh`` operations (2 * S * S/2 * dh).
"""
from __future__ import annotations

import model


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at best: bound by compute or by memory."""
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


# -- whole model ----------------------------------------------------------------
# The architecture's counts are its family's (``bench/families/``): the
# same numbers for every reader that holds a ``dims``.

def matmul_params_per_token(dims: dict) -> int:
    """Weights one token multiplies through in the decoder stack."""
    return model.family_of(dims).matmul_params_per_token(dims)


def head_flops(dims: dict, rows: int) -> float:
    return 2.0 * rows * dims["d"] * dims["V"]


def attn_flops(dims: dict, ctx_sum: float) -> float:
    """Attention's score and value products of every layer for query
    rows whose key counts sum to ``ctx_sum``."""
    return model.family_of(dims).attn_flops(dims, ctx_sum)


def train_step_flops(dims: dict, batch: int, seq: int) -> float:
    """Forward and backward (three times the forward), no recomputation:
    the dense work of every token, causal attention and the LM head."""
    tokens = batch * seq
    fwd = (2.0 * tokens * matmul_params_per_token(dims)
           + attn_flops(dims, batch * seq * seq / 2)
           + head_flops(dims, tokens))
    return 3.0 * fwd


def serve_flops(dims: dict, rows: int, ctx_sum: float,
                head_rows: int) -> float:
    """Forward work of ``rows`` live token rows whose attention spans sum
    to ``ctx_sum``, with the LM head over ``head_rows`` sampled rows."""
    return (2.0 * rows * matmul_params_per_token(dims)
            + attn_flops(dims, ctx_sum) + head_flops(dims, head_rows))


# -- kernels ------------------------------------------------------------------

def touched_experts(E: int, assignments: float) -> float:
    """Experts hit by ``assignments`` uniform draws, expected."""
    return E * (1.0 - (1.0 - 1.0 / E) ** assignments)


def grouped_mlp_fwd(dims: dict, rows: float, item: int) -> tuple:
    """One expert-FFN forward call over ``rows`` assignment rows: three
    (rows, d) x (d, f) products; each touched expert's three matrices
    read once, x read and y written once."""
    d, f, E = dims["d"], dims["f"], dims["E"]
    flops = 6.0 * rows * d * f
    nbytes = item * (touched_experts(E, rows) * 3 * d * f + 2 * rows * d)
    return flops, nbytes


def grouped_mlp_bwd(dims: dict, rows: float, item: int) -> tuple:
    """The backward of one call (dx and dW together): the six products
    of the input and weight gradients; x and dy read, dx written, each
    touched expert's weights read and its gradients written once."""
    d, f, E = dims["d"], dims["f"], dims["E"]
    flops = 12.0 * rows * d * f
    nbytes = item * (touched_experts(E, rows) * 6 * d * f + 3 * rows * d)
    return flops, nbytes


def decode_attention(dims: dict, ctxs, item: int) -> tuple:
    """One layer's single-query attention over decode rows with key
    counts ``ctxs``: every live cached key and value read once."""
    H, Kh, dh = dims["H"], dims["Kh"], dims["dh"]
    n, total = len(ctxs), float(sum(ctxs))
    flops = 4.0 * H * dh * total
    nbytes = item * (2 * Kh * dh * total + 2 * n * H * dh)
    return flops, nbytes


def flash_fwd(dims: dict, batch: int, seq: int, item: int) -> tuple:
    """One layer's causal attention forward: q, k, v read, o written."""
    H, Kh, dh = dims["H"], dims["Kh"], dims["dh"]
    flops = 2.0 * batch * H * seq * seq * dh
    nbytes = item * batch * seq * (2 * H + 2 * Kh) * dh
    return flops, nbytes


def flash_bwd(dims: dict, batch: int, seq: int, item: int) -> tuple:
    """One layer's causal attention backward (dq and dk/dv together),
    flash style: the scores recomputed once, then dP, dQ, dK and dV --
    five products, 2.5 times the forward; q, k, v, o, do read, dq, dk,
    dv written."""
    H, Kh, dh = dims["H"], dims["Kh"], dims["dh"]
    flops = 5.0 * batch * H * seq * seq * dh
    nbytes = item * batch * seq * (4 * H + 4 * Kh) * dh
    return flops, nbytes
